"""One run of one cell: build the served system, warm it up, drive the
traffic on the wall clock, check what it served, print the result.

The served path is the program's: ``make_backend(cfg, "paged")`` →
``PagedBackend`` (kernel decode) → ``ServeEngine`` (pipelined) →
``MarsScheduler``, with weights made here from the seed.  The harness
calls ``engine.submit`` and ``engine.step(now=...)`` itself and stamps
each output token when the ``step`` that produced it returns.  The
program's ``Observer`` stays off.  Spans are taken here, by wrapping the
engine's, scheduler's and backend's methods, and go into the profiler's
trace as ``bench.*`` annotations.

``build`` and ``serve`` are separate so that the tools under
``bench/tools`` can serve several windows from one set-up.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Optional

from benchkit import check, peaks, refmodel, spec as spec_mod, \
    trace as trace_mod, traffic

# a program obtained, compiled or read from the persistent cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_SECONDS = 5.0          # the traced run traces the window's last 5 s
WARMUP_RID = 1_000_000_000   # warm-up requests' ids start here


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Req:
    """One request of the cell's traffic, as the harness saw it."""
    rid: int
    prompt: tuple
    max_new: int
    due: float                      # when it was due (host clock)
    submitted: Optional[float] = None
    times: list = dataclasses.field(default_factory=list)
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Run:
    """Everything a metric reader reads (``bench/metrics/*.py``)."""
    w0: float                       # window, host clock
    w1: float
    setup_s: float
    requests: list
    steps: list                     # (start, end, tokens made)
    prefills: list                  # (start, end, prompt tokens, rid)
    decodes: list                   # (start, lanes' cached tokens, staged)
    model: refmodel.Model
    serve: dict
    peaks: Optional[dict] = None
    trace: Optional[dict] = None    # trace.load record
    traced: Optional[tuple] = None  # traced window, host clock

    def in_window(self, t: float) -> bool:
        return self.w0 <= t < self.w1


class Recorder:
    """Host spans around the program's serving calls.  Each wrapped
    method also writes a ``bench.<name>`` annotation into the profiler's
    trace when one is being taken."""

    def __init__(self):
        self.prefills: list = []
        self.decodes: list = []
        # prompt -> ids of the submitted requests that carry it, oldest
        # first (prompts can repeat: the prefill takes the oldest)
        self.prompt_rid: dict = collections.defaultdict(collections.deque)

    @staticmethod
    def wrap(obj, name: str, after=None):
        import jax
        fn = getattr(obj, name)
        label = f"bench.{name}"

        def wrapped(*args, **kw):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(label):
                out = fn(*args, **kw)
            if after is not None:
                after(t0, time.perf_counter(), args, kw)
            return out

        setattr(obj, name, wrapped)

    def attach(self, engine, backend):
        def on_prefill(t0, t1, args, kw):
            prompt = tuple(args[1] if len(args) > 1 else kw["prompt"])
            rids = self.prompt_rid.get(prompt)
            self.prefills.append((t0, t1, len(prompt),
                                  rids.popleft() if rids else None))

        def on_dispatch(t0, t1, args, kw):
            ctx = [backend.table(s).num_tokens for s in kw["sids"]]
            self.decodes.append((t0, ctx, backend.staged_blocks_last_step))

        self.wrap(backend, "new_seq", on_prefill)
        self.wrap(backend, "dispatch_decode", on_dispatch)
        self.wrap(backend, "sync")
        self.wrap(backend, "flush")
        self.wrap(engine.scheduler, "schedule_batch")


class Tracer:
    """The profiler over the last ``TRACE_SECONDS`` of the window."""

    def __init__(self, enabled: bool, start_at: float, stop_at: float):
        self.enabled, self.start_at, self.stop_at = enabled, start_at, \
            stop_at
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if enabled \
            else None
        self.on = False
        self.bounds = None
        self._ann = None

    def poll(self, now: float) -> None:
        import jax
        if not self.enabled:
            return
        if not self.on and self.bounds is None and now >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            self._ann.__enter__()
            self.on, self.bounds = True, (time.perf_counter(), None)
        elif self.on and now >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            self.bounds = (self.bounds[0], time.perf_counter())
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False

    def record(self) -> Optional[dict]:
        if not self.enabled:
            return None
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        rec = trace_mod.load(paths[0]) if paths else None
        shutil.rmtree(self.dir, ignore_errors=True)
        return rec


class Driver:
    """Feeds the engine and stamps what comes out."""

    def __init__(self, engine, request_cls, recorder: Recorder, seed: int,
                 vocab: int):
        self.engine = engine
        self.Request = request_cls
        self.rec = recorder
        self.seed = seed
        self.vocab = vocab
        self.active: dict = {}
        self.queue: collections.deque = collections.deque()
        self.steps: list = []

    def make(self, mix: dict, s: traffic.Spec, due: float) -> Req:
        return Req(s.rid, traffic.prompt(mix, self.seed, s, self.vocab),
                   s.max_new, due)

    def offer(self, now: float) -> None:
        while self.queue:
            r = self.queue[0]
            req = self.Request(rid=r.rid, prompt=r.prompt, arrival=r.due,
                               max_new=r.max_new)
            if not self.engine.submit(req):
                return
            self.queue.popleft()
            r.submitted = now
            self.rec.prompt_rid[r.prompt].append(r.rid)
            self.active[r.rid] = r

    def idle(self) -> bool:
        e = self.engine
        return not e.running and not e.paused and not len(e.scheduler)

    def step(self) -> list:
        """One engine step; returns the requests that finished in it."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            made = self.engine.step(now=t0)
        t1 = time.perf_counter()
        self.steps.append((t0, t1, made))
        for s in self.engine.running:
            r = self.active.get(s.rid)
            if r is not None:
                while len(r.times) < s.n_generated:
                    r.times.append(t1)
        finished = []
        for rid in [rid for rid in self.active
                    if rid in self.engine.finished]:
            r = self.active.pop(rid)
            r.out = list(self.engine.finished.pop(rid)[0])
            while len(r.times) < len(r.out):
                r.times.append(t1)
            r.done = True
            finished.append(r)
        return finished


def _warm_up(driver: Driver, mix: dict, max_lanes: int) -> None:
    """Run once every program the cell's traffic can reach: a prefill at
    each prompt length on the mix's ladder, a decode step at every
    power-of-two lane count up to ``max_lanes`` against contexts from
    every rung (the program pads a decode to powers of two in lanes and
    pages), a step at every lane count from 1 to ``max_lanes`` (the
    engine orders its lanes with device operations on the unpadded lane
    count), and a few prefills of the longest prompt landing together
    (the block-staging sizes they make)."""
    eng, Request = driver.engine, driver.Request
    ladder = sorted(mix["prompt"]["ladder"])
    lanes = [1 << i for i in range(max_lanes.bit_length())
             if 1 << i <= max_lanes]
    rid = [WARMUP_RID]

    def req(length, **kw):
        rid[0] += 1
        return Request(rid=rid[0], prompt=traffic.tokens(
            driver.seed, rid[0], length, driver.vocab), **kw)

    def submit(r):
        while not eng.submit(r):
            eng.step(now=time.perf_counter())

    def drain():
        while not driver.idle():
            eng.step(now=time.perf_counter())

    for rung in ladder:
        submit(req(rung, max_new=2 * len(lanes) + 2))
        eng.step(now=time.perf_counter())          # its prefill
        for b in lanes:
            if b > 1:
                submit(req(ladder[0], n_samples=b - 1, max_new=2))
                eng.step(now=time.perf_counter())  # their prefill
            eng.step(now=time.perf_counter())      # b lanes decode
        drain()
    for n in range(1, max_lanes + 1):
        submit(req(ladder[0], n_samples=n, max_new=2))
        drain()
    for k in (2, 4, 8):
        for _ in range(k):
            submit(req(ladder[-1], max_new=2))
        drain()
    eng.finished.clear()


def _open_loop(driver: Driver, mix: dict, seed: int, t_base: float,
               w0: float, w1: float, tracer) -> list:
    specs = traffic.open_loop(mix, seed, w1 - w0)
    reqs, i = [], 0
    drain_until = w1 + traffic.drain_s(mix)
    while True:
        now = time.perf_counter()
        tracer.poll(now)
        while i < len(specs) and t_base + specs[i].due <= now:
            r = driver.make(mix, specs[i], t_base + specs[i].due)
            reqs.append(r)
            driver.queue.append(r)
            i += 1
        driver.offer(now)
        if i == len(specs) and now >= w1:
            owed = [r for r in reqs if r.due >= w0 and not r.done]
            if not owed or now >= drain_until:
                break
        if driver.idle():
            if driver.queue:
                raise RuntimeError(f"request {driver.queue[0].rid} can "
                                   f"never be admitted into an empty "
                                   f"engine")
            nxt = t_base + specs[i].due if i < len(specs) else now + 1e-3
            time.sleep(min(max(nxt - now, 0.0), 0.01))
            continue
        driver.step()
    tracer.stop()
    return reqs


def _closed_loop(driver: Driver, mix: dict, seed: int, t_base: float,
                 w1: float, tracer) -> list:
    specs = iter(traffic.closed_loop(mix, seed))
    reqs = []

    def new(now):
        r = driver.make(mix, next(specs), now)
        reqs.append(r)
        driver.queue.append(r)

    for _ in range(int(mix["clients"])):
        new(t_base)
    while True:
        now = time.perf_counter()
        tracer.poll(now)
        driver.offer(now)
        if now >= w1:
            break
        for _ in driver.step():
            new(time.perf_counter())
    tracer.stop()
    return reqs


def drain(driver: Driver) -> None:
    """Serve what is still queued or running to the end, unrecorded."""
    while driver.queue or not driver.idle():
        driver.offer(time.perf_counter())
        driver.step()
    driver.engine.finished.clear()
    driver.active.clear()


def _place_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.serve.place_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<checkout>/.jax_cache``), with every program cached,
    however quickly it compiled."""
    from repro.launch.serve import place_compile_cache
    path = place_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _device_check(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"needs {chips} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)")
    return devs


@dataclasses.dataclass
class Setup:
    """What ``start`` found: the cell, its files, the devices and the
    compile log."""
    root: pathlib.Path
    spec: spec_mod.Spec
    cell: dict
    conf: dict
    mix: dict
    devs: list
    compiles: list
    cache_hits: list


@dataclasses.dataclass
class System:
    """One served system, built from one seed's weights."""
    cfg: object
    params: object
    backend: object
    engine: object
    driver: Driver
    recorder: Recorder
    phases: list


def start(root, cell_name: str, *, require_tpu: bool = True,
          spec: Optional[spec_mod.Spec] = None) -> Setup:
    """Read the cell's files, place the compile cache, start JAX and look
    for the chips.  Raises ``NoAccelerator`` when they are not there and
    ``FileNotFoundError`` when the program is not beside the benchmark."""
    root = pathlib.Path(root)
    spec = spec or spec_mod.Spec(root)
    cell = spec.cell(cell_name)
    conf, mix = spec.config(cell), spec.traffic(cell)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not at {src}/repro")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    if require_tpu:
        _place_cache()
    import jax
    compiles: list = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(time.perf_counter())
        if ev == COMPILE_EVENT else None)
    cache_hits: list = []
    jax.monitoring.register_event_listener(
        lambda ev, **kw: cache_hits.append(time.perf_counter())
        if ev == CACHE_HIT_EVENT else None)
    devs = _device_check(int(cell["chips"]), require_tpu)
    return Setup(root, spec, cell, conf, mix, devs, compiles, cache_hits)


def build(setup: Setup, seed: int, *, warm_up: bool = True) -> System:
    """Weights from ``seed``, the backend, the engine and the scheduler,
    and (``warm_up``) one run of every program the traffic reaches."""
    from repro.kvcache.backend import make_backend
    from repro.models.config import ModelConfig
    from repro.serve.engine import PagedLM, ServeEngine
    from repro.serving.scheduler import MarsScheduler, Request
    from benchkit import weights

    phases = [("jax", time.perf_counter())]
    cfg = ModelConfig(**setup.conf["model"])
    serve = setup.conf["serve"]
    params = weights.make_params(cfg, seed)
    phases.append(("weights", time.perf_counter()))
    backend = make_backend(cfg, "paged", num_blocks=serve["num_blocks"],
                           block_size=serve["block_size"])
    engine = ServeEngine(backend.pool, MarsScheduler(pool=backend.pool),
                         PagedLM(params, cfg, backend),
                         max_lanes=serve["max_lanes"])
    recorder = Recorder()
    recorder.attach(engine, backend)
    driver = Driver(engine, Request, recorder, seed, cfg.vocab)
    phases.append(("backend", time.perf_counter()))
    if warm_up:
        _warm_up(driver, setup.mix, serve["max_lanes"])
        phases.append(("warm_up", time.perf_counter()))
    return System(cfg, params, backend, engine, driver, recorder, phases)


def serve(system: System, mix: dict, seed: int, seconds: float,
          tracer=None) -> tuple:
    """Serve ``mix`` for its ramp and then a window of ``seconds``;
    returns ``(requests, w0, w1)``, host clock."""
    t_base = time.perf_counter()
    w0 = t_base + float(mix.get("ramp_s", 0))
    w1 = w0 + float(seconds)
    tracer = tracer or Tracer(False, 0.0, 0.0)
    if mix["loop"] == "open":
        reqs = _open_loop(system.driver, mix, seed, t_base, w0, w1, tracer)
    elif mix["loop"] == "closed":
        reqs = _closed_loop(system.driver, mix, seed, t_base, w1, tracer)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return reqs, w0, w1


def counts(mix: dict, reqs: list, w0: float, w1: float) -> tuple:
    """``(attempted, failed)``: open loop, the requests due in the window
    and those of them not completed by the drain limit; closed loop, the
    requests started in the window, none failed (an error ends the
    run)."""
    if mix["loop"] == "open":
        window = [r for r in reqs if w0 <= r.due < w1]
        return len(window), sum(not r.done for r in window)
    window = [r for r in reqs
              if r.submitted is not None and w0 <= r.submitted < w1]
    return len(window), 0


def free(system: System) -> list:
    """Drop the served system's device state (the pool mirrors and the
    program's buffers); returns the steps it recorded."""
    steps = system.driver.steps
    system.backend = system.engine = system.driver = None
    gc.collect()
    return steps


def checked_gap(setup: Setup, system: System, reqs: list, seed: int,
                prec: str = "f32") -> tuple:
    """``(widest gap, requests compared, served tokens compared)`` over
    the sample of finished requests (see ``check``)."""
    lim = setup.conf["check"]
    sample = check.sample([r for r in reqs if r.done], seed,
                          lim["min_tokens"], lim["min_requests"],
                          lim["max_requests"])
    if not sample:
        return float("inf"), 0, 0
    m = refmodel.Model.from_config(setup.conf["model"])
    ref = setup.spec.reference(setup.cell)
    s_pad = max(setup.mix["prompt"]["ladder"]) + traffic.max_output(
        setup.mix)
    gap, n = check.widest_gap(ref.logits, system.params, m, sample, s_pad,
                              traffic.max_output(setup.mix), prec)
    return gap, len(sample), n


def run_cell(root, cell_name: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, require_tpu: bool = True,
             spec: Optional[spec_mod.Spec] = None, keep_trace=None,
             out=sys.stdout, err=sys.stderr) -> dict:
    """Run ``cell_name`` once and print its result line; returns it.
    Raises ``NoAccelerator`` before printing anything when the chips are
    not there.  ``keep_trace``: a path to write the traced run's reduced
    trace record to (``trace.load``), as JSON."""
    setup = start(root, cell_name, require_tpu=require_tpu, spec=spec)
    cell, mix, devs = setup.cell, setup.mix, setup.devs
    metric_defs = setup.spec.metrics(cell, trace)
    system = build(setup, seed)
    n_setup = (len(setup.compiles), len(setup.cache_hits))

    w_start = time.perf_counter() + float(mix.get("ramp_s", 0))
    tracer = Tracer(trace, w_start + max(seconds - TRACE_SECONDS, 0.0),
                    w_start + seconds)
    reqs, w0, w1 = serve(system, mix, seed, seconds, tracer)
    n_compiles = sum(1 for t in setup.compiles if w0 <= t < w1)
    print(f"window_compiles={n_compiles}", file=out, flush=True)
    mem = devs[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    prev = t_process
    for name, t in system.phases + [("ramp", w0)]:
        print(f"setup {name} {t - prev:.3f}s", file=err)
        prev = t
    win = [s for s in system.driver.steps if w0 <= s[0] < w1]
    print(f"setup programs {n_setup[0]} ({n_setup[1]} from the persistent "
          f"cache); window: {len(win)} steps, "
          f"{sum(s[2] for s in win)} tokens, mean step "
          f"{1e3 * sum(s[1] - s[0] for s in win) / max(len(win), 1):.2f} "
          f"ms, {sum(r.done for r in reqs)}/{len(reqs)} requests done, "
          f"{len(system.driver.queue)} waiting to be offered, peak "
          f"{peak} bytes", file=err, flush=True)

    # the program's state goes before the reference runs on the chip
    steps = free(system)
    mem = devs[0].memory_stats() or {}
    print(f"in use before the reference: {mem.get('bytes_in_use')} bytes",
          file=err, flush=True)
    run = Run(w0=w0, w1=w1,
              setup_s=w0 - t_process, requests=reqs, steps=steps,
              prefills=system.recorder.prefills,
              decodes=system.recorder.decodes,
              model=refmodel.Model.from_config(setup.conf["model"]),
              serve=setup.conf["serve"],
              traced=tracer.bounds if trace else None)
    attempted, failed = counts(mix, reqs, w0, w1)

    limit = setup.conf["check"]["max_logit_gap"]
    gap, n_req, n_tok = checked_gap(setup, system, reqs, seed)
    print(f"checked {n_req} requests, {n_tok} served tokens",
          file=out, flush=True)
    numbers = {"max_logit_gap": {"value": gap, "limit": limit}}
    correct = n_req > 0 and gap <= limit

    if trace:
        run.trace = tracer.record()
        if keep_trace is not None and run.trace is not None:
            with open(keep_trace, "w", encoding="utf-8") as fh:
                json.dump(run.trace, fh)
        run.peaks = peaks.peaks(devs[0].device_kind) if require_tpu \
            else None
    metrics = {}
    for md in metric_defs:
        value = setup.spec.reader(md)(run)
        if value is not None:
            metrics[md["name"]] = {"value": value, "unit": md["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = trace_mod.busy_s(run.trace)
        device["window_s"] = trace_mod.window_s(run.trace)
        bd = trace_mod.breakdown(run.trace)
        if bd is not None:
            result["breakdown"] = bd
    result["check"] = numbers
    for name, v in numbers.items():
        print(f"check {name}={v['value']} limit={v['limit']}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result
