"""Serve qwen1.5-0.5B at full width on a TPU and check what comes back.

    python chip_smoke.py              # one chip
    python chip_smoke.py --shards 4   # four chips: the 4-shard path only

One chip: compiles the kernel decode step at full width ahead of time
and checks that the Pallas kernel is in it (``tpu_custom_call``), then
serves 16 requests through ``repro.launch.serve --paged`` — kernel decode,
pipelined engine, random weights from seed 0 — and checks that every
request was served, that the kernel ran compiled, and that 4 served
sequences agree with the dense backend (the parity check inside
``serve``).  ``--shards 4`` runs only the mesh-sharded serve and its
parity check, one pool shard per chip, and checks that the four shards
sit on four distinct devices.

Everything runs in this one process; nothing is started beside it.  It
exits nonzero, printing no result, when JAX finds no TPU or the repo's
``src/`` is not next to this file.  Seconds printed along the way are
information, not metrics.  The last line of standard output is one JSON
object naming the device JAX reports.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
_MODEL = "qwen1_5_0_5b"
_SERVE_ARGS = ["--paged", "--config", _MODEL, "--requests", "16",
               "--batch", "8", "--new-tokens", "16", "--parity-checks", "4"]


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _compile_decode_step(cfg, lanes: int, pages: int, pool_blocks: int):
    """Compile ``_paged_decode_kernel`` for the default device at the
    served width, from shapes alone (the pool as the backend's folded
    device mirror); returns its HLO text."""
    import jax
    import jax.numpy as jnp
    from repro.kvcache.backend import _paged_decode_kernel
    from repro.models import lm

    params = jax.eval_shape(lambda: lm.init(cfg, jax.random.key(0)).params)
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, pool_blocks, 16, cfg.n_kv_heads * cfg.d_head),
        cfg.kvdtype)
    lowered = _paged_decode_kernel.lower(
        params, cfg, jax.ShapeDtypeStruct((lanes, 1), jnp.int32), kv, kv,
        jax.ShapeDtypeStruct((lanes, pages), jnp.int32),
        jax.ShapeDtypeStruct((lanes,), jnp.int32), None, None)
    return lowered.compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shards", type=int, default=1, choices=(1, 4),
                    help="4: serve one pool shard per chip on a 4-chip "
                         "host, and nothing else")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if not os.path.isdir(os.path.join(_ROOT, "src", "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(_ROOT, "src"))
    from repro import configs
    from repro.kernels import pallas_interpret
    from repro.launch import serve

    cache_dir = serve.place_compile_cache()
    print(f"[chip_smoke] device={devices[0].device_kind} "
          f"count={len(devices)} pallas_interpret={pallas_interpret()} "
          f"compile_cache={cache_dir}")
    _check(not pallas_interpret(), "Pallas would interpret on a TPU")

    serve_args = list(_SERVE_ARGS)
    if args.shards > 1:
        serve_args += ["--shards", str(args.shards)]
    else:
        cfg = configs.get(_MODEL)
        # 8 lanes, 2 pages: the step that decodes a 24-token prompt's
        # first new token across a full batch
        tc = time.perf_counter()
        hlo = _compile_decode_step(cfg, lanes=8, pages=2, pool_blocks=256)
        _check("tpu_custom_call" in hlo,
               "the compiled decode step holds no Pallas kernel")
        print(f"[chip_smoke] decode step holds tpu_custom_call: "
              f"compile_s={time.perf_counter() - tc:.1f} "
              f"setup_s={time.perf_counter() - t0:.1f}")

    t1 = time.perf_counter()
    res = serve.main(serve_args)
    print(f"[chip_smoke] serve_s={time.perf_counter() - t1:.1f} "
          f"(serve setup_s={res['setup_s']:.1f} "
          f"engine_s={res['serve_s']:.1f})")
    _check(res["served"] == 16, f"served {res['served']}/16 requests")
    _check(res["decode"] == "kernel", f"decode ran as {res['decode']!r}")
    _check(res["kernel_interpret"] is False,
           "the backend's kernel decode would interpret")
    _check(res["parity_checked"] == 4 and res["parity_ok"] == 4,
           f"parity {res['parity_ok']}/{res['parity_checked']}")
    if args.shards > 1:
        _check(len(set(res["devices"])) == args.shards,
               f"shards on devices {res['devices']}")
    print(f"[chip_smoke] served {res['served']}/16, dense-vs-kernel parity "
          f"{res['parity_ok']}/{res['parity_checked']}, "
          f"shard devices {res['devices'] or [devices[0].id]}, "
          f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
