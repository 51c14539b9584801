"""End-to-end training driver.

``python -m repro.launch.train --arch qwen1_5_0_5b --smoke --steps 50``

Wires together: config registry -> model init (sharded) -> data pipeline ->
train step (pjit) -> checkpoint/restart + heartbeat/straggler supervision.
On CPU it runs reduced configs; on a real pod the same file runs the full
configs (the mesh adapts to the available devices).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.pipeline import DataConfig, TokenStream
from repro.ft import checkpoint as ckpt
from repro.ft.manager import RunSupervisor
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.models import lm
from repro.optim import adamw as optim
from repro.sharding import context as shctx, rules
from repro.train.step import TrainFlags, make_train_step


def pick_mesh():
    n = len(jax.devices())
    if n >= 512:
        return make_production_mesh(multi_pod=True)
    if n >= 256:
        return make_production_mesh()
    # largest (data, model) split of available devices
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-interval", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    mesh = pick_mesh()
    opt_cfg = optim.OptConfig(lr=args.lr, total_steps=args.steps,
                              warmup_steps=max(args.steps // 20, 5))
    sup = RunSupervisor(args.workdir, ckpt_interval=args.ckpt_interval)

    with shctx.use_mesh(mesh):
        cap = {}

        def mk(key):
            b = lm.init(cfg, key)
            cap["specs"] = b.specs
            return b.params

        abs_params = jax.eval_shape(mk, jax.random.key(0))
        pshard = rules.param_shardings(cap["specs"], abs_params, mesh)
        params = jax.jit(mk, out_shardings=pshard)(jax.random.key(0))
        opt_state = jax.jit(
            lambda p: optim.opt_init(p, opt_cfg),
        )(params)

        start_step = 0
        last = ckpt.latest_step(sup.ckpt_dir) if args.resume else None
        if last is not None:
            print(f"[train] resuming from step {last}")
            state = ckpt.restore({"p": params, "o": opt_state, "s": 0},
                                 last, sup.ckpt_dir)
            params, opt_state = state["p"], state["o"]
            start_step = int(np.asarray(state["s"]))

        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg,
                            TrainFlags(remat=False,
                                       microbatches=args.microbatches)),
            donate_argnums=(0, 1))

        data = TokenStream(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                      global_batch=args.batch),
                           start_step=start_step)

        losses = []
        for step in range(start_step, args.steps):
            batch_np = next(data)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
            if cfg.frontend:
                batch["frontend"] = jnp.zeros(
                    (args.batch, cfg.frontend_seq, cfg.d_model),
                    cfg.cdtype)
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            losses.append(loss)
            events = sup.after_step(step, dt)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step} loss {loss:.4f} "
                      f"({dt*1e3:.0f} ms)"
                      + (f" events={events}" if any(events.values()) else ""))
            if sup.should_checkpoint(step):
                t0 = time.time()
                ckpt.save({"p": params, "o": opt_state,
                           "s": jnp.asarray(step + 1)}, step + 1,
                          sup.ckpt_dir)
                sup.record_ckpt_time(time.time() - t0)
        print(f"[train] done: first loss {losses[0]:.4f} "
              f"final loss {losses[-1]:.4f}")
        return losses


if __name__ == "__main__":
    main()
