"""End-to-end training launcher.

Runs a real training loop for any ``--arch`` (smoke-scaled by default so it
trains on this CPU container; ``--full`` uses the published config for fleet
runs) with the whole substrate engaged: deterministic host-sharded data,
sharded AdamW, checkpoint/restart, straggler watchdog, optional failure
injection, optional int8 gradient compression, microbatched grad accum.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a fleet the same script runs under ``jax.distributed.initialize()`` with
the production mesh from ``mesh.py``; on 1 CPU device the mesh is (1, 1).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import make_dataset
from repro.models import steps as S
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.optim.schedules import cosine_with_warmup
from repro.runtime import sharding as SH
from repro.runtime.compress import compress_grads, ef_init
from repro.runtime.device import enable_compile_cache
from repro.runtime.ft import FailureInjector, FaultTolerantRunner, StragglerWatchdog


def build(args, registry=None):
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.smoke()
    lr_fn = cosine_with_warmup(args.lr, warmup=max(10, args.steps // 20),
                               total=args.steps)
    step_fn = S.make_train_step(
        cfg, lr_fn, n_microbatches=args.microbatches,
        weight_decay=args.weight_decay, registry=registry)
    return cfg, step_fn


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="published config (fleet scale); default smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--weight-decay", type=float, default=0.1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at", type=int, nargs="*", default=None,
                    help="inject failures at these steps (FT demo)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default="auto",
                    help="'auto' (all devices on the data axis) | 'single' | 'multi'")
    ap.add_argument("--registry", default=None,
                    help="tuned-schedule registry JSON (dense sites consult "
                         "it at trace time; default: plain XLA path)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    registry = None
    if args.registry:
        from repro.core.registry import ScheduleRegistry
        registry = ScheduleRegistry(args.registry)
    cfg, raw_step = build(args, registry=registry)
    if args.mesh == "auto":
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    else:
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    ds = make_dataset(cfg, None, seed=args.seed, global_batch=args.batch,
                      seq_len=args.seq)

    params = T.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = adamw_init(params, keep_master=cfg.dtype != "float32")
    ef = ef_init(params) if args.compress_grads else None

    if args.compress_grads:
        def step_with_ef(state, batch):
            params, opt, ef = state
            lr_fn = cosine_with_warmup(args.lr, 10, args.steps)
            loss_fn = S.make_loss_fn(cfg, registry=registry)
            (_, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads, ef = compress_grads(grads, ef)
            from repro.optim import adamw_update
            p2, o2, gn = adamw_update(grads, opt, params, lr_fn(opt.step),
                                      weight_decay=args.weight_decay,
                                      max_grad_norm=1.0)
            return (p2, o2, ef), dict(metrics, grad_norm=gn)

        step_jit = jax.jit(step_with_ef, donate_argnums=(0,))
        state = (params, opt, ef)
    else:
        step_jit = jax.jit(lambda st, b: _pack(raw_step(st[0], st[1], b)),
                           donate_argnums=(0,))
        state = (params, opt)

    def _pack(r):
        p, o, m = r
        return (p, o), m

    def batch_fn(step):
        return {k: jnp.asarray(v) for k, v in ds.batch(step).items()}

    ckpt = CheckpointManager(args.ckpt_dir or "/tmp/repro_ckpt",
                             keep_n=3)
    injector = FailureInjector(args.fail_at) if args.fail_at else None
    watchdog = StragglerWatchdog(n_hosts=max(1, mesh.shape.get("data", 1)))
    runner = FaultTolerantRunner(
        step_jit, ckpt, save_every=args.save_every, injector=injector,
        extras_fn=lambda s: {"data_seed": args.seed, "arch": cfg.name})

    # resume if a checkpoint exists
    start = 0
    restored = ckpt.restore_latest(state)
    if restored is not None:
        start, state, extras = restored
        print(f"[train] resumed from step {start}", flush=True)

    t0 = time.time()
    losses = []

    def log_hook(step, m):
        losses.append(m["loss"])
        # single-host container: per-host time == step time
        watchdog.record(step, np.array([m["step_time_s"]]))
        if step % args.log_every == 0:
            tput = args.batch * args.seq / m["step_time_s"]
            print(f"[train] step {step:5d} loss {m['loss']:.4f} "
                  f"ce {m.get('ce', float('nan')):.4f} "
                  f"gnorm {m['grad_norm']:.3f} tok/s {tput:,.0f}", flush=True)

    with mesh, SH.use_mesh(mesh):
        state, final_step, metrics = runner.run(
            state, batch_fn, start, args.steps - start, hooks=[log_hook])

    dt = time.time() - t0
    summary = {
        "arch": cfg.name, "steps": final_step, "wall_s": round(dt, 1),
        "loss_first": losses[0] if losses else None,
        "loss_last": float(np.mean(losses[-5:])) if losses else None,
        "restarts": runner.restarts,
        "straggler_events": len(watchdog.events),
        "tokens_per_s": round(args.batch * args.seq * len(losses) / dt, 1),
    }
    print("[train] done:", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
