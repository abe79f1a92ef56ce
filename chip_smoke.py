#!/usr/bin/env python3
"""Chip smoke test: tune -> registry -> serve for musicgen-large at full
width on one TPU chip, in one process.

    python3 chip_smoke.py

Run it from the repository root on a machine with a TPU.  Phases:

1. device   — print platform, device kind and count; anything but a TPU
               exits non-zero (there is no CPU fallback).
2. tune     — ``launch.tune.tune_model`` on musicgen-large's published
               config (48 layers, d_model 2048) with the measured ``jax``
               backend: harvest the serving steps' dots, tune the top
               contractions by FLOP share through the compiled Pallas
               matmul, write a fresh registry and kernel store under
               ``.chip_smoke/`` (wiped first).
3. serve    — ``launch.serve.serve_once`` with that registry at the same
               batch / prompt / max length the tune harvested.
4. kernels  — every registry entry through ``kernels.ops.tuned_einsum``
               (the compiled kernel with the tuned block) against XLA's
               dot on random bf16 operands.
5. compare  — one decode step's logits with the registry (routed through
               the Pallas kernel) against the same step on the plain XLA
               path, same weights and inputs; both steps are also timed.

Every phase prints its numbers on a line of its own.  The run fails
(non-zero exit, no result line) if no contraction was tuned, no dot was
routed, a kernel failed to export or load, the registry's hardware stamp is
not the device kind, a tuned kernel differs from XLA's dot by more than
``KERNEL_TOL`` or the logits by more than ``LOGITS_TOL``.  The last line of
stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".chip_smoke")

ARCH = "musicgen-large"
SHAPES = {"batch": 4, "prompt_len": 32, "max_len": 128}
REQUESTS = 8
GEN_LEN = 16
MAX_CONTRACTIONS = 6  # the six dense shapes: >99% of the serving FLOPs
TUNE_BUDGET_S = 120.0
TUNE_EVAL_BUDGET = 32
BF16_EPS = 2.0 ** -8  # 8 significant bits
#: one tuned kernel vs XLA's dot, max |difference| over max |XLA output|:
#: both accumulate in f32 and round once to bf16, so they differ by at most
#: one bf16 step of the output
KERNEL_TOL = 2 * BF16_EPS
#: routed-vs-XLA decode logits, same measure.  A routed dot rounds its
#: output to bf16 where XLA may fuse the dot into the next op and keep f32
#: in between; 48 layers carry those one-step differences into the logits
#: (about 6 eps on a v5e with seed 0).  A wrong kernel or route is off by
#: O(1).
LOGITS_TOL = 16 * BF16_EPS
TIMED_STEPS = 10


def log(phase: str, **fields) -> None:
    print(f"[smoke] {phase} {json.dumps(fields, default=str)}", flush=True)


def fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def tune_phase(cfg, registry_path: str, kernel_dir: str):
    from repro.launch.tune import tune_model

    t0 = time.perf_counter()
    report = tune_model(cfg, smoke=False, backend="jax",
                        registry_path=registry_path, kernel_cache=kernel_dir,
                        max_contractions=MAX_CONTRACTIONS,
                        budget_s=TUNE_BUDGET_S, eval_budget=TUNE_EVAL_BUDGET,
                        **SHAPES)
    log("tune", wall_s=time.perf_counter() - t0,
        n_harvested=report["n_harvested"], n_tuned=report["n_tuned"],
        flop_share_covered=report["flop_share_covered"],
        registry_size=report["registry_size"])
    for c in report["contractions"]:
        log("tune.contraction", **c)
    log("tune.compile", **{k: v for k, v in report["compile"].items()
                           if k != "store"})
    return report


def serve_phase(cfg, registry_path: str):
    from repro.launch.serve import serve_once

    t0 = time.perf_counter()
    summary = serve_once(cfg, requests=REQUESTS, gen_len=GEN_LEN,
                         registry=registry_path, **SHAPES)
    serving = summary["registry"]["serving"]
    log("serve", wall_s=time.perf_counter() - t0,
        requests=summary["requests"], tokens=summary["tokens"],
        decode_steps=summary["decode_steps"],
        step_p50_ms=summary["decode_step_p50_ms"],
        decode_tokens_per_s=summary["decode_tokens_per_s"],
        hits=serving["hits"], misses=serving["misses"],
        routed=serving["routed"])
    return serving


def kernels_phase(registry_path: str, seed: int = 0) -> float:
    """Every tuned (m, k, n) through the serving route vs XLA's dot; the
    largest relative difference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.registry import ScheduleRegistry
    from repro.kernels import ops as K

    t0 = time.perf_counter()
    reg = ScheduleRegistry(registry_path)
    worst = 0.0
    for i, (key, entry) in enumerate(sorted(reg.entries())):
        _, dims, dtype = reg.split_key(key)[0].split(":")
        m, k, n = (int(d) for d in dims.split("x"))
        ka, kb = jax.random.split(jax.random.PRNGKey(seed + i))
        a = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
        b = jax.random.normal(kb, (k, n), jnp.float32).astype(dtype)
        got = K.tuned_einsum("mk,kn->mn", a, b, registry=reg, pallas="on")
        ref = jnp.einsum("mk,kn->mn", a, b)
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        rel = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
        worst = max(worst, rel if np.isfinite(rel) else float("inf"))
        log("kernel", m=m, k=k, n=n, dtype=dtype, block=entry["block"],
            grid_order=entry["grid_order"], gflops_f32=entry["gflops"],
            rel_diff=rel, tol=KERNEL_TOL)
    K.reset_serving_stats()
    log("kernels", wall_s=time.perf_counter() - t0, worst_rel_diff=worst)
    return worst


def _median_step_ms(step, *args) -> float:
    """Median host-clock time of ``TIMED_STEPS`` compiled calls, each
    waited for on the device."""
    import jax
    import numpy as np

    times = []
    for _ in range(TIMED_STEPS):
        t0 = time.perf_counter()
        jax.block_until_ready(step(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def compare_phase(cfg, registry_path: str, seed: int = 0):
    """One decode step routed vs plain XLA: the routed-dot count, whether
    both logits are finite, and max |routed - XLA| / max |XLA|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.registry import ScheduleRegistry
    from repro.kernels import ops as K
    from repro.models import steps as S
    from repro.models import transformer as T

    t0 = time.perf_counter()
    b, p = SHAPES["batch"], SHAPES["prompt_len"]
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    embeds = jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (b, p + 1, cfg.d_model), jnp.float32)
    prefill = jax.jit(S.make_prefill_step(cfg, max_len=SHAPES["max_len"]))
    _, caches, cache_len = prefill(params, {"embeds": embeds[:, :p]})
    step_in = {"embeds": embeds[:, p:]}

    plain = jax.jit(S.make_decode_step(cfg))
    routed = jax.jit(S.make_decode_step(
        cfg, registry=ScheduleRegistry(registry_path)))
    K.reset_serving_stats()
    _, ref, _ = plain(params, step_in, caches, cache_len)
    _, got, _ = routed(params, step_in, caches, cache_len)
    stats = K.serving_stats(reset=True)
    plain_ms = _median_step_ms(plain, params, step_in, caches, cache_len)
    routed_ms = _median_step_ms(routed, params, step_in, caches, cache_len)
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    diff = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    out = {"hits": stats["hits"], "misses": stats["misses"],
           "routed": stats["routed"],
           "finite": bool(np.isfinite(got).all() and np.isfinite(ref).all()),
           "rel_diff": diff / scale if scale > 0 else float("inf")}
    log("compare", wall_s=time.perf_counter() - t0, shape=list(ref.shape),
        max_abs_diff=diff, max_abs_ref=scale, tol=LOGITS_TOL,
        xla_step_ms=plain_ms, routed_step_ms=routed_ms, **out)
    return out


def main() -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        return fail(f"the repro package is not beside this script ({e})")
    import jax

    t_start = time.perf_counter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log("device", **device)
    if device["platform"] != "tpu":
        return fail(f"needs a TPU; JAX found platform {device['platform']!r}")

    from repro.configs import get_config
    from repro.core.registry import current_hardware
    from repro.runtime.device import enable_compile_cache

    cache_dir = enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log("compile_cache", dir=cache_dir, entries_at_start=entries)

    cfg = get_config(ARCH)
    log("config", name=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        d_ff=cfg.d_ff, n_heads=cfg.n_heads, vocab=cfg.vocab, dtype=cfg.dtype,
        **SHAPES)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    registry_path = os.path.join(OUT, "registry.json")

    report = tune_phase(cfg, registry_path, os.path.join(OUT, "kernels"))
    serving = serve_phase(cfg, registry_path)
    kernel_rel = kernels_phase(registry_path)
    compared = compare_phase(cfg, registry_path)
    entries_end = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log("done", wall_s=time.perf_counter() - t_start,
        compile_cache_entries=entries_end)

    compile_stats = report["compile"]
    if report["n_tuned"] < 1:
        return fail("no contraction was tuned")
    if serving["routed"] == 0:
        return fail("serving routed no dot through the Pallas kernel")
    if current_hardware() != device["kind"]:
        return fail(f"registry hardware {current_hardware()!r} is not the "
                    f"device kind {device['kind']!r}")
    if compile_stats["export_errors"] or compile_stats["deser_errors"]:
        return fail(f"kernel export/load errors: {compile_stats}")
    if not kernel_rel <= KERNEL_TOL:
        return fail(f"a tuned kernel differs from XLA's dot by {kernel_rel}"
                    f" of its scale (bound {KERNEL_TOL})")
    if compared["routed"] == 0 or not compared["finite"]:
        return fail(f"the compared decode step routed no dot or is not "
                    f"finite: {compared}")
    if not compared["rel_diff"] <= LOGITS_TOL:
        return fail(f"routed logits differ from XLA by {compared['rel_diff']}"
                    f" of their scale (bound {LOGITS_TOL})")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
