"""Batched serving launcher: continuous-batching decode loop.

Implements the serving shape cells' step for real: a request pool feeds a
fixed-size decode batch; finished requests are retired and their slots
refilled (continuous batching), prefill runs per-admission, and the decode
step is the jitted ``serve_step`` the dry-run lowers for decode_32k /
long_500k.

``--registry PATH`` serves tuned schedules: the prefill/decode step bodies
trace under ``kernels.ops.serving``, so every dense site looks its workload
signature up in the tuned-schedule table (``launch/tune`` writes it) and
routes hits through the registry-backed Pallas kernel where Mosaic
compiles.  ``--tune`` runs the tuning pre-pass first, against the same
serving shapes.  Both default off — the untuned path is untouched.

    PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large \
        --requests 16 --batch 4 --prompt-len 32 --gen-len 32 \
        --tune --registry /tmp/musicgen.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.registry import ScheduleRegistry
from repro.models import steps as S
from repro.models import transformer as T
from repro.runtime.device import enable_compile_cache


class Request:
    def __init__(self, rid: int, prompt: np.ndarray, gen_len: int):
        self.rid = rid
        self.prompt = prompt
        self.gen_len = gen_len
        self.generated: List[int] = []
        self.t_submit = time.perf_counter()
        self.t_done: Optional[float] = None


def serve_once(
    cfg,
    *,
    requests: int = 16,
    batch: int = 4,
    prompt_len: int = 32,
    gen_len: int = 32,
    max_len: int = 128,
    seed: int = 0,
    registry: Union[str, ScheduleRegistry, None] = None,
) -> Dict[str, Any]:
    """Run the continuous-batching serve loop once; return the summary.

    ``registry``: tuned-schedule table (path or ScheduleRegistry) to serve
    with.  When given, the summary grows a ``"registry"`` block with the
    per-contraction hit/miss/routed counters from the traced steps.
    """
    rng = np.random.default_rng(seed)
    params = T.init_params(cfg, jax.random.PRNGKey(seed))

    if isinstance(registry, str):
        registry = ScheduleRegistry(registry)
    if registry is not None:
        from repro.kernels import ops as K
        K.reset_serving_stats()

    serve_step = jax.jit(S.make_decode_step(cfg, registry=registry),
                         donate_argnums=(2,))
    prefill_one = jax.jit(S.make_prefill_step(cfg, max_len=max_len,
                                              registry=registry))

    def make_inputs(tokens_np):
        if cfg.frontend == "tokens":
            return {"tokens": jnp.asarray(tokens_np, jnp.int32)}
        b, s = tokens_np.shape
        emb = np.take(np.asarray(
            jax.random.normal(jax.random.PRNGKey(1), (cfg.vocab, cfg.d_model),
                              jnp.float32)), tokens_np, axis=0)
        return {"embeds": jnp.asarray(emb)}

    # request pool
    pool = [Request(i, rng.integers(0, cfg.vocab, (prompt_len,)), gen_len)
            for i in range(requests)]
    pending = list(pool)
    done: List[Request] = []

    # continuous batch state: per-slot request + shared cache
    b = batch
    caches = T.init_cache(cfg, b, max_len)
    slots: List[Optional[Request]] = [None] * b
    slot_len = np.zeros(b, np.int32)

    t0 = time.perf_counter()
    decode_steps = 0
    step_times: List[float] = []
    # NOTE (batched-cache simplification): a production server tracks
    # per-slot cache lengths; here admission happens in waves (all slots
    # share cache_len), which is exact because prompts are equal-length.
    while pending or any(s is not None for s in slots):
        # admit a wave when all slots are free
        if all(s is None for s in slots) and pending:
            wave = [pending.pop(0) for _ in range(min(b, len(pending)))]
            prompts = np.stack(
                [w.prompt for w in wave]
                + [wave[-1].prompt] * (b - len(wave)))
            last_logits, caches, cache_len = prefill_one(
                params, make_inputs(prompts))
            nxt = np.asarray(jnp.argmax(last_logits, -1), np.int32)
            for i, w in enumerate(wave):
                slots[i] = w
                w.generated.append(int(nxt[i]))
            slot_len[:] = prompt_len
            cur = nxt
        # one decode step for the active wave
        one = make_inputs(cur[:, None])
        t_step = time.perf_counter()
        nxt, logits, caches = serve_step(
            params, one, caches, jnp.asarray(int(slot_len[0]), jnp.int32))
        decode_steps += 1
        slot_len += 1
        nxt = np.asarray(nxt, np.int32)  # device sync closes the step timer
        step_times.append(time.perf_counter() - t_step)
        for i, r in enumerate(slots):
            if r is None:
                continue
            r.generated.append(int(nxt[i]))
            if len(r.generated) >= r.gen_len:
                r.t_done = time.perf_counter()
                done.append(r)
                slots[i] = None
        cur = nxt

    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.generated) for r in done)
    lat = [r.t_done - r.t_submit for r in done]
    # steady-state decode throughput: median per-step time, excluding the
    # first step (it pays the decode jit compile) — this is the number the
    # tuned-schedule comparison is about; tokens_per_s keeps the whole-loop
    # view (prefill + compile included)
    steady = step_times[1:] if len(step_times) > 1 else step_times
    step_p50 = float(np.percentile(steady, 50))
    summary = {
        "arch": cfg.name,
        "requests": len(done),
        "decode_steps": decode_steps,
        "tokens": total_tokens,
        "tokens_per_s": round(total_tokens / dt, 1),
        "decode_step_p50_ms": round(step_p50 * 1e3, 3),
        "decode_tokens_per_s": round(b / step_p50, 1),
        "latency_p50_s": round(float(np.percentile(lat, 50)), 3),
        "latency_p95_s": round(float(np.percentile(lat, 95)), 3),
    }
    if registry is not None:
        from repro.kernels import ops as K
        summary["registry"] = {
            "path": registry.path,
            "size": len(registry),
            "serving": K.serving_stats(reset=True),
        }
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="musicgen-large")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--registry", default=None,
                    help="tuned-schedule registry JSON to serve with")
    ap.add_argument("--tune", action="store_true",
                    help="run the tuning pre-pass before serving "
                         "(requires --registry)")
    ap.add_argument("--tune-budget-s", type=float, default=4.0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.smoke()

    registry = None
    if args.registry:
        registry = ScheduleRegistry(args.registry)
        if args.tune:
            from repro.launch.tune import tune_model
            report = tune_model(
                cfg, registry=registry, registry_path=args.registry,
                budget_s=args.tune_budget_s, smoke=False,  # cfg already set
                batch=args.batch, prompt_len=args.prompt_len,
                max_len=args.max_len)
            print("[serve] tuned:", json.dumps(
                {k: report[k] for k in ("n_harvested", "n_tuned",
                                        "flop_share_covered",
                                        "registry_size", "tune_time_s")}),
                flush=True)
    elif args.tune:
        ap.error("--tune requires --registry")

    summary = serve_once(
        cfg, requests=args.requests, batch=args.batch,
        prompt_len=args.prompt_len, gen_len=args.gen_len,
        max_len=args.max_len, seed=args.seed, registry=registry)
    print("[serve] done:", json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
