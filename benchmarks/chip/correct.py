"""What decides ``correct``: the timed path's answers against the plain
reference, each compared number beside its limit.

Serve cells: after the window, a sample of the finished requests, drawn
from the seed, goes through the float32 reference of the configuration's
block (``reference/<block>.py``) over its prompt and the tokens it was
served.  Decoding is greedy, so each served token should be the
reference's best at its position up to rounding; the number compared is
the widest gap by which a served token's reference logit lies below the
reference's best logit there.

Tune cells: every entry of the last table goes through the serving route,
``kernels.ops.tuned_einsum`` with the compiled kernel, on bfloat16 operands
drawn from the seed, against a plain float32 product of the same operands.
The numbers compared are the worst relative error (max |kernel - reference|
over max |reference|) and how many entries the route did not send through
the kernel (limit 0).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

Numbers = Dict[str, Dict[str, float]]


def sample_requests(finished: List[dict], batch: int, n: int, seed: int
                    ) -> List[Tuple[int, int]]:
    """``n`` of the finished (wave, slot) pairs, drawn from the seed.  All
    requests of a cell have one length, so every one is among the
    longest."""
    pool = [(f["wave"], s) for f in finished for s in range(batch)]
    rng = np.random.default_rng(int(seed) % 2 ** 64)
    pick = rng.choice(len(pool), size=min(n, len(pool)), replace=False)
    return sorted(pool[i] for i in pick)


def reference_gaps(cfg: dict, seed: int, loop, finished: List[dict],
                   picks: List[Tuple[int, int]], quant=None):
    """Per picked request: (served-token gaps under the float32 reference
    of the loop's block, and, with ``quant``, the gaps of the tokens the
    lower precision puts first)."""
    tokens = {f["wave"]: f["tokens"] for f in finished}
    by_wave: Dict[int, List[int]] = defaultdict(list)
    for w, s in picks:
        by_wave[w].append(s)
    served_gaps, control_gaps = [], []
    for w, slots in sorted(by_wave.items()):
        served = tokens[w][np.asarray(slots)]
        seq, pos = loop.reference_inputs(w, slots, served)
        ref = loop.block.logits(cfg, seed, seq, pos)
        best = ref.max(axis=-1)
        got = np.take_along_axis(ref, served[..., None], -1)[..., 0]
        served_gaps.append(best - got)
        if quant is not None:
            low = loop.block.logits(cfg, seed, seq, pos, quant=quant)
            first = np.take_along_axis(ref, low.argmax(-1)[..., None], -1)
            control_gaps.append(best - first[..., 0])
    return served_gaps, control_gaps


def gap_numbers(gaps: List[np.ndarray], traffic: dict, limits: dict
                ) -> Numbers:
    """The numbers compared for a serve cell, from the gaps of the sampled
    requests (one array per wave)."""
    n = sum(g.shape[0] for g in gaps)
    out: Numbers = {"sample_short": {
        "value": float(max(0, traffic["check_requests"] - n)),
        "limit": 0.0}}
    if gaps:
        out["max_logit_gap"] = {
            "value": float(max(g.max() for g in gaps)),
            "limit": float(limits["max_logit_gap"])}
    return out


def serve_numbers(cfg: dict, seed: int, loop, res: dict, traffic: dict,
                  limits: dict) -> Numbers:
    picks = sample_requests(res["finished"], loop.batch,
                            traffic["check_requests"], seed)
    gaps = (reference_gaps(cfg, seed, loop, res["finished"], picks)[0]
            if picks else [])
    return gap_numbers(gaps, traffic, limits)


def table_operands(reg, seed: int):
    """Operands drawn from the seed for every entry of a table, in the
    entry's dtype and shape: (a (m, k), b (k, n)) per entry."""
    key = W.base_key(seed)
    for i, (rkey, _) in enumerate(sorted(reg.entries())):
        _, dims, dtype = reg.split_key(rkey)[0].split(":")
        m, k, n = (int(d) for d in dims.split("x"))
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        yield (jax.random.normal(ka, (m, k), jnp.float32).astype(dtype),
               jax.random.normal(kb, (k, n), jnp.float32).astype(dtype))


def tune_numbers(registry_path: str, seed: int, limits: dict, pallas: str
                 ) -> Numbers:
    from repro.core.registry import ScheduleRegistry
    from repro.kernels import ops as K

    reg = ScheduleRegistry(registry_path)
    K.reset_serving_stats()
    worst = 0.0
    for a, b in table_operands(reg, seed):
        got = K.tuned_einsum("mk,kn->mn", a, b, registry=reg, pallas=pallas)
        worst = max(worst, float(rel_err(got, a, b)))
    stats = K.serving_stats(reset=True)
    return kernel_numbers(worst, len(reg) - stats["routed"], limits)


def kernel_numbers(worst: float, not_routed: int, limits: dict) -> Numbers:
    """The numbers compared for a tune cell."""
    return {
        "kernel_rel_err": {"value": worst,
                           "limit": float(limits["kernel_rel_err"])},
        "entries_not_routed": {"value": float(not_routed), "limit": 0.0},
    }


@jax.jit
def rel_err(got, a, b):
    """max |got - a @ b| over max |a @ b|, the product in float32."""
    ref = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return (jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
            / jnp.max(jnp.abs(ref)))


def is_correct(numbers: Numbers) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())
