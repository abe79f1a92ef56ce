"""The work a served step needs, from the configuration's sizes alone.

``dense_sites`` lists the products that go through the program's dense
entry point (q/k/v/o, gate/up/down, the LM head), each as the (m, k, n)
workload key the schedule registry uses, with how often one step calls
it.  ``site_work`` gives each call's operations and the bytes it must move
at the least: its unpadded operands and output, each read or written once.
``model_flops`` is the useful arithmetic of a step (the analysis module's
formula: 2 operations per weight per token, the logits product, and
attention over the keys each query sees).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def dense_sites(cfg: dict, m: int) -> List[Tuple[Tuple[int, int, int], int, int]]:
    """[((m, k, n), calls per step, output itemsize)] for one step whose
    products have ``m`` rows (batch x new tokens)."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    act = ITEMSIZE[cfg["dtype"]]
    layers = cfg["n_layers"]
    sites = [((m, d, hq), layers, act),       # q
             ((m, d, hkv), 2 * layers, act),  # k, v
             ((m, hq, d), layers, act),       # o
             ((m, d, f), 2 * layers, act),    # gate, up
             ((m, f, d), layers, act),        # down
             ((m, d, v), 1, 4)]               # LM head, float32 logits
    return sites


def site_work(cfg: dict, mkn: Tuple[int, int, int], out_itemsize: int
              ) -> Tuple[float, float]:
    """(operations, least bytes) of one call."""
    m, k, n = mkn
    inb = ITEMSIZE[cfg["dtype"]]
    return 2.0 * m * k * n, float((m * k + k * n) * inb + m * n * out_itemsize)


def least_seconds(cfg: dict, mkn, out_itemsize: int, peaks: dict) -> float:
    ops, nbytes = site_work(cfg, mkn, out_itemsize)
    return max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def matmul_params(cfg: dict) -> int:
    """Weights that take part in products per token, less the head."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    return cfg["n_layers"] * (d * hq + 2 * d * hkv + hq * d + 3 * d * f)


def model_flops(cfg: dict, batch: int, new_tokens: int, kv_len: int) -> float:
    """Useful operations of one step: ``new_tokens`` per sequence, the last
    of them at position ``kv_len - 1``.  Prefill is ``new_tokens ==
    kv_len`` (causal: half the query-key pairs); decode is one token
    against ``kv_len`` keys."""
    tokens = batch * new_tokens
    f = 2.0 * matmul_params(cfg) * tokens
    f += 2.0 * cfg["d_model"] * cfg["vocab"] * tokens
    if new_tokens == kv_len:
        pairs = new_tokens * kv_len / 2.0
    else:
        pairs = new_tokens * kv_len
    f += batch * 4.0 * pairs * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    return f


def routed_least_seconds(cfg: dict, steps: Dict[str, Dict], routed_keys,
                         peaks: dict) -> float:
    """Least seconds of every routed product in the counted steps.

    ``steps``: {kind: {"m": rows, "count": steps of that kind}};
    ``routed_keys``: the registry keys ``mm:MxKxN:dtype`` that the served
    steps routed through the kernel.
    """
    total = 0.0
    for rec in steps.values():
        for mkn, calls, out_b in dense_sites(cfg, rec["m"]):
            key = f"mm:{'x'.join(map(str, mkn))}:{cfg['dtype']}"
            if key in routed_keys:
                total += rec["count"] * calls * least_seconds(cfg, mkn, out_b,
                                                              peaks)
    return total
