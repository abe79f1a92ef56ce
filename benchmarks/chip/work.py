"""The least time of a served step's routed products, from their shapes.

A block module's ``sites`` lists the products that go through the
program's dense entry point, each as the (m, k, n) workload key the
schedule registry uses, with how often one step calls it.  ``site_work``
gives each call's operations and the bytes it must move at the least: its
unpadded operands and output, each read or written once.
"""
from __future__ import annotations

from typing import Dict, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def site_work(cfg: dict, mkn: Tuple[int, int, int], out_itemsize: int
              ) -> Tuple[float, float]:
    """(operations, least bytes) of one call."""
    m, k, n = mkn
    inb = ITEMSIZE[cfg["dtype"]]
    return 2.0 * m * k * n, float((m * k + k * n) * inb + m * n * out_itemsize)


def least_seconds(cfg: dict, mkn, out_itemsize: int, peaks: dict) -> float:
    ops, nbytes = site_work(cfg, mkn, out_itemsize)
    return max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])


def routed_least_seconds(block, cfg: dict, steps: Dict[str, Dict],
                         routed_keys, peaks: dict) -> float:
    """Least seconds of every routed product in the counted steps.

    ``block``: the configuration's block module, whose ``sites`` lists a
    step's products; ``steps``: {kind: {"m": rows, "count": steps of that
    kind}}; ``routed_keys``: the registry keys ``mm:MxKxN:dtype`` that the
    served steps routed through the kernel.
    """
    total = 0.0
    for rec in steps.values():
        for mkn, calls, out_b in block.sites(cfg, rec["m"]):
            key = f"mm:{'x'.join(map(str, mkn))}:{cfg['dtype']}"
            if key in routed_keys:
                total += rec["count"] * calls * least_seconds(cfg, mkn, out_b,
                                                              peaks)
    return total
