"""Block legality for the tiled Pallas matmul (``kernels/matmul.py``).

One place holds what Mosaic accepts for a block, so the tuner's cost model,
the schedule-to-BlockSpec lowering and the kernel agree:

* **VMEM** — ``pallas_call`` is granted :data:`VMEM_LIMIT_BYTES` of scoped
  VMEM, and the analytical cost model budgets its resident tile against the
  same number.  :func:`matmul_vmem_bytes` counts what the kernel holds: both
  input blocks and the output block double-buffered by the Pallas pipeline,
  plus the f32 accumulator scratch and the f32 product it adds each step.
* **Tiling** — the last two dims of every block must be divisible by
  ``(SUBLANES, LANES)`` = (8, 128), or equal the (padded) array's dims.

:func:`block_error` is the one legality check; :func:`legalize_block` moves
a tuned block to the nearest legal one.  Pure Python: no JAX import.
"""
from __future__ import annotations

from typing import Optional, Tuple

#: TPU v5e has 128 MiB of VMEM per core; the kernel asks Mosaic for this
#: much scoped VMEM and the tuner never picks a block that needs more
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
SUBLANES = 8
LANES = 128
ACC_BYTES = 4  # f32 accumulator


def tile_vmem_bytes(in_elems: int, out_elems: int, in_bytes: int,
                    out_bytes: int) -> int:
    """VMEM a blocked Pallas contraction holds: input and output blocks
    double-buffered, plus an f32 accumulator and the f32 partial product."""
    return (2 * in_elems * in_bytes + 2 * out_elems * out_bytes
            + 2 * out_elems * ACC_BYTES)


def matmul_vmem_bytes(bm: int, bk: int, bn: int, in_bytes: int,
                      out_bytes: int) -> int:
    """VMEM the tiled matmul holds for one ``(bm, bk, bn)`` block."""
    return tile_vmem_bytes(bm * bk + bk * bn, bm * bn, in_bytes, out_bytes)


def _dim_ok(block: int, dim: int, unit: int) -> bool:
    return block == dim or block % unit == 0


def block_error(shape: Tuple[int, int, int], block: Tuple[int, int, int],
                in_bytes: int, out_bytes: int) -> Optional[str]:
    """Why Mosaic would refuse ``block`` for an ``(m, k, n)`` matmul, or None.

    ``shape`` is the (padded) array shape the kernel tiles, ``block`` the
    ``(bm, bk, bn)`` it tiles with, each no larger than its dim.
    """
    m, k, n = shape
    bm, bk, bn = block
    problems = []
    if not _dim_ok(bm, m, SUBLANES):
        problems.append(f"bm={bm} is neither a multiple of {SUBLANES} "
                        f"nor m={m}")
    for name, b, d in (("bk", bk, k), ("bn", bn, n)):
        if not _dim_ok(b, d, LANES):
            problems.append(f"{name}={b} is neither a multiple of {LANES} "
                            f"nor the full dim {d}")
    need = matmul_vmem_bytes(bm, bk, bn, in_bytes, out_bytes)
    if need > VMEM_LIMIT_BYTES:
        problems.append(f"needs {need} B of VMEM")
    if not problems:
        return None
    return (f"illegal block (bm, bk, bn)={block} for matmul (m, k, n)="
            f"{shape} with {in_bytes}-byte operands: " + "; ".join(problems)
            + f" (a block dim must be a multiple of ({SUBLANES}, {LANES}) or "
            f"the full dim, and the block must fit the {VMEM_LIMIT_BYTES} B "
            "VMEM limit)")


def _align(b: int, dim: int, unit: int) -> int:
    """Round ``b`` up to a multiple of ``unit``; the full dim if that
    reaches it."""
    b = -(-max(b, 1) // unit) * unit
    return dim if b >= dim else b


def _padded(dim: int, b: int) -> int:
    return -(-dim // b) * b


def legalize_block(shape: Tuple[int, int, int], block: Tuple[int, int, int],
                   in_bytes: int, out_bytes: int) -> Tuple[int, int, int]:
    """The legal block nearest ``block``: each dim aligned up to its tiling
    unit, then the dim whose halving frees the most VMEM halved until the
    block fits :data:`VMEM_LIMIT_BYTES`."""
    units = (SUBLANES, LANES, LANES)
    cur = [_align(min(b, d), d, u) for b, d, u in zip(block, shape, units)]
    while matmul_vmem_bytes(*cur, in_bytes, out_bytes) > VMEM_LIMIT_BYTES:
        best = None
        for i, u in enumerate(units):
            half = max(u, cur[i] // 2 // u * u)
            if half >= cur[i]:
                continue
            trial = list(cur)
            trial[i] = half
            need = matmul_vmem_bytes(*trial, in_bytes, out_bytes)
            if best is None or need < best[0]:
                best = (need, trial)
        if best is None:  # every dim at its unit: cannot shrink further
            break
        cur = best[1]
    out = tuple(cur)
    err = block_error(tuple(_padded(d, b) for d, b in zip(shape, out)), out,
                      in_bytes, out_bytes)
    if err is not None:
        raise ValueError(err)
    return out
