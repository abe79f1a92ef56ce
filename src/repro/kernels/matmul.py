"""Tiled matmul Pallas kernel — the kernel LoopTune schedules.

The tuned loop nest lowers onto this kernel: the VMEM-resident suffix of the
schedule becomes the BlockSpec block shape ``(bm, bk, bn)`` and the grid
iterates the outer levels in schedule order (``grid_order``).  The k grid
dimension is always innermost (sequential) so the f32 VMEM scratch
accumulator implements LoopNest's register tiling: the output tile stays
resident across the whole contraction and is written back exactly once.

Validated against ``ref.matmul_ref`` in interpret mode (CPU); on TPU the
same ``pl.pallas_call`` compiles to a Mosaic kernel, granted
``core.tiling.VMEM_LIMIT_BYTES`` of scoped VMEM.  A compiled call checks its
block with ``core.tiling.block_error`` first, so an illegal block fails here
with the block, the shape and the limit named, not inside Mosaic.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import VMEM_LIMIT_BYTES, block_error
from repro.runtime.device import resolve_interpret


def _mm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k_i == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "grid_order", "interpret", "out_dtype"),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    bn: int = 128,
    grid_order: str = "mn",  # outer-grid traversal: "mn" | "nm"
    interpret: Optional[bool] = None,
    out_dtype=None,
) -> jax.Array:
    """C[m, n] = A[m, k] @ B[k, n] with explicit VMEM tiling.

    Non-divisible dims are zero-padded (zeros are sum-neutral) and the
    output sliced back — the ``tail`` semantics of the loop IR.
    ``interpret=None`` compiles with Mosaic on a TPU and interprets
    elsewhere.
    """
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)

    pm, pk, pn = -m % bm, -k % bk, -n % bn
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    gm, gn, gk = _cdiv(m + pm, bm), _cdiv(n + pn, bn), _cdiv(k + pk, bk)
    if not interpret:
        err = block_error((m + pm, k + pk, n + pn), (bm, bk, bn),
                          jnp.dtype(a.dtype).itemsize,
                          jnp.dtype(out_dtype).itemsize)
        if err is not None:
            raise ValueError(err)

    if grid_order == "mn":
        grid = (gm, gn, gk)
        a_map = lambda i, j, kk: (i, kk)
        b_map = lambda i, j, kk: (kk, j)
        o_map = lambda i, j, kk: (i, j)
    else:  # "nm": n outermost
        grid = (gn, gm, gk)
        a_map = lambda j, i, kk: (i, kk)
        b_map = lambda j, i, kk: (kk, j)
        o_map = lambda j, i, kk: (i, j)

    out = pl.pallas_call(
        functools.partial(_mm_kernel, n_k=gk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), a_map),
            pl.BlockSpec((bk, bn), b_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
