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


def _mm_kernel(*refs, n_k: int):
    # a stacked call passes its scalar-prefetched layer index first
    a_ref, b_ref, o_ref, acc_ref = refs[-4:]
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


def stack_divides(k: int, n: int, bk: int, bn: int) -> bool:
    """Whether the block ``(bk, bn)`` (clamped as :func:`matmul` clamps
    it) tiles a ``[L, k, n]`` stack with no padding: a stack is never
    padded, since that would copy every layer of it."""
    return k % min(bk, k) == 0 and n % min(bn, n) == 0


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "bn", "grid_order", "interpret", "out_dtype"),
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    layer: Optional[jax.Array] = None,
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

    ``b`` may instead be a stack ``[L, k, n]`` of per-layer weights, with
    ``layer`` a traced int32 index: C = A @ B[layer].  The index is
    scalar-prefetched and B's index map reads that layer's blocks straight
    out of the stack, so no copy of the layer's weight is made.  The stack
    is never padded: its block must divide k and n (:func:`stack_divides`).
    """
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    stacked = layer is not None
    k2, n = b.shape[-2:]
    assert k == k2 and b.ndim == 2 + stacked, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)

    pm, pk, pn = -m % bm, -k % bk, -n % bn
    if stacked and (pk or pn):
        raise ValueError(f"block (bk, bn)=({bk}, {bn}) does not divide the "
                         f"stack {tuple(b.shape)}, which is never padded")
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

    def at(index_map):
        """``index_map`` of (i, j, kk, *layer ref) as the grid order
        passes them"""
        if grid_order == "mn":
            return index_map
        # "nm": n outermost
        return lambda j, i, kk, *layer_ref: index_map(i, j, kk, *layer_ref)

    grid = (gm, gn, gk) if grid_order == "mn" else (gn, gm, gk)
    a_spec = pl.BlockSpec((bm, bk), at(lambda i, j, kk, *_: (i, kk)))
    o_spec = pl.BlockSpec((bm, bn), at(lambda i, j, kk, *_: (i, j)))
    if stacked:
        b_spec = pl.BlockSpec((None, bk, bn),
                              at(lambda i, j, kk, lr: (lr[0], kk, j)))
    else:
        b_spec = pl.BlockSpec((bk, bn), at(lambda i, j, kk: (kk, j)))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    call = functools.partial(
        pl.pallas_call,
        functools.partial(_mm_kernel, n_k=gk),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )
    if stacked:
        out = call(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=[a_spec, b_spec],
            out_specs=o_spec, scratch_shapes=scratch),
        )(jnp.reshape(layer, (1,)).astype(jnp.int32), a, b)
    else:
        out = call(grid=grid, in_specs=[a_spec, b_spec], out_specs=o_spec,
                   scratch_shapes=scratch)(a, b)
    return out[:m, :n]
