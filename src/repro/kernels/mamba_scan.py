"""Mamba selective-scan Pallas kernel.

The discretized SSM h_t = decay_t * h_{t-1} + dt_t B_t x_t, y_t = C_t . h_t
is scanned per chunk: the (bd, N) hidden state lives in VMEM scratch and the
in-chunk recurrence uses a log-space cumulative-product trick — within a
chunk the state contribution of token i to token t is
``exp(cumA_t - cumA_i)``, so the chunk reduces to two matmuls plus a masked
(L, L) combine (MXU-friendly; the per-channel scan never materializes in
HBM).

Grid ``(B, n_d_blocks, n_chunks)``; chunks innermost (sequential) carrying
the state; the d_inner dimension is blocked with ``bd`` (the LoopTune-tuned
tile).  Validated against ``ref.mamba_scan_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.device import resolve_interpret


def _mamba_kernel(dtx_ref, da_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
                  n_chunks: int, chunk: int, seq: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    dtx = dtx_ref[0].astype(jnp.float32)  # (L, bd)   dt_t * x_t
    da = da_ref[0].astype(jnp.float32)    # (L, bd, N) dt_t * A  (log decay)
    bm = b_ref[0].astype(jnp.float32)     # (L, N)
    cm = c_ref[0].astype(jnp.float32)     # (L, N)

    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1, 1), 0)
    valid = pos < seq
    dtx = jnp.where(valid[..., 0], dtx, 0.0)
    da = jnp.where(valid, da, 0.0)  # exp(0) = 1: state-neutral padding

    # u_t = dt_t x_t B_t : (L, bd, N)
    u = dtx[:, :, None] * bm[:, None, :]
    cum = jnp.cumsum(da, axis=0)          # (L, bd, N) inclusive log-decay
    h0 = h_ref[...]                       # (bd, N) carried state

    # h_t = exp(cum_t) h0 + sum_{i<=t} exp(cum_t - cum_i) u_i
    # y_t = C_t . h_t  (reduce over N)
    contrib = u * jnp.exp(-cum)
    csum = jnp.cumsum(contrib, axis=0)
    h_all = jnp.exp(cum) * (h0[None] + csum)  # (L, bd, N)
    y = jnp.einsum("lbn,ln->lb", h_all, cm)
    y_ref[0] = y.astype(y_ref.dtype)

    h_ref[...] = h_all[-1]

    @pl.when(ci == n_chunks - 1)
    def _done():
        hout_ref[0] = h_ref[...]


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def mamba_scan(
    dtx: jax.Array,   # (B, S, C)      dt_t * x_t
    da: jax.Array,    # (B, S, C, N)   dt_t * A   (log decay, <= 0)
    b: jax.Array,     # (B, S, N)
    c: jax.Array,     # (B, S, N)
    *,
    chunk: int = 32,
    bd: int = 128,
    interpret: Optional[bool] = None,
):
    """Returns (y (B, S, C) f32, final_state (B, C, N) f32)."""
    interpret = resolve_interpret(interpret)
    bsz, s, ch = dtx.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    bd = min(bd, ch)
    ps, pc = -s % chunk, -ch % bd
    if ps or pc:
        dtx = jnp.pad(dtx, ((0, 0), (0, ps), (0, pc)))
        da = jnp.pad(da, ((0, 0), (0, ps), (0, pc), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, ps), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, ps), (0, 0)))
    n_chunks = _cdiv(s + ps, chunk)
    n_d = _cdiv(ch + pc, bd)

    y, h_out = pl.pallas_call(
        functools.partial(_mamba_kernel, n_chunks=n_chunks, chunk=chunk,
                          seq=s),
        grid=(bsz, n_d, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, chunk, bd, n), lambda i, j, k: (i, k, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j, k: (i, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, bd, n), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s + ps, ch + pc), jnp.float32),
            jax.ShapeDtypeStruct((bsz, ch + pc, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        interpret=interpret,
    )(dtx, da, b, c)
    return y[:, :s, :ch], h_out[:, :ch]
