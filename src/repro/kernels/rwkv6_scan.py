"""RWKV-6 chunked-scan Pallas kernel.

The Finch recurrence S_t = diag(w_t) S_{t-1} + k_t^T v_t is attention-free
and O(S) — the LoopTune-relevant structure is the *chunk*: within a chunk of
L tokens the recurrence unrolls into dense (L, N) x (N, N) and strictly
lower-triangular (L, L) matmuls (MXU work); across chunks a tiny (N, N) f32
state is carried in VMEM scratch.

Grid ``(B*H, n_chunks)`` with the chunk dimension innermost (sequential):
the state scratch persists across chunk steps, so each (batch, head) stream
is scanned without the state ever leaving VMEM.

Inputs are per-head streams (B*H, S, N) with N = head_dim; decay ``logw`` is
the log-space data-dependent decay (<= 0).  Validated against
``ref.rwkv6_ref`` (the token-by-token recurrence).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.device import resolve_interpret


def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref,
                 s_ref, *, n_chunks: int, chunk: int, seq: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)   # (L, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = w_ref[0].astype(jnp.float32)  # log-decay, <= 0
    u = u_ref[0].astype(jnp.float32)   # (N,) bonus

    # state-neutral padding (k = 0, logw = 0) for positions >= seq
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    valid = pos < seq
    k = jnp.where(valid, k, 0.0)
    lw = jnp.where(valid, lw, 0.0)

    cum = jnp.cumsum(lw, axis=0)       # inclusive log-decay products
    cum_ex = cum - lw                  # exclusive
    s = s_ref[...]                     # (N, N) carried state

    r_dec = r * jnp.exp(cum_ex)
    y = jnp.dot(r_dec, s, preferred_element_type=jnp.float32)  # inter-chunk
    k_dec = k * jnp.exp(-cum)
    att = jnp.dot(r_dec, k_dec.T, preferred_element_type=jnp.float32)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(li > lj, att, 0.0)  # strictly causal intra-chunk
    diag = jnp.sum(r * (u[None, :] * k), axis=-1)  # u-bonus for t == i
    y = y + jnp.dot(att, v, preferred_element_type=jnp.float32)
    y = y + diag[:, None] * v
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S' = diag(prod w) S + sum_i (k_i * W_L / W_i)^T v_i
    w_last = cum[-1:, :]               # (1, N)
    k_carry = k * jnp.exp(w_last - cum)
    s_ref[...] = s * jnp.exp(w_last[0])[:, None] + jnp.dot(
        k_carry.T, v, preferred_element_type=jnp.float32)

    @pl.when(ci == n_chunks - 1)
    def _done():
        sout_ref[0] = s_ref[...]


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_chunk_scan(
    r: jax.Array,     # (BH, S, N)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (BH, S, N) log-space decay (<= 0), f32
    u: jax.Array,     # (BH, N) per-head bonus
    *,
    chunk: int = 64,
    interpret: Optional[bool] = None,
):
    """Returns (y (BH, S, N) f32, final_state (BH, N, N) f32)."""
    interpret = resolve_interpret(interpret)
    bh, s, n = r.shape
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        zp = lambda t: jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        r, k, v, logw = zp(r), zp(k), zp(v), zp(logw)
    n_chunks = _cdiv(s + pad, chunk)

    y, s_out = pl.pallas_call(
        functools.partial(_rwkv_kernel, n_chunks=n_chunks, chunk=chunk, seq=s),
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, n), lambda h, c: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, n, n), lambda h, c: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s + pad, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u)
    return y[:, :s], s_out
