"""Flash attention Pallas kernel (TPU target, interpret-validated on CPU).

Grid ``(batch*heads, n_q_blocks, n_kv_blocks)`` with the kv dimension
innermost (sequential): the f32 accumulator / running-max / running-sum live
in VMEM scratch across kv steps — the online-softmax state never touches
HBM.  Supports causal masking, sliding windows and gemma-style score
softcaps; the block shapes come from the LoopTune schedule registry via
``ops.py``.

The pure-jnp oracle is ``ref.attention_ref`` (the same math as
``repro.models.layers.attention``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.device import resolve_interpret

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
               n_kv: int, bq: int, bk: int, causal: bool, scale: float,
               softcap: Optional[float], window: Optional[int],
               seq_q: int, seq_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (bq, bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (q_pos < seq_q) & (kv_pos < seq_kv)
    if causal:
        mask &= kv_pos <= q_pos
    if window is not None:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_prev * alpha + p.sum(axis=-1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, T, HKV, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    bq: int = 128,
    bk: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if g > 1:  # GQA: expand KV heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scale = 1.0 / math.sqrt(d)
    bq = min(bq, s)
    bk = min(bk, t)

    # (B*H, S, D) layout; pad seq dims to block multiples
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hq, t, d)
    ps, pt = -s % bq, -t % bk
    if ps:
        qf = jnp.pad(qf, ((0, 0), (0, ps), (0, 0)))
    if pt:
        kf = jnp.pad(kf, ((0, 0), (0, pt), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pt), (0, 0)))
    n_q, n_kv = _cdiv(s + ps, bq), _cdiv(t + pt, bk)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, n_kv=n_kv, bq=bq, bk=bk, causal=causal, scale=scale,
            softcap=softcap, window=window, seq_q=s, seq_kv=t),
        grid=(b * hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, bk, d), lambda h, i, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, s + ps, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :s].reshape(b, hq, s, d).transpose(0, 2, 1, 3)
