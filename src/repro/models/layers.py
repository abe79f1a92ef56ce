"""Core model primitives, pure JAX (the XLA path used by the dry-run).

All attention here is memory-efficient by construction: query-block ×
kv-block online-softmax (a flash-attention *reference*; the Pallas kernel in
``repro.kernels.flash_attention`` is the TPU-target twin validated against
the same math).  Norm/softmax accumulate in f32 regardless of activation
dtype.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.sharding import ashard

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(dtype)


# ---------------------------------------------------------------------------
# Tuned-serving hook: matmul sites route through the schedule registry
# ---------------------------------------------------------------------------


def _serving_ops():
    """``repro.kernels.ops`` iff a tuned-schedule registry is active.

    Deferred import keeps the plain XLA path free of any kernels/ import;
    the check runs at trace time, so ``kernels.ops.serving(...)`` wrapped
    around a step-function body is enough to switch every dense site.
    """
    from repro.kernels import ops as _kops
    return _kops if _kops.serving_registry() is not None else None


class LayerWeight(NamedTuple):
    """Layer ``index`` (a traced int32) of a stacked ``[n_layers, K, N]``
    weight, kept as the whole stack and the index, so that the routed
    kernel reads the layer's blocks out of the stack with no copy."""
    stack: jax.Array
    index: jax.Array


#: the weights :func:`dense` consumes, by the parameter group holding
#: them: while a registry is served the layer scan passes them as
#: :class:`LayerWeight` views (``transformer._run_layers``)
DENSE_WEIGHTS = {"attn": ("wq", "wk", "wv", "wo"),
                 "cross": ("wq", "wk", "wv", "wo"),
                 "mlp": ("w_gate", "w_up", "w_down")}


def dense(x: jax.Array, w) -> jax.Array:
    """``x (..., K) @ w (K, N)`` — the model zoo's matmul hot path.

    With a tuned-schedule registry being served (``kernels.ops.serving``),
    the contraction routes through :func:`repro.kernels.ops.tuned_einsum`
    (registry lookup + Pallas tiled kernel on hit), and ``w`` may be a
    :class:`LayerWeight`; otherwise it is exactly the plain ``@`` it
    always was.
    """
    kops = _serving_ops()
    if kops is None:
        return x @ w
    free = "abce"[: x.ndim - 1]  # skip k/n (bound in the spec)
    spec = f"{free}k,kn->{free}n"
    if isinstance(w, LayerWeight):
        return kops.tuned_einsum(spec, x, w.stack, layer=w.index)
    return kops.tuned_einsum(spec, x, w)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if plus_one:  # gemma parameterization: weight stored as (w - 1)
        w = w + 1.0
    return (y * w).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Online-softmax blocked attention (the XLA reference "flash" path)
# ---------------------------------------------------------------------------


def _softcap(scores: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, T, HKV, D) -> (B, T, HKV*groups, D)."""
    if groups == 1:
        return k
    b, t, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, h, groups, d)).reshape(
        b, t, h * groups, d
    )


def _block_mask(q_pos, kv_pos, kv_valid, causal, window):
    mask = kv_pos[None, :] < kv_valid
    if causal:
        mask = mask & (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
    return mask


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(static, q, k, v, q_offset, kv_valid):
    """Blocked online-softmax attention with a hand-written (flash-style)
    backward pass: O(S) memory in both directions.

    static = (causal, window, softcap, scale, qb, tb, n_qb, n_tb, s, t).
    q: (n_qb, B, H, qb, D) pre-scaled; k, v: (n_tb, B, H, tb, D).
    Returns (n_qb, B, H, qb, D) f32.
    """
    out, _ = _flash_fwd_impl(static, q, k, v, q_offset, kv_valid)
    return out


def _flash_fwd_impl(static, q, k, v, q_offset, kv_valid):
    causal, window, softcap, scale, qb, tb, n_qb, n_tb, s, t = static
    _, b, h, _, d = q.shape

    def one_q_block(qi, q_blk):
        q_pos = q_offset + qi * qb + jnp.arange(qb, dtype=jnp.int32)

        def kv_step(carry, inp):
            acc, m, l = carry
            ti, k_blk, v_blk = inp
            kv_pos = ti * tb + jnp.arange(tb, dtype=jnp.int32)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk,
                                preferred_element_type=jnp.float32)
            scores = _softcap(scores, softcap)
            mask = _block_mask(q_pos, kv_pos, kv_valid, causal, window)
            scores = jnp.where(mask[None, None], scores, -1e30)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (acc_new, m_new, l_new), None

        acc0 = jnp.zeros((b, h, qb, d), jnp.float32)
        m0 = jnp.full((b, h, qb), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, qb), jnp.float32)
        tis = jnp.arange(n_tb, dtype=jnp.int32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (tis, k, v))
        l = jnp.maximum(l, 1e-30)
        return acc / l[..., None], m + jnp.log(l)  # out, lse

    if n_qb == 1:
        o, lse = one_q_block(jnp.asarray(0, jnp.int32), q[0])
        return o[None], lse[None]
    out, lse = jax.lax.map(
        lambda args: one_q_block(*args),
        (jnp.arange(n_qb, dtype=jnp.int32), q))
    return out, lse


def _flash_fwd(static, q, k, v, q_offset, kv_valid):
    out, lse = _flash_fwd_impl(static, q, k, v, q_offset, kv_valid)
    return out, (q, k, v, out, lse, q_offset, kv_valid)


def _flash_bwd(static, res, dout):
    causal, window, softcap, scale, qb, tb, n_qb, n_tb, s, t = static
    q, k, v, out, lse, q_offset, kv_valid = res
    _, b, h, _, d = q.shape
    delta = jnp.sum(dout * out, axis=-1)  # (n_qb, B, H, qb)

    def one_q_block(carry, inp):
        dk_tot, dv_tot = carry
        qi, q_blk, do_blk, lse_blk, delta_blk = inp
        q_pos = q_offset + qi * qb + jnp.arange(qb, dtype=jnp.int32)

        def kv_step(dq_acc, inp2):
            ti, k_blk, v_blk = inp2
            kv_pos = ti * tb + jnp.arange(tb, dtype=jnp.int32)
            raw = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk,
                             preferred_element_type=jnp.float32)
            scores = _softcap(raw, softcap)
            mask = _block_mask(q_pos, kv_pos, kv_valid, causal, window)
            scores = jnp.where(mask[None, None], scores, -1e30)
            p = jnp.exp(scores - lse_blk[..., None])
            dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, do_blk)
            dp = jnp.einsum("bhqd,bhkd->bhqk", do_blk,
                            v_blk.astype(jnp.float32))
            ds = p * (dp - delta_blk[..., None])
            if softcap is not None:
                th = jnp.tanh(raw / softcap)
                ds = ds * (1.0 - jnp.square(th))
            ds = jnp.where(mask[None, None], ds, 0.0)
            dq_acc = dq_acc + jnp.einsum(
                "bhqk,bhkd->bhqd", ds, k_blk.astype(jnp.float32))
            dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds,
                                q_blk.astype(jnp.float32))
            return dq_acc, (dk_blk, dv_blk)

        dq0 = jnp.zeros((b, h, qb, d), jnp.float32)
        tis = jnp.arange(n_tb, dtype=jnp.int32)
        dq_blk, (dks, dvs) = jax.lax.scan(kv_step, dq0, (tis, k, v))
        return (dk_tot + dks, dv_tot + dvs), dq_blk

    zeros_kv = jnp.zeros((n_tb, b, h, tb, d), jnp.float32)
    qis = jnp.arange(n_qb, dtype=jnp.int32)
    (dk, dv), dq = jax.lax.scan(
        one_q_block, (zeros_kv, zeros_kv),
        (qis, q, dout.astype(jnp.float32), lse, delta))
    f0 = lambda x: np.zeros(np.shape(x), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            f0(q_offset), f0(kv_valid))


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: Any = 0,
    kv_len: Any = None,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_block: int = 512,
    kv_block: int = 1024,
) -> jax.Array:
    """Blocked online-softmax attention (flash reference, custom VJP).

    q: (B, S, HQ, D); k, v: (B, T, HKV, D); HQ % HKV == 0.
    ``q_offset``: absolute position of q[0] (int or traced scalar) — supports
    decode (S=1, offset=cache_len) and prefill (offset=0).
    ``window``: sliding-window size; query at position p sees [p-window+1, p].
    ``kv_len``: valid cache length (trailing slots masked).
    Returns (B, S, HQ, D).
    """
    b, s, hq, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    groups = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    if s == 1:
        # Decode path: one query row — materializing (B, H, 1, T) scores is
        # tiny, avoids the blocked scan (whose leading-axis iteration defeats
        # GSPMD when the cache's seq dim is sharded), and lets XLA lower the
        # softmax reductions over a sharded T as plain all-reduces.
        kv_pos = jnp.arange(t, dtype=jnp.int32)
        q_pos = jnp.asarray(q_offset, jnp.int32)
        kvl = jnp.asarray(t if kv_len is None else kv_len, jnp.int32)
        scores = jnp.einsum(
            "bqhd,bthd->bhqt", (q * jnp.asarray(scale, q.dtype)),
            _repeat_kv(k, groups), preferred_element_type=jnp.float32)
        scores = _softcap(scores, softcap)
        mask = kv_pos < kvl
        if causal:
            mask &= kv_pos <= q_pos
        if window is not None:
            mask &= kv_pos > q_pos - window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqt,bthd->bqhd", p.astype(v.dtype),
                         _repeat_kv(v, groups))
        return out

    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)

    # Pad S and T to block multiples (masked out inside).
    s_pad = -s % q_block if s > q_block else 0
    qb = q_block if s > q_block else s
    t_pad = -t % kv_block if t > kv_block else 0
    tb = kv_block if t > kv_block else t
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad), (0, 0), (0, 0)))
    n_qb = q.shape[1] // qb
    n_tb = k.shape[1] // tb

    # (n_qb, B, H, qb, D) / (n_tb, B, H, tb, D).  The head dim must stay
    # model-sharded through this re-layout: without the constraints GSPMD
    # replicates the whole attention interior over heads (16x compute +
    # 16x block buffers + per-kv-step collectives — EXPERIMENTS §Perf).
    spec = (None, "batch", "heads", None, None)
    qr = ashard((q.reshape(b, n_qb, qb, hq, d).transpose(1, 0, 3, 2, 4)
                 * jnp.asarray(scale, q.dtype)), spec)
    kr = ashard(k.reshape(b, n_tb, tb, hq, d).transpose(1, 0, 3, 2, 4), spec)
    vr = ashard(v.reshape(b, n_tb, tb, hq, d).transpose(1, 0, 3, 2, 4), spec)

    static = (causal, window, softcap, scale, qb, tb, n_qb, n_tb, s, t)
    out = _flash(static, qr, kr, vr, jnp.asarray(q_offset, jnp.int32),
                 jnp.asarray(t if kv_len is None else kv_len, jnp.int32))
    # (n_qb, B, H, qb, D) -> (B, S, H, D)
    out = out.transpose(1, 0, 3, 2, 4).reshape(b, n_qb * qb, hq, d)
    return out[:, :s].astype(v.dtype)


def local_attention(
    q: jax.Array,  # (B, S, HQ, D)
    k: jax.Array,  # (B, S, HKV, D)
    v: jax.Array,
    *,
    window: int,
    softcap: Optional[float] = None,
    q_block: int = 512,
    kv_block: int = 1024,
) -> jax.Array:
    """Sliding-window causal self-attention in O(S·window).

    The blocked path computes (and masks) every S×S block — 4× waste at
    window/S = 1/4 and 32× at prefill_32k.  Here the sequence is cut into
    chunks of size ``window``; chunk i attends to (chunk i-1, chunk i)
    folded into the batch dim, so each real kv position a query may see is
    present and the standard causal+window mask is exact.  Chunk 0 runs
    alone (no zero-pad keys ever enter the softmax).  Reuses the flash
    custom-VJP — no new backward code.
    """
    b, s, hq, d = q.shape
    c = window
    if s <= c:  # window covers everything: plain causal
        return attention(q, k, v, causal=True, softcap=softcap,
                         q_block=q_block, kv_block=kv_block)
    pad = -s % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = q.shape[1]
    nc = sp // c

    def blocks(t):  # (B, S, H, D) -> (B, nc, C, H, D)
        return t.reshape(b, nc, c, *t.shape[2:])

    qb_, kb_, vb_ = blocks(q), blocks(k), blocks(v)
    # chunk 0: plain causal over its own keys
    out0 = attention(qb_[:, 0], kb_[:, 0], vb_[:, 0], causal=True,
                     softcap=softcap, q_block=q_block, kv_block=kv_block)
    if nc == 1:
        return out0[:, :s]
    # chunks 1..nc-1: fold into batch; kv = (prev chunk, own chunk)
    qf = qb_[:, 1:].reshape(b * (nc - 1), c, hq, d)
    kf = jnp.concatenate([kb_[:, :-1], kb_[:, 1:]], axis=2).reshape(
        b * (nc - 1), 2 * c, k.shape[2], d)
    vf = jnp.concatenate([vb_[:, :-1], vb_[:, 1:]], axis=2).reshape(
        b * (nc - 1), 2 * c, v.shape[2], d)
    outf = attention(qf, kf, vf, causal=True, q_offset=c, window=window,
                     softcap=softcap, q_block=q_block, kv_block=kv_block)
    out = jnp.concatenate(
        [out0[:, None], outf.reshape(b, nc - 1, c, hq, d)], axis=1)
    return out.reshape(b, sp, hq, d)[:, :s]


# ---------------------------------------------------------------------------
# Attention layer (GQA, RoPE, optional qk-norm / softcap / window)
# ---------------------------------------------------------------------------


def attn_params(key, cfg, dtype, cross: bool = False) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim_
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 6)
    kv_in = cfg.d_cross if (cross and cfg.d_cross) else d
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), dtype),
        "wk": dense_init(ks[1], (kv_in, hkv * hd), dtype),
        "wv": dense_init(ks[2], (kv_in, hkv * hd), dtype),
        "wo": dense_init(ks[3], (hq * hd, d), dtype),
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((hq * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def attn_qkv(p, cfg, x, kv_src=None, positions=None, rope: bool = True):
    """Project to q/k/v heads (+bias, +qk-norm, +rope)."""
    b = x.shape[0]
    hd = cfg.head_dim_
    kv_src = x if kv_src is None else kv_src
    q = dense(x, p["wq"])
    k = dense(kv_src, p["wk"])
    v = dense(kv_src, p["wv"])
    if cfg.attn_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, x.shape[1], cfg.n_heads, hd)
    k = k.reshape(b, kv_src.shape[1], cfg.n_kv_heads, hd)
    v = v.reshape(b, kv_src.shape[1], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True),
         "relu": jax.nn.relu}


def mlp_params(key, d_model: int, d_ff: int, dtype) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp_apply(p, x: jax.Array, act: str = "silu") -> jax.Array:
    g = _ACTS[act](dense(x, p["w_gate"]))
    return dense(g * dense(x, p["w_up"]), p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_params(key, vocab: int, d_model: int, dtype) -> Dict[str, Any]:
    return {"table": dense_init(key, (vocab, d_model), dtype, scale=1.0)}


def embed_apply(p, tokens: jax.Array, scale: Optional[float] = None) -> jax.Array:
    x = jnp.take(p["table"], tokens, axis=0)
    if scale is not None:
        x = x * jnp.asarray(scale, x.dtype)
    return x


def logits_apply(embed_p, x: jax.Array, head_p=None,
                 softcap: Optional[float] = None) -> jax.Array:
    table = head_p if head_p is not None else embed_p["table"]
    kops = _serving_ops()
    if kops is not None:
        logits = kops.tuned_einsum("bsd,vd->bsv", x, table,
                                   preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum(
            "bsd,vd->bsv", x, table, preferred_element_type=jnp.float32
        )
    if softcap is not None:
        logits = _softcap(logits, softcap)
    return logits
