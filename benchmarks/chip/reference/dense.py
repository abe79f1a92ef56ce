"""Plain float32 forward of the dense decoder block the benchmark serves.

Written from the block's definition, not from the program: RMSNorm, rotary
positions on the two halves of each head, causal softmax attention, a gated
MLP (SiLU or tanh-approximated GELU), a final RMSNorm and an untied LM
head.  No kernels, no cache, no batching tricks; every product at
``highest`` precision, so a TPU does not round float32 operands to
bfloat16.  Each configuration file lists where this block departs from the
published model.

The stack runs one layer at a time, with that layer's weights made again
from the seed (``weights.reference_layer``), so only one layer's float32
weights are on the device at once.  ``quant="fp8"`` is the control: the
same forward with every dense product's operands rounded to float8 (e4m3,
one scale per row of the activations and per output column of the weight),
the precision below the served bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x: (N, S, H, D); rotate the halves (x1, x2) of each head."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32), scale


def matmul(x, w, quant: Optional[str]):
    """x (..., K) @ w (K, N) in float32, or with float8 operands."""
    if quant is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, xs = _fp8(x, -1)
    wq, ws = _fp8(w, 0)
    return jnp.matmul(xq, wq, precision=HIGHEST) * xs * ws


ACTS = {"silu": jax.nn.silu,
        "gelu": partial(jax.nn.gelu, approximate=True)}


@partial(jax.jit, static_argnums=(0, 3))
def _layer(shape, x, w, quant):
    """One decoder layer over ``x`` (N, S, d_model)."""
    n_heads, n_kv, hd, theta, eps, act = shape
    n, s, _ = x.shape
    pos = jnp.arange(s)
    h = rms_norm(x, w["norm_attn"], eps)
    q = matmul(h, w["attn"]["wq"], quant).reshape(n, s, n_heads, hd)
    k = matmul(h, w["attn"]["wk"], quant).reshape(n, s, n_kv, hd)
    v = matmul(h, w["attn"]["wv"], quant).reshape(n, s, n_kv, hd)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HIGHEST)
    x = x + matmul(o.reshape(n, s, n_heads * hd), w["attn"]["wo"], quant)
    h = rms_norm(x, w["norm_ffn"], eps)
    g = ACTS[act](matmul(h, w["mlp"]["w_gate"], quant))
    u = matmul(h, w["mlp"]["w_up"], quant)
    return x + matmul(g * u, w["mlp"]["w_down"], quant)


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, top, idx, eps, quant):
    """Logits at positions ``idx`` of every sequence."""
    x = rms_norm(x[:, idx], top["final_norm"], eps)
    return matmul(x, top["lm_head"].T, quant)


def logits(cfg: dict, seed: int, inputs, positions: Sequence[int],
           quant: Optional[str] = None, rows: int = 4) -> np.ndarray:
    """Reference logits (N, len(positions), vocab) of N sequences.

    ``inputs``: token ids (N, S) for the ``tokens`` frontend, else frame
    embeddings (N, S, d_model).  Sequences are processed ``rows`` at a time
    inside each layer, so attention scores stay small.
    """
    shape = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
             float(cfg["rope_theta"]), float(cfg["norm_eps"]), cfg["act"])
    top = W.reference_top(cfg, seed)
    inputs = jnp.asarray(inputs)
    if cfg["frontend"] == "tokens":
        x = jnp.take(top["table"], inputs, axis=0)
    else:
        x = inputs.astype(jnp.float32)
    blocks = [x[i:i + rows] for i in range(0, x.shape[0], rows)]
    del x
    with jax.default_matmul_precision("highest"):
        for layer in range(cfg["n_layers"]):
            w = W.reference_layer(cfg, seed, layer)
            blocks = [_layer(shape, b, w, quant) for b in blocks]
        idx = jnp.asarray(list(positions), jnp.int32)
        out = [np.asarray(_head(b, top, idx, float(cfg["norm_eps"]), quant))
               for b in blocks]
    return np.concatenate(out, axis=0)
