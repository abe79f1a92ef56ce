"""The dense block: attention and a gated MLP in every layer.

A block module is what a configuration file names with ``"block"``
(absent: ``dense``); the harness loads ``reference/<block>.py`` and uses
four functions of it, so a new block is one new file here:

- ``program_params(cfg, seed)``: the program's parameter tree, every
  weight made from the seed in the served dtype, in one jitted call;
- ``logits(cfg, seed, inputs, positions, quant=None, rows=...)``: the plain
  float32 reference, one layer's weights at a time, with ``quant="fp8"``
  the control;
- ``sites(cfg, m)``: ``[((m, k, n), calls per step, output itemsize)]`` of
  the products that pass through the program's dense entry point
  (``layers.dense`` and the LM head) in a step of ``m`` rows;
- ``model_flops(cfg, batch, new_tokens, kv_len)``: a step's useful
  operations.

This block's reference is written from its definition, not from the
program: RMSNorm, rotary positions on the two halves of each head, causal
softmax attention, a gated MLP (SiLU or tanh-approximated GELU), a final
RMSNorm and an untied LM head.  No kernels, no cache, no batching tricks;
every product at ``highest`` precision, so a TPU does not round float32
operands to bfloat16.  Each configuration file lists where this block
departs from the published model.

The stack runs one layer at a time, with that layer's weights made again
from the seed (:func:`reference_layer`), so only one layer's float32
weights are on the device at once.  ``quant="fp8"`` is the control: the
same forward with every dense product's operands rounded to float8 (e4m3,
one scale per row of the activations and per output column of the weight),
the precision below the served bfloat16.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
import work

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


#: the config file keys that size the stack
SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def layer_weights(cfg: dict, words, layer, dtype) -> dict:
    """One layer's weights in the program's parameter layout."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    base = (jnp.asarray(layer, jnp.uint32) + 1) * 16

    def leaf(i, shape, scale):
        return W.uniform(words, base + i, shape, scale)

    w = {
        "norm_attn": 1.0 + leaf(0, (d,), 0.1),
        "norm_ffn": 1.0 + leaf(1, (d,), 0.1),
        "attn": {"wq": leaf(2, (d, hq), d ** -0.5),
                 "wk": leaf(3, (d, hkv), d ** -0.5),
                 "wv": leaf(4, (d, hkv), d ** -0.5),
                 "wo": leaf(5, (hq, d), hq ** -0.5)},
        "mlp": {"w_gate": leaf(6, (d, f), d ** -0.5),
                "w_up": leaf(7, (d, f), d ** -0.5),
                "w_down": leaf(8, (f, d), f ** -0.5)},
    }
    return jax.tree.map(lambda x: x.astype(dtype), w)


def _items(cfg: dict):
    return tuple((k, int(cfg[k])) for k in SIZE_KEYS)


@partial(jax.jit, static_argnums=(0, 2))
def _stack(cfg_items, words, dtype):
    cfg = dict(cfg_items)
    blocks = jax.lax.map(lambda i: layer_weights(cfg, words, i, dtype),
                         jnp.arange(cfg["n_layers"], dtype=jnp.uint32))
    top = W.top_weights(cfg, words, dtype)
    return {"embed": {"table": top["table"]},
            "final_norm": top["final_norm"],
            "lm_head": top["lm_head"],
            "blocks": (blocks,)}


def program_params(cfg: dict, seed: int):
    """Every weight of the stack, in the served dtype, in one jitted call:
    the tree the program's steps take."""
    return _stack(_items(cfg), W.seed_words(seed), jnp.dtype(cfg["dtype"]))


@partial(jax.jit, static_argnums=(0, 3))
def _one_layer(cfg_items, words, layer, dtype):
    return layer_weights(dict(cfg_items), words, layer, dtype)


def reference_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s served values, as float32."""
    return W.as_float32(_one_layer(_items(cfg), W.seed_words(seed),
                                   jnp.uint32(i), jnp.dtype(cfg["dtype"])))


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


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
            w = reference_layer(cfg, seed, layer)
            blocks = [_layer(shape, b, w, quant) for b in blocks]
        idx = jnp.asarray(list(positions), jnp.int32)
        out = [np.asarray(_head(b, top, idx, float(cfg["norm_eps"]), quant))
               for b in blocks]
    return np.concatenate(out, axis=0)


# ---------------------------------------------------------------------------
# the work of a step
# ---------------------------------------------------------------------------


def sites(cfg: dict, m: int) -> List[Tuple[Tuple[int, int, int], int, int]]:
    """[((m, k, n), calls per step, output itemsize)] for one step whose
    products have ``m`` rows (batch x new tokens): q/k/v/o, gate/up/down
    and the LM head."""
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    act = work.ITEMSIZE[cfg["dtype"]]
    layers = cfg["n_layers"]
    return [((m, d, hq), layers, act),       # q
            ((m, d, hkv), 2 * layers, act),  # k, v
            ((m, hq, d), layers, act),       # o
            ((m, d, f), 2 * layers, act),    # gate, up
            ((m, f, d), layers, act),        # down
            ((m, d, v), 1, 4)]               # LM head, float32 logits


def matmul_params(cfg: dict) -> int:
    """Weights that take part in products per token, less the head."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    return cfg["n_layers"] * (d * hq + 2 * d * hkv + hq * d + 3 * d * f)


def model_flops(cfg: dict, batch: int, new_tokens: int, kv_len: int) -> float:
    """Useful operations of one step (the analysis module's formula: 2 per
    weight per token, the logits product, and attention over the keys each
    query sees): ``new_tokens`` per sequence, the last of them at position
    ``kv_len - 1``.  Prefill is ``new_tokens == kv_len`` (causal: half the
    query-key pairs); decode is one token against ``kv_len`` keys."""
    tokens = batch * new_tokens
    f = 2.0 * matmul_params(cfg) * tokens
    f += 2.0 * cfg["d_model"] * cfg["vocab"] * tokens
    if new_tokens == kv_len:
        pairs = new_tokens * kv_len / 2.0
    else:
        pairs = new_tokens * kv_len
    f += batch * 4.0 * pairs * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    return f
