"""A block for the harness tests: attention with q/k norms and a routed
top-k expert FFN in every layer, as the zoo's ``olmoe-1b-7b`` serves it.

The tests copy it into a throwaway root as ``reference/moe_topk.py`` and
name it from a configuration file (``"block": "moe_topk"``, with the
file's own ``moe``), to show that a block the dense one does not cover is
one new file.  Its reference is written from the block's definition: the
router's softmax over every expert, each token's ``top_k`` experts with
their gates renormalised to sum to 1, every expert computed over every
token and the chosen ones summed.  No capacity and no dispatch, so it
matches the program only where the program drops no token.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
import work
from reference import dense as D

SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "vocab")
MOE_KEYS = ("n_experts", "top_k", "d_ff_expert")


def _items(cfg: dict):
    return (tuple((k, int(cfg[k])) for k in SIZE_KEYS)
            + tuple((k, int(cfg["moe"][k])) for k in MOE_KEYS))


def layer_weights(cfg: dict, words, layer, dtype) -> dict:
    """One layer's weights in the program's layout; the router stays in
    float32, as the program keeps it."""
    d, hd = cfg["d_model"], cfg["head_dim"]
    hq, hkv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    e, f = cfg["n_experts"], cfg["d_ff_expert"]
    base = (jnp.asarray(layer, jnp.uint32) + 1) * 16

    def leaf(i, shape, scale):
        return W.uniform(words, base + i, shape, scale)

    w = {"norm_attn": 1.0 + leaf(0, (d,), 0.1),
         "norm_ffn": 1.0 + leaf(1, (d,), 0.1),
         "attn": {"wq": leaf(2, (d, hq), d ** -0.5),
                  "wk": leaf(3, (d, hkv), d ** -0.5),
                  "wv": leaf(4, (d, hkv), d ** -0.5),
                  "wo": leaf(5, (hq, d), hq ** -0.5),
                  "q_norm": 1.0 + leaf(6, (hd,), 0.1),
                  "k_norm": 1.0 + leaf(7, (hd,), 0.1)},
         "moe": {"w_gate": leaf(8, (e, d, f), d ** -0.5),
                 "w_up": leaf(9, (e, d, f), d ** -0.5),
                 "w_down": leaf(10, (e, f, d), f ** -0.5)}}
    w = jax.tree.map(lambda x: x.astype(dtype), w)
    w["moe"]["router"] = leaf(11, (d, e), d ** -0.5)
    return w


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
    return _stack(_items(cfg), W.seed_words(seed), jnp.dtype(cfg["dtype"]))


@partial(jax.jit, static_argnums=(0, 3))
def _one_layer(cfg_items, words, layer, dtype):
    return layer_weights(dict(cfg_items), words, layer, dtype)


def experts(h, w, top_k, act, quant):
    """Each token's ``top_k`` experts by the router, gate-weighted."""
    probs = jax.nn.softmax(jnp.matmul(h, w["router"],
                                      precision=D.HIGHEST), axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / gates.sum(-1, keepdims=True)
    every = jnp.stack(
        [D.matmul(D.ACTS[act](D.matmul(h, w["w_gate"][e], quant))
                  * D.matmul(h, w["w_up"][e], quant), w["w_down"][e], quant)
         for e in range(w["router"].shape[1])], axis=-2)
    chosen = jnp.take_along_axis(every, idx[..., None], axis=-2)
    return (chosen * gates[..., None]).sum(-2)


@partial(jax.jit, static_argnums=(0, 3))
def _layer(shape, x, w, quant):
    n_heads, n_kv, hd, theta, eps, act, top_k = shape
    n, s, _ = x.shape
    pos = jnp.arange(s)
    h = D.rms_norm(x, w["norm_attn"], eps)
    q = D.matmul(h, w["attn"]["wq"], quant).reshape(n, s, n_heads, hd)
    k = D.matmul(h, w["attn"]["wk"], quant).reshape(n, s, n_kv, hd)
    v = D.matmul(h, w["attn"]["wv"], quant).reshape(n, s, n_kv, hd)
    q = D.rope(D.rms_norm(q, w["attn"]["q_norm"], eps), pos, theta)
    k = D.rope(D.rms_norm(k, w["attn"]["k_norm"], eps), pos, theta)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=D.HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(pos[:, None] >= pos[None, :], scores, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, axis=-1), v,
                   precision=D.HIGHEST)
    x = x + D.matmul(o.reshape(n, s, n_heads * hd), w["attn"]["wo"], quant)
    h = D.rms_norm(x, w["norm_ffn"], eps)
    return x + experts(h, w["moe"], top_k, act, quant)


@partial(jax.jit, static_argnums=(3, 4))
def _head(x, top, idx, eps, quant):
    x = D.rms_norm(x[:, idx], top["final_norm"], eps)
    return D.matmul(x, top["lm_head"].T, quant)


def logits(cfg: dict, seed: int, inputs, positions: Sequence[int],
           quant: Optional[str] = None, rows: int = 4) -> np.ndarray:
    """Reference logits (N, len(positions), vocab) of N token sequences."""
    shape = (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
             float(cfg["rope_theta"]), float(cfg["norm_eps"]), cfg["act"],
             cfg["moe"]["top_k"])
    top = W.reference_top(cfg, seed)
    x = jnp.take(top["table"], jnp.asarray(inputs), axis=0)
    blocks = [x[i:i + rows] for i in range(0, x.shape[0], rows)]
    with jax.default_matmul_precision("highest"):
        for layer in range(cfg["n_layers"]):
            w = W.as_float32(_one_layer(_items(cfg), W.seed_words(seed),
                                        jnp.uint32(layer),
                                        jnp.dtype(cfg["dtype"])))
            blocks = [_layer(shape, b, w, quant) for b in blocks]
        idx = jnp.asarray(list(positions), jnp.int32)
        out = [np.asarray(_head(b, top, idx, float(cfg["norm_eps"]), quant))
               for b in blocks]
    return np.concatenate(out, axis=0)


def sites(cfg: dict, m: int):
    """q/k/v/o and the LM head pass through the dense entry point; the
    router and the experts do not."""
    d, v = cfg["d_model"], cfg["vocab"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    act = work.ITEMSIZE[cfg["dtype"]]
    layers = cfg["n_layers"]
    return [((m, d, hq), layers, act), ((m, d, hkv), 2 * layers, act),
            ((m, hq, d), layers, act), ((m, d, v), 1, 4)]


def model_flops(cfg: dict, batch: int, new_tokens: int, kv_len: int) -> float:
    """Two operations per weight a token uses (attention, router, its
    ``top_k`` experts), the logits product, and attention over the keys
    each query sees (prefill: ``new_tokens == kv_len``, causal)."""
    d, hd, moe = cfg["d_model"], cfg["head_dim"], cfg["moe"]
    hq, hkv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    per_layer = (2 * d * hq + 2 * d * hkv + d * moe["n_experts"]
                 + moe["top_k"] * 3 * d * moe["d_ff_expert"])
    tokens = batch * new_tokens
    pairs = (new_tokens * kv_len / 2.0 if new_tokens == kv_len
             else new_tokens * kv_len)
    return (2.0 * (cfg["n_layers"] * per_layer + d * cfg["vocab"]) * tokens
            + batch * 4.0 * pairs * hq * cfg["n_layers"])
