"""Seeded weights and request inputs of a dense attention + MLP stack.

Everything is made on the device from ``--seed``.  The same functions feed
the system under test (all layers at once, stacked, in the served dtype)
and the plain reference (one layer at a time, the same values read in
float32), so both see the same numbers without either taking anything the
other made.

The weights are a hash of (seed, layer, leaf, position), cheap enough to
make 3.8 B parameters in a fraction of a second; prompts and the code
table come from JAX's PRNG, each kind of data from its own stream of the
run's seed, so the weights never depend on how many requests a run made.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

PROMPTS, CODES = 1, 2

#: the config file keys that size the stack
SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
             "d_ff", "vocab")


def base_key(seed: int) -> jax.Array:
    """A key from any whole number: the low 32 bits seed it, the rest are
    folded in, so seeds past 2**32 do not alias smaller ones."""
    lo, hi = _split(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _split(seed: int):
    seed = int(seed) % 2 ** 64
    return seed & 0xFFFFFFFF, seed >> 32


def _words(seed: int) -> jax.Array:
    """The seed as two uint32 words, an argument (not a constant) of the
    jitted generators, so a new seed compiles nothing."""
    return jnp.asarray(_split(seed), jnp.uint32)


def _fmix(h):
    """MurmurHash3's 32-bit finalizer: every input bit reaches every
    output bit."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _uniform(seed_words, stream, shape, scale):
    """Values of mean 0 and standard deviation ``scale`` (uniform), a pure
    function of (seed, stream, position): integer hashing, so they cost
    about one pass over memory and come out the same in any program."""
    lo, hi = seed_words[0], seed_words[1]
    k = _fmix(_fmix(lo ^ _fmix(hi + jnp.uint32(0x9E3779B9)))
              ^ stream.astype(jnp.uint32))
    idx = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
    h = _fmix(_fmix(idx * jnp.uint32(0x9E3779B1) + k) ^ k)
    u = (h >> 8).astype(jnp.float32) * (2.0 ** -24) + 2.0 ** -25
    return (2.0 * u - 1.0) * (math.sqrt(3.0) * scale)


def layer_weights(cfg: dict, seed_words, layer, dtype) -> dict:
    """One layer's weights in the program's parameter layout."""
    d, f = cfg["d_model"], cfg["d_ff"]
    hq = cfg["n_heads"] * cfg["head_dim"]
    hkv = cfg["n_kv_heads"] * cfg["head_dim"]
    base = (jnp.asarray(layer, jnp.uint32) + 1) * 16

    def leaf(i, shape, scale):
        return _uniform(seed_words, base + i, shape, scale)

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


def top_weights(cfg: dict, seed_words, dtype) -> dict:
    """Embedding table, final norm and LM head (logits of about unit
    scale: the head is scaled by 1/sqrt(d_model))."""
    d, v = cfg["d_model"], cfg["vocab"]
    s = jnp.uint32(0)
    w = {"table": _uniform(seed_words, s + 1, (v, d), 1.0),
         "final_norm": 1.0 + _uniform(seed_words, s + 2, (d,), 0.1),
         "lm_head": _uniform(seed_words, s + 3, (v, d), d ** -0.5)}
    return jax.tree.map(lambda x: x.astype(dtype), w)


def _items(cfg: dict):
    return tuple((k, int(cfg[k])) for k in SIZE_KEYS)


@partial(jax.jit, static_argnums=(0, 2))
def _stack(cfg_items, seed_words, dtype):
    cfg = dict(cfg_items)
    blocks = jax.lax.map(lambda i: layer_weights(cfg, seed_words, i, dtype),
                         jnp.arange(cfg["n_layers"], dtype=jnp.uint32))
    top = top_weights(cfg, seed_words, dtype)
    return {"embed": {"table": top["table"]},
            "final_norm": top["final_norm"],
            "lm_head": top["lm_head"],
            "blocks": (blocks,)}


def program_params(cfg: dict, seed: int):
    """Every weight of the stack, in the served dtype, in one jitted call:
    the tree the program's steps take."""
    return _stack(_items(cfg), _words(seed), jnp.dtype(cfg["dtype"]))


@partial(jax.jit, static_argnums=(0, 3))
def _one_layer(cfg_items, seed_words, layer, dtype):
    return layer_weights(dict(cfg_items), seed_words, layer, dtype)


@partial(jax.jit, static_argnums=(0, 2))
def _top(cfg_items, seed_words, dtype):
    return top_weights(dict(cfg_items), seed_words, dtype)


def reference_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s served values, as float32."""
    w = _one_layer(_items(cfg), _words(seed), jnp.uint32(i),
                   jnp.dtype(cfg["dtype"]))
    return jax.tree.map(lambda x: x.astype(jnp.float32), w)


def reference_top(cfg: dict, seed: int) -> dict:
    w = _top(_items(cfg), _words(seed), jnp.dtype(cfg["dtype"]))
    return jax.tree.map(lambda x: x.astype(jnp.float32), w)


# ---------------------------------------------------------------------------
# request inputs
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _prompts(frontend, batch, length, width, dtype, seed_key, wave):
    """Prompts of one wave: one key per (wave, slot)."""
    wkey = jax.random.fold_in(jax.random.fold_in(seed_key, PROMPTS), wave)
    keys = jax.vmap(lambda i: jax.random.fold_in(wkey, i))(jnp.arange(batch))
    if frontend == "tokens":
        return jax.vmap(lambda k: jax.random.randint(
            k, (length,), 0, width, jnp.int32))(keys)
    return jax.vmap(lambda k: jax.random.normal(
        k, (length, width), jnp.float32).astype(dtype))(keys)


def prompts(cfg: dict, seed_key, wave: int, batch: int, length: int):
    """(batch, length) token ids, or (batch, length, d_model) frame
    embeddings in the served dtype, for the ``embeds`` frontend."""
    width = cfg["vocab"] if cfg["frontend"] == "tokens" else cfg["d_model"]
    return _prompts(cfg["frontend"], batch, length, width,
                    jnp.dtype(cfg["dtype"]), seed_key, wave)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _codes(vocab, width, dtype, seed_key):
    return jax.random.normal(jax.random.fold_in(seed_key, CODES),
                             (vocab, width), jnp.float32).astype(dtype)


def code_table(cfg: dict, seed_key) -> jax.Array:
    """The ``embeds`` frontend's next input: row ``t`` is the frame
    embedding of generated code ``t``."""
    return _codes(cfg["vocab"], cfg["d_model"], jnp.dtype(cfg["dtype"]),
                  seed_key)
