"""Seeded weights and request inputs, for every block.

Everything is made on the device from ``--seed``.  A block module
(``reference/<block>.py``) builds its layers' weights from :func:`uniform`
and the embedding, final norm and head from :func:`top_weights`, and feeds
the same functions to the system under test (all layers at once, stacked,
in the served dtype) and to its plain reference (one layer at a time, the
same values read in float32), so both see the same numbers without either
taking anything the other made.

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


def base_key(seed: int) -> jax.Array:
    """A key from any whole number: the low 32 bits seed it, the rest are
    folded in, so seeds past 2**32 do not alias smaller ones."""
    lo, hi = _split(seed)
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def _split(seed: int):
    seed = int(seed) % 2 ** 64
    return seed & 0xFFFFFFFF, seed >> 32


def seed_words(seed: int) -> jax.Array:
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


def uniform(words, stream, shape, scale):
    """Values of mean 0 and standard deviation ``scale`` (uniform), a pure
    function of (seed, stream, position): integer hashing, so they cost
    about one pass over memory and come out the same in any program."""
    lo, hi = words[0], words[1]
    k = _fmix(_fmix(lo ^ _fmix(hi + jnp.uint32(0x9E3779B9)))
              ^ stream.astype(jnp.uint32))
    idx = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
    h = _fmix(_fmix(idx * jnp.uint32(0x9E3779B1) + k) ^ k)
    u = (h >> 8).astype(jnp.float32) * (2.0 ** -24) + 2.0 ** -25
    return (2.0 * u - 1.0) * (math.sqrt(3.0) * scale)


def top_weights(cfg: dict, words, dtype) -> dict:
    """Embedding table, final norm and LM head (logits of about unit
    scale: the head is scaled by 1/sqrt(d_model)), on streams 1-3; a
    block's layers take streams from 16 up."""
    d, v = cfg["d_model"], cfg["vocab"]
    s = jnp.uint32(0)
    w = {"table": uniform(words, s + 1, (v, d), 1.0),
         "final_norm": 1.0 + uniform(words, s + 2, (d,), 0.1),
         "lm_head": uniform(words, s + 3, (v, d), d ** -0.5)}
    return jax.tree.map(lambda x: x.astype(dtype), w)


@partial(jax.jit, static_argnums=(0, 1, 3))
def _top(d_model, vocab, words, dtype):
    return top_weights({"d_model": d_model, "vocab": vocab}, words, dtype)


def reference_top(cfg: dict, seed: int) -> dict:
    """The served embedding, final norm and head, as float32."""
    w = _top(cfg["d_model"], cfg["vocab"], seed_words(seed),
             jnp.dtype(cfg["dtype"]))
    return as_float32(w)


def as_float32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


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
