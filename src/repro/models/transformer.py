"""Model assembly: embeddings -> scan over stacked layer-periods -> LM head.

Every assigned architecture is a repeating **period** of heterogeneous layers
(attn / local-attn / mamba / rwkv6 / cross-attn mixers x dense / moe / rwkv
channel-mix FFNs).  Parameters for each position-in-period are stacked over
``n_periods`` on axis 0 and the forward runs ``lax.scan`` over periods with
per-period remat — this keeps the lowered HLO one-period-sized, which is what
makes 80 production-mesh compiles tractable (and is the standard MaxText
trick on real fleets).

Three entry points (all pure functions of (params, batch[, cache])):
  * :func:`forward`        — full-sequence logits (train / prefill)
  * :func:`decode_step`    — one token with a KV/state cache
  * :func:`init_cache`     — allocate the decode cache pytree
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    ATTN,
    ATTN_LOCAL,
    CROSS_ATTN,
    DENSE,
    MAMBA,
    MOE,
    RWKV6,
    LayerSpec,
    ModelConfig,
)
from repro.runtime.sharding import ashard, current_mesh, keep_kv_layout
from . import layers as L
from . import mamba as M
from . import moe as X
from . import rwkv6 as R

RWKV_CMIX = "rwkv_cmix"


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def _block_init(key, spec: LayerSpec, cfg: ModelConfig):
    dt = _dtype(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"norm_attn": jnp.ones((d,), dt), "norm_ffn": jnp.ones((d,), dt)}
    if cfg.post_norm:
        p["post_attn"] = jnp.ones((d,), dt)
        p["post_ffn"] = jnp.ones((d,), dt)
    if spec.mixer in (ATTN, ATTN_LOCAL, CROSS_ATTN):
        p["attn"] = L.attn_params(ks[0], cfg, dt)
        if spec.mixer == CROSS_ATTN:
            p["cross"] = L.attn_params(ks[1], cfg, dt, cross=True)
            p["norm_cross"] = jnp.ones((d,), dt)
    elif spec.mixer == MAMBA:
        p["mamba"] = M.mamba_params(
            ks[0], d, cfg.ssm_d_state, cfg.ssm_d_conv, cfg.ssm_expand, dt
        )
    elif spec.mixer == RWKV6:
        p["rwkv"] = R.rwkv_time_mix_params(ks[0], d, cfg.rwkv_head_dim, dt)
    else:
        raise ValueError(spec.mixer)

    if spec.ffn == DENSE:
        if spec.mixer == RWKV6:
            p["cmix"] = R.channel_mix_params(ks[2], d, cfg.d_ff, dt)
        else:
            p["mlp"] = L.mlp_params(ks[2], d, cfg.d_ff, dt)
    elif spec.ffn == MOE:
        p["moe"] = X.moe_params(ks[2], d, cfg.moe, dt)
    else:
        raise ValueError(spec.ffn)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dt = _dtype(cfg)
    k_embed, k_head, k_blocks = jax.random.split(key, 3)
    params: Dict[str, Any] = {
        "embed": L.embed_params(k_embed, cfg.vocab, cfg.d_model, dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (cfg.vocab, cfg.d_model), dt, 1.0)
    blocks = []
    for pos, spec in enumerate(cfg.period):
        keys = jax.random.split(jax.random.fold_in(k_blocks, pos), cfg.n_periods)
        blocks.append(jax.vmap(lambda k: _block_init(k, spec, cfg))(keys))
    params["blocks"] = tuple(blocks)
    return params


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        keys = jax.tree_util.keystr(path)
        n = math.prod(leaf.shape)
        if active_only and cfg.moe is not None and "moe" in keys and "shared" not in keys and "router" not in keys:
            n = int(n * cfg.moe.top_k / cfg.moe.n_experts)
        total += n
    return total


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Tuple[Any, ...]:
    """Decode cache: one entry per period position, leaves stacked (n_periods, ...).

    Attention leaves ``k``/``v`` hold (n_periods, B, buf, H_kv, D), the
    sequence on axis 2: a step writes its tokens' slots of one layer.
    Every other leaf is a layer's whole state (Mamba ``h``/``conv``, RWKV
    ``s``/``xt``/``xc``, cross-attention ``ck``/``cv``), written whole.
    The layer scan carries these stacks and updates them in place
    (:func:`_run_layers`), so a caller that donates the cache to the jitted
    step gets the same buffers back."""
    dt = _dtype(cfg)
    np_, hd = cfg.n_periods, cfg.head_dim_
    caches = []
    for spec in cfg.period:
        c: Dict[str, Any] = {}
        if spec.mixer in (ATTN, ATTN_LOCAL, CROSS_ATTN):
            win = spec.window if spec.mixer == ATTN_LOCAL else None
            buf = min(max_len, win) if win else max_len
            c["k"] = jnp.zeros((np_, batch, buf, cfg.n_kv_heads, hd), dt)
            c["v"] = jnp.zeros((np_, batch, buf, cfg.n_kv_heads, hd), dt)
            if spec.mixer == CROSS_ATTN:
                c["ck"] = jnp.zeros((np_, batch, max(cfg.n_cross_tokens, 1),
                                     cfg.n_kv_heads, hd), dt)
                c["cv"] = jnp.zeros_like(c["ck"])
        elif spec.mixer == MAMBA:
            d_inner = cfg.ssm_expand * cfg.d_model
            c["h"] = jnp.zeros((np_, batch, d_inner, cfg.ssm_d_state), jnp.float32)
            c["conv"] = jnp.zeros((np_, batch, cfg.ssm_d_conv - 1, d_inner), dt)
        elif spec.mixer == RWKV6:
            h = cfg.d_model // cfg.rwkv_head_dim
            c["s"] = jnp.zeros((np_, batch, h, cfg.rwkv_head_dim, cfg.rwkv_head_dim),
                               jnp.float32)
            c["xt"] = jnp.zeros((np_, batch, cfg.d_model), dt)
        if spec.ffn == DENSE and spec.mixer == RWKV6:
            c["xc"] = jnp.zeros((np_, batch, cfg.d_model), dt)
        caches.append(c)
    return tuple(caches)


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


def _layer(leaf, i):
    """Layer ``i``'s slice of a stacked cache leaf."""
    return jax.lax.dynamic_index_in_dim(leaf, i, keepdims=False)


def _put(leaf, i, value, *at):
    """``leaf`` with ``value`` written into layer ``i``'s slice at offsets
    ``at`` of the slice's leading axes (zero elsewhere): one
    ``dynamic_update_slice`` of the stack, in place in the scan carry."""
    start = (i, *at) + (0,) * (value.ndim - len(at))
    return jax.lax.dynamic_update_slice(leaf, value[None], start)


def _put_token(leaf, i, value, pos):
    """:func:`_put` of one token's K or V at sequence position ``pos``,
    the stack held to the layout it enters the step with
    (:func:`~repro.runtime.sharding.keep_kv_layout`)."""
    return keep_kv_layout(_put(leaf, i, value, 0, pos))


def _apply_mixer(spec, p, cfg, h, cache, li, cache_len, positions, encoder,
                 decode):
    """Mixer on normed input ``h``.  Returns (out, cache): ``cache`` is the
    period position's stacked cache with layer ``li``'s entries written."""
    if spec.mixer in (ATTN, ATTN_LOCAL, CROSS_ATTN):
        q, k, v = L.attn_qkv(p["attn"], cfg, h, positions=positions)
        window = spec.window if spec.mixer == ATTN_LOCAL else None

        def full_seq_attn(q, k, v):
            # NOTE: the O(S·window) chunk-folded `L.local_attention` is
            # numerically exact and saves the masked-block compute, but
            # under GSPMD its batch-fold reshapes fight the seq-sharded
            # residual layout (gemma3 train_4k: +17 GiB temp, +500 GiB of
            # collective-permute — EXPERIMENTS §Perf iter 13), so the
            # masked blocked path stays the default; the chunked form is
            # the right shape for an explicit-layout Pallas kernel.
            return L.attention(q, k, v, causal=True, window=window,
                               softcap=cfg.attn_softcap)

        if cache is None:
            out = full_seq_attn(q, k, v)
        elif not decode:  # prefill: run full attention, fill the cache
            out = full_seq_attn(q, k, v)
            # the layer's whole slice, zeros past the prompt: decode reads
            # the masked slots too, and XLA on TPU may hand the scan a
            # fresh stack it never filled with init_cache's zeros
            buf = cache["k"].shape[2]
            k, v = (x[:, -buf:] if buf < x.shape[1]  # windowed: the tail
                    else jnp.pad(x, ((0, 0), (0, buf - x.shape[1]),
                                     (0, 0), (0, 0)))
                    for x in (k, v))
            cache = dict(cache, k=_put(cache["k"], li, k),
                         v=_put(cache["v"], li, v))
        else:  # decode: write one token's slot, attend over the layer
            cache = dict(cache, k=_put_token(cache["k"], li, k, cache_len),
                         v=_put_token(cache["v"], li, v, cache_len))
            out = L.attention(q, _layer(cache["k"], li),
                              _layer(cache["v"], li), causal=True,
                              q_offset=cache_len, kv_len=cache_len + 1,
                              window=window, softcap=cfg.attn_softcap)
        if spec.mixer == CROSS_ATTN:
            out = L.dense(out.reshape(*h.shape[:2], -1), p["attn"]["wo"])
            hx = L.rms_norm(h + out.astype(h.dtype), p["norm_cross"])
            if decode:
                ck, cv = _layer(cache["ck"], li), _layer(cache["cv"], li)
                qx = L.dense(hx, p["cross"]["wq"]).reshape(
                    *hx.shape[:2], cfg.n_heads, cfg.head_dim_)
            else:
                qx, ck, cv = L.attn_qkv(p["cross"], cfg, hx, kv_src=encoder,
                                        rope=False)
                if cache is not None:
                    cache = dict(cache, ck=_put(cache["ck"], li, ck),
                                 cv=_put(cache["cv"], li, cv))
            xout = L.attention(qx, ck, cv, causal=False)
            return (out + L.dense(xout.reshape(*h.shape[:2], -1),
                                  p["cross"]["wo"]).astype(out.dtype)), cache
        return L.dense(out.reshape(*h.shape[:2], -1),
                       p["attn"]["wo"]), cache

    if spec.mixer == MAMBA:
        st = (M.MambaState(_layer(cache["h"], li), _layer(cache["conv"], li))
              if cache is not None else None)
        if decode:
            out, st2 = M.mamba_decode(p["mamba"], h, st)
        else:
            out, st2 = M.mamba_apply(p["mamba"], h, st)
        if cache is not None:
            cache = dict(cache, h=_put(cache["h"], li, st2.h),
                         conv=_put(cache["conv"], li, st2.conv))
        return out, cache

    if spec.mixer == RWKV6:
        if decode:
            out, s2, xt = R.time_mix_decode(
                p["rwkv"], h, cfg.rwkv_head_dim, _layer(cache["s"], li),
                _layer(cache["xt"], li))
        else:
            s0 = _layer(cache["s"], li) if cache is not None else None
            xp = _layer(cache["xt"], li) if cache is not None else None
            out, s2, xt = R.time_mix_chunked(
                p["rwkv"], h, cfg.rwkv_head_dim, state=s0, x_prev=xp)
        if cache is not None:
            cache = dict(cache, s=_put(cache["s"], li, s2),
                         xt=_put(cache["xt"], li, xt))
        return out, cache

    raise ValueError(spec.mixer)


def _apply_ffn(spec, p, cfg, h, cache, li, decode):
    aux = None
    if spec.ffn == MOE:
        out, aux = X.moe_apply(p["moe"], h, cfg.moe, cfg.act)
    elif spec.mixer == RWKV6:
        xc = _layer(cache["xc"], li) if (cache is not None and decode) else None
        out, last = R.channel_mix(p["cmix"], h, x_prev=xc)
        if cache is not None:
            cache = dict(cache, xc=_put(cache["xc"], li, last))
    else:
        out = L.mlp_apply(p["mlp"], h, cfg.act)
    return out, cache, aux


def _apply_block(spec, p, cfg, x, cache, li, cache_len, positions, encoder,
                 decode, aux_acc):
    h = L.rms_norm(x, p["norm_attn"])
    mix, new_cache = _apply_mixer(spec, p, cfg, h, cache, li, cache_len,
                                  positions, encoder, decode)
    if cfg.post_norm:
        mix = L.rms_norm(mix, p["post_attn"])
    if cfg.parallel_block:
        ff, new_cache, aux = _apply_ffn(spec, p, cfg, h, new_cache, li,
                                        decode)
        x = x + mix.astype(x.dtype) + ff.astype(x.dtype)
    else:
        x = x + mix.astype(x.dtype)
        h2 = L.rms_norm(x, p["norm_ffn"])
        ff, new_cache, aux = _apply_ffn(spec, p, cfg, h2, new_cache, li,
                                        decode)
        if cfg.post_norm:
            ff = L.rms_norm(ff, p["post_ffn"])
        x = x + ff.astype(x.dtype)
    x = ashard(x, ("batch", "act_seq", None))
    if aux is not None:
        aux_acc = aux_acc + aux["moe_aux_loss"] + aux["moe_z_loss"]
    return x, new_cache, aux_acc


# ---------------------------------------------------------------------------
# Full model
# ---------------------------------------------------------------------------


def _embed_in(params, cfg, batch) -> jax.Array:
    if cfg.frontend == "tokens":
        scale = math.sqrt(cfg.d_model) if cfg.embed_scale else None
        x = L.embed_apply(params["embed"], batch["tokens"], scale)
    else:  # audio / stub frontends supply precomputed frame embeddings
        x = batch["embeds"].astype(_dtype(cfg))
    return ashard(x, ("batch", "act_seq", None))


def _run_layers(params, cfg, x, caches, cache_len, positions, encoder,
                decode, remat=True):
    """Scan the layer periods over ``x``.  Returns (x, caches, aux).

    The stacked caches travel in the scan carry with the period index, not
    as scanned inputs and outputs: layer ``i`` reads its slice of each
    stack and writes its entries back with one ``dynamic_update_slice``
    (a decode step: one token's K/V at ``cache_len``; prefill: the
    prompt's block; states: the layer's whole slice).  XLA aliases a
    while-loop carry updated that way, so the returned caches are the
    input buffers when the caller donates them; without donation the
    step copies the cache once on entry."""
    n_specs = len(cfg.period)
    policy = (cfg.remat_policy if remat is True
              else (remat if isinstance(remat, str) else "none"))

    def make_block_fn(spec):
        def f(p, x, cache, li, aux, cache_len, positions, encoder):
            return _apply_block(spec, p, cfg, x, cache, li, cache_len,
                                positions, encoder, decode, aux)
        return f

    block_fns = [make_block_fn(spec) for spec in cfg.period]
    if policy == "block":
        # per-layer remat: the scan backward saves each block's INPUT (one
        # seq-sharded residual per layer) and recomputes one block at a
        # time — peak transient = max over layers, not sum over the period
        # (decisive for wide heterogeneous periods, EXPERIMENTS §Perf).
        block_fns = [jax.checkpoint(f) for f in block_fns]

    scanned, held = params["blocks"], ({},) * n_specs
    # under a mesh the kernel's custom call would gather a sharded stack
    # whole, where a scanned slice gathers one layer
    if L._serving_ops() is not None and current_mesh() is None:
        scanned, held = zip(*map(_hold_dense_weights, scanned))

    def period_body(carry, blocks):
        x, aux, li, pcaches = carry
        pcaches = list(pcaches)
        for pos in range(n_specs):
            x, pcaches[pos], aux = block_fns[pos](
                _layer_views(blocks[pos], held[pos], li), x, pcaches[pos],
                li, aux, cache_len, positions, encoder)
        return (x, aux, li + 1, tuple(pcaches)), None

    body = jax.checkpoint(period_body) if policy == "period" else period_body
    carry = (x, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
             caches if caches is not None else (None,) * n_specs)
    (x, aux, _, new_caches), _ = jax.lax.scan(
        body, carry, tuple(scanned), length=cfg.n_periods)
    return x, (new_caches if caches is not None else None), aux


def _hold_dense_weights(p):
    """(``p`` less the stacks that ``L.dense`` consumes, those stacks).

    While a registry is served the layer scan holds these stacks whole
    instead of scanning them, and hands each layer a
    :class:`~repro.models.layers.LayerWeight` view: the routed kernel then
    reads the layer's blocks out of the stack, where a scanned weight is a
    slice that XLA copies before the kernel can read it."""
    p, held = dict(p), {}
    for group, names in L.DENSE_WEIGHTS.items():
        if group in p:
            p[group] = dict(p[group])
            held[group] = {n: p[group].pop(n) for n in names}
    return p, held


def _layer_views(p, held, li):
    """``p`` with layer ``li``'s views of the ``held`` stacks put back."""
    if not held:
        return p
    p = dict(p)
    for group, stacks in held.items():
        p[group] = dict(p[group], **{n: L.LayerWeight(w, li)
                                     for n, w in stacks.items()})
    return p


def hidden_states(
    params: Dict[str, Any],
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    caches: Optional[Tuple] = None,
    remat: bool = True,
) -> Tuple[jax.Array, Optional[Tuple], jax.Array]:
    """Full-sequence forward up to the final norm (no logits).

    Returns (hidden (B, S, D), new_caches, aux_loss) — the training loss
    consumes this through a seq-chunked CE so the (B, S, vocab) logits are
    never materialized (decisive for the 256k-vocab archs)."""
    x = _embed_in(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)[None]
    encoder = batch.get("encoder")
    x, new_caches, aux = _run_layers(
        params, cfg, x, caches, 0, positions, encoder, decode=False,
        remat=remat)
    x = L.rms_norm(x, params["final_norm"])
    return x, new_caches, aux


def forward(
    params: Dict[str, Any],
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],
    caches: Optional[Tuple] = None,
    remat: bool = True,
) -> Tuple[jax.Array, Optional[Tuple], jax.Array]:
    """Full-sequence forward (train when caches=None, prefill otherwise).

    Returns (logits, new_caches, aux_loss)."""
    x, new_caches, aux = hidden_states(params, cfg, batch, caches, remat)
    logits = L.logits_apply(params["embed"], x, params.get("lm_head"),
                            cfg.logit_softcap)
    logits = ashard(logits, ("batch", None, "model"))
    return logits, new_caches, aux


def decode_step(
    params: Dict[str, Any],
    cfg: ModelConfig,
    batch: Dict[str, jax.Array],      # one-token inputs
    caches: Tuple,
    cache_len: jax.Array,             # i32 scalar: valid cache length
) -> Tuple[jax.Array, Tuple]:
    """One decode step.  Returns (logits (B, 1, V), new_caches).

    Writes the token's K/V into slot ``cache_len`` of each attention layer
    and each layer's new state, in place in the layer scan's carry: jit
    the step with the cache donated (``donate_argnums``) and the new
    caches reuse its buffers, with no copy of a layer or of the stack."""
    x = _embed_in(params, cfg, batch)
    positions = jnp.full((1, 1), cache_len, jnp.int32)
    x, new_caches, _ = _run_layers(
        params, cfg, x, caches, cache_len, positions, None, decode=True,
        remat=False)
    x = L.rms_norm(x, params["final_norm"])
    logits = L.logits_apply(params["embed"], x, params.get("lm_head"),
                            cfg.logit_softcap)
    return logits, new_caches
