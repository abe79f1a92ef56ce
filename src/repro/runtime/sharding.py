"""Logical-axis sharding rules (MaxText-style) + mesh context.

Physical meshes (``launch/mesh.py``):
    single-pod  (data=16, model=16)            — v5e-256
    multi-pod   (pod=2, data=16, model=16)     — 2 pods, 512 chips

Logical axes used by models / optimizer / caches:

    batch   -> (pod, data)      activations' leading dim
    model   -> model            generic tensor-parallel dim
    heads   -> model            attention Q heads
    kv      -> model            attention KV heads (replicated if indivisible)
    mlp     -> model            FFN hidden
    expert  -> model            MoE expert dim (expert parallelism)
    vocab   -> model            vocab-parallel embedding / logits
    seq     -> data             long-context decode: KV cache sequence dim
    zero    -> data             optimizer-state sharding (ZeRO-1/2)

Every rule applies **only when the dim is divisible** by the mesh-axis
product; otherwise the dim is replicated and the fallback is recorded in
:data:`FALLBACKS` (DESIGN §5: llama4's 40 Q-heads on model=16, kv_heads=8 on
model=16, ...).
"""
from __future__ import annotations

import math
import re
import threading
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_TLS = threading.local()

# logical -> physical mesh axis (tuples allowed)
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "model": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "mlp": ("model",),
    "expert": ("model",),
    "vocab": ("model",),
    "seq": ("data",),
    "zero": ("data",),
    # sequence parallelism: residual-stream seq dim between blocks -> model
    # (GSPMD inserts the all-gather before attention / reduce-scatter after,
    # so the n_periods saved scan carries are 1/model_size the size)
    "act_seq": ("model",),
    # flattened token dim (MoE dispatch): all mesh axes
    "tokens": ("pod", "data", "model"),
    # token dim sharded over data only (MoE internals keep tokens on
    # (pod, data) so the expert buffers can take (model, data))
    "tokens_dp": ("pod", "data"),
    # expert FFN hidden dim: static 2nd shard axis for expert weights
    # (expert -> model, d_ff_expert -> data).  Fully 2D-sharded expert
    # weights never need FSDP gathers — the (small) dispatched activations
    # reshard instead of the (huge) weights.
    "expert_ff": ("data",),
}

FALLBACKS: List[str] = []  # record of replication fallbacks (for DESIGN/EXPERIMENTS)


def _record_fallback(msg: str) -> None:
    if msg not in FALLBACKS:
        FALLBACKS.append(msg)


@contextmanager
def use_mesh(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    """Install the mesh + rules for :func:`ashard` activation constraints."""
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, dict(rules or DEFAULT_RULES))
    try:
        yield
    finally:
        _TLS.ctx = prev


def current_mesh() -> Optional[Mesh]:
    ctx = getattr(_TLS, "ctx", None)
    return ctx[0] if ctx else None


def _resolve(mesh: Mesh, rules, logical: Optional[str], dim: int):
    """Logical axis -> physical axes for a concrete dim, or None (replicate)."""
    if logical is None:
        return None
    phys = tuple(a for a in rules.get(logical, ()) if a in mesh.axis_names)
    if not phys:
        return None
    prod = math.prod(mesh.shape[a] for a in phys)
    if dim % prod != 0:
        _record_fallback(f"dim {dim} ({logical}) % {prod} != 0 -> replicated")
        return None
    return phys if len(phys) > 1 else phys[0]


def logical_spec(mesh: Mesh, rules, axes: Sequence[Optional[str]],
                 shape: Sequence[int]) -> P:
    return P(*(_resolve(mesh, rules, ax, d) for ax, d in zip(axes, shape)))


def ashard(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Activation sharding constraint; no-op outside a mesh context."""
    ctx = getattr(_TLS, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_spec(mesh, rules, axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules (path + shape -> logical axes)
# ---------------------------------------------------------------------------

# (regex on the flattened path, logical axes for the TRAILING dims).
# Leading dims not covered (e.g. the n_periods stack axis) are replicated.
_PARAM_RULES: List[Tuple[str, Tuple[Optional[str], ...]]] = [
    # embeddings / head: vocab-parallel
    (r"embed.*table", ("vocab", None)),
    (r"lm_head", ("vocab", None)),
    # MoE: expert parallelism (experts over model axis; expert-internal dims
    # stay local so each expert's FFN runs on one shard group).  Shared-
    # expert rules must precede the generic expert rules (both match "moe.").
    (r"moe.*router", (None, None)),
    (r"moe.*shared.*w_(gate|up)$", (None, "mlp")),
    (r"moe.*shared.*w_down", ("mlp", None)),
    (r"moe.*w_(gate|up)$", ("expert", None, "expert_ff")),
    (r"moe.*w_down", ("expert", "expert_ff", None)),
    # attention projections (column-parallel in, row-parallel out)
    (r"attn.*w(q)$|cross.*wq$", (None, "heads")),
    (r"attn.*w(k|v)$|cross.*w(k|v)$", (None, "kv")),
    (r"attn.*wo$|cross.*wo$", ("heads", None)),
    (r"b(q)$", ("heads",)),
    (r"b(k|v)$", ("kv",)),
    # dense mlp
    (r"mlp.*w_(gate|up)$", (None, "mlp")),
    (r"mlp.*w_down", ("mlp", None)),
    # rwkv time-mix (heads over model via the flattened d axis)
    (r"rwkv.*w_(r|k|v|g)$", (None, "model")),
    (r"rwkv.*w_o$", ("model", None)),
    (r"rwkv.*u$", ("model", None)),
    (r"rwkv.*w_lora_a", (None, None)),
    (r"rwkv.*w_lora_b", (None, "model")),
    (r"rwkv.*w0", ("model",)),
    # rwkv channel-mix
    (r"cmix.*w_k$", (None, "mlp")),
    (r"cmix.*w_v$", ("mlp", None)),
    (r"cmix.*w_r$", (None, "model")),
    # mamba (d_inner over model)
    (r"mamba.*in_proj", (None, "model")),
    (r"mamba.*conv_w", (None, "model")),
    (r"mamba.*conv_b", ("model",)),
    (r"mamba.*x_proj", ("model", None)),
    (r"mamba.*dt_proj", (None, "model")),
    (r"mamba.*dt_bias", ("model",)),
    (r"mamba.*a_log", ("model", None)),
    (r"mamba.*\bd\b", ("model",)),
    (r"mamba.*out_proj", ("model", None)),
]


def _path_str(path) -> str:
    return jax.tree_util.keystr(path).replace("'", "").replace("]", "").replace(
        "[", ".")


def _axes_for(path_str: str, ndim: int) -> Tuple[Optional[str], ...]:
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path_str):
            if len(axes) > ndim:
                return (None,) * ndim
            return (None,) * (ndim - len(axes)) + tuple(axes)
    return (None,) * ndim  # norms, scalars, mu vectors: replicated


def param_pspecs(params_tree: Any, mesh: Mesh,
                 rules: Optional[Dict] = None,
                 special_kv_heads: Optional[int] = None) -> Any:
    """PartitionSpec pytree matching ``params_tree`` (shapes or arrays).

    ``special_kv_heads``: if given, KV projections are only sharded when the
    *head count* divides the model axis (a flat-dim divisibility check would
    wrongly split single heads across shards)."""
    rules = dict(rules or DEFAULT_RULES)
    model_size = math.prod(
        mesh.shape[a] for a in rules["kv"] if a in mesh.axis_names) or 1

    def one(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        axes = _axes_for(ps, len(shape))
        if special_kv_heads is not None and "kv" in axes:
            if special_kv_heads % model_size != 0:
                _record_fallback(
                    f"kv_heads={special_kv_heads} % model={model_size} != 0 "
                    f"-> KV projections replicated ({ps})")
                axes = tuple(None if a == "kv" else a for a in axes)
        return logical_spec(mesh, rules, axes, shape)

    return jax.tree_util.tree_map_with_path(one, params_tree)


def zero_pspecs(param_specs: Any, shapes: Any, mesh: Mesh,
                rules: Optional[Dict] = None,
                min_size: int = 0) -> Any:
    """ZeRO/FSDP sharding: spec + 'data' on the first unsharded dim that
    divides the data axis.  Applied to optimizer state (ZeRO-1/2) and — via
    :func:`fsdp_pspecs` — to the bf16 params themselves (FSDP; GSPMD inserts
    the per-layer all-gather inside the period scan).  ``min_size`` skips
    small leaves (norm scales etc.) where gather latency beats memory."""
    rules = dict(rules or DEFAULT_RULES)
    data_axes = tuple(a for a in rules["zero"] if a in mesh.axis_names)
    if not data_axes:
        return param_specs
    dsize = math.prod(mesh.shape[a] for a in data_axes)

    def _uses_data(parts) -> bool:
        for p in parts:
            for a in (p if isinstance(p, tuple) else (p,)):
                if a in data_axes:
                    return True
        return False

    def one(spec: P, leaf) -> P:
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        if math.prod(leaf.shape) < min_size or _uses_data(parts):
            return P(*parts)  # small, or already data-sharded (2D experts)
        for i, (p, d) in enumerate(zip(parts, leaf.shape)):
            if p is None and d % dsize == 0 and d >= dsize:
                parts[i] = data_axes if len(data_axes) > 1 else data_axes[0]
                return P(*parts)
        return P(*parts)

    return jax.tree_util.tree_map(one, param_specs, shapes)


def fsdp_pspecs(param_specs: Any, shapes: Any, mesh: Mesh,
                rules: Optional[Dict] = None) -> Any:
    """FSDP param sharding: TP spec + data axis on large leaves (>= 1M
    elements).  Small leaves stay TP-only to avoid gather latency."""
    return zero_pspecs(param_specs, shapes, mesh, rules, min_size=1 << 20)


# ---------------------------------------------------------------------------
# Cache / activation input specs
# ---------------------------------------------------------------------------


def _batch_axis(mesh: Mesh, rules, batch: int):
    """(the mesh axes a cache's batch dim shards over or None, whether the
    batch divides over the batch axes)."""
    axes = tuple(a for a in rules["batch"] if a in mesh.axis_names)
    bsize = math.prod(mesh.shape[a] for a in axes) if axes else 1
    ok = batch % bsize == 0 and batch >= bsize
    return ((axes if len(axes) > 1 else axes[0]) if ok and axes else None), ok


def kv_pspec(mesh: Mesh, shape: Sequence[int], kv_heads: int,
             rules: Optional[Dict] = None) -> P:
    """Spec of a K/V cache stack (n_periods, B, T, HKV, D).  Preference
    order for the model axis: KV heads when divisible, else the sequence
    dim (the decode path reduces over T with plain all-reduces).  batch=1
    long-context shards T over data as well."""
    rules = dict(rules or DEFAULT_RULES)
    b_ax, batch_ok = _batch_axis(mesh, rules, shape[1])
    model_size = math.prod(
        mesh.shape[a] for a in rules["model"] if a in mesh.axis_names) or 1
    head_ax = "model" if kv_heads % model_size == 0 else None
    seq_parts = []
    if not batch_ok:
        seq_parts += list(a for a in rules["seq"] if a in mesh.axis_names)
    if head_ax is None:
        seq_parts += list(a for a in rules["act_seq"] if a in mesh.axis_names)
    seq_ax = None
    if seq_parts:
        prod = math.prod(mesh.shape[a] for a in seq_parts)
        if shape[2] % prod == 0:
            seq_ax = tuple(seq_parts) if len(seq_parts) > 1 else seq_parts[0]
        else:
            _record_fallback(
                f"cache seq {shape[2]} % {prod} != 0 -> replicated")
    return P(None, b_ax, seq_ax, _resolve(mesh, rules, head_ax, shape[3])
             if head_ax else None, None)


def cache_pspecs(cache_tree: Any, mesh: Mesh, batch: int,
                 kv_heads: int, rules: Optional[Dict] = None) -> Any:
    """Decode-cache specs.  Normal decode: batch over (pod, data), heads over
    model.  batch=1 long-context: sequence dim over data (flash-decode style;
    GSPMD inserts the partial-softmax combine collectives)."""
    rules = dict(rules or DEFAULT_RULES)
    b_ax, _ = _batch_axis(mesh, rules, batch)

    def one(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        nd = len(shape)
        if re.search(r"\.(k|v|ck|cv)$", ps) and nd == 5:
            return kv_pspec(mesh, shape, kv_heads, rules)
        if re.search(r"\.(h|conv)$", ps) and nd >= 3:
            # mamba: (n_periods, B, ..., d_inner[, N]) — d_inner over model
            inner_axis = 2 if ps.endswith(".h") else 3
            parts = [None] * nd
            parts[1] = b_ax
            parts[inner_axis] = _resolve(mesh, rules, "model", shape[inner_axis])
            return P(*parts)
        if re.search(r"\.s$", ps) and nd == 5:
            # rwkv state (n_periods, B, H, N, N) — heads over model
            return P(None, b_ax, _resolve(mesh, rules, "model", shape[2]),
                     None, None)
        parts = [None] * nd
        if nd >= 2:
            parts[1] = b_ax
        return P(*parts)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def keep_kv_layout(stack: jax.Array) -> jax.Array:
    """A K/V cache stack (n_periods, B, T, HKV, D) held to the layout JAX
    gives a jit argument of its shape, on the devices the step compiles
    for: each shard of the :func:`use_mesh` mesh (sharded by
    :func:`kv_pspec`), else JAX's default device.  A loop carry so held
    keeps the layout it enters the loop with; left free, XLA lays it out
    for its uses inside the loop and copies the whole stack on entry and
    exit.  A no-op where the device's client cannot say its default
    layout."""
    ctx = getattr(_TLS, "ctx", None)
    mesh = ctx[0] if ctx else None
    if mesh is None:
        dev = jax.config.jax_default_device or jax.devices()[0]
        shard = stack.shape
    else:
        spec = kv_pspec(mesh, stack.shape, stack.shape[3], ctx[1])
        dev = mesh.devices.flat[0]
        shard = NamedSharding(mesh, spec).shard_shape(stack.shape)
    try:
        layout = Layout.from_pjrt_layout(
            dev.client.get_default_layout(stack.dtype, shard, dev))
    except jax.errors.JaxRuntimeError as e:
        if str(e).startswith("UNIMPLEMENTED"):
            return stack
        raise

    def pin(x):
        return with_layout_constraint(x, layout)

    if mesh is None:
        return pin(stack)
    # inside a shard_map the partitioner leaves each shard where it is;
    # outside, it would gather the stack around the constraint
    return jax.shard_map(pin, mesh=mesh, in_specs=spec, out_specs=spec)(stack)


def batch_pspec(mesh: Mesh, batch: int, ndim: int,
                rules: Optional[Dict] = None) -> P:
    rules = dict(rules or DEFAULT_RULES)
    axes = tuple(a for a in rules["batch"] if a in mesh.axis_names)
    bsize = math.prod(mesh.shape[a] for a in axes) if axes else 1
    if not axes or batch % bsize != 0:
        return P(*([None] * ndim))
    return P(axes if len(axes) > 1 else axes[0], *([None] * (ndim - 1)))


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))
