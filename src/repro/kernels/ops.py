"""Public kernel entry points: jitted wrappers that consult the LoopTune
schedule registry for block shapes (the paper's auto-tuned schedules become
BlockSpecs here — `DESIGN §2`).

``set_registry(path_or_registry)`` installs a tuned-schedule table (produced
by ``examples/autotune_matmul.py`` or ``LoopTuner``); every wrapper falls
back to MXU-aligned defaults when no entry exists.  ``interpret`` defaults
to None, which ``runtime.device.resolve_interpret`` turns into a Mosaic
compile on a TPU and the Pallas interpreter everywhere else; pass a bool
to force either.

**Tuned serving** (`launch/serve --registry`): :func:`tuned_einsum` is the
model zoo's consume path.  Inside a :func:`serving` context every
matmul-shaped contraction looks its workload signature up in the active
:class:`ScheduleRegistry` at model-compile (trace) time; hits route through
the Pallas tiled kernel with the tuned BlockSpec on hardware where Mosaic
compiles (``pallas="auto"`` → real TPU), and fall back to the plain
``jnp.einsum`` XLA lowering on cold miss, non-matmul shapes, or CPU hosts
(where interpret-mode Pallas would be a de-optimization).  Per-contraction
hit/miss/routed counters are kept per trace — read them with
:func:`serving_stats`.
"""
from __future__ import annotations

import contextlib
import os
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.registry import ScheduleRegistry, current_hardware
from repro.runtime.device import on_tpu

from .flash_attention import flash_attention as _flash_attention
from .mamba_scan import mamba_scan as _mamba_scan
from .matmul import matmul as _matmul
from .matmul import stack_divides
from .rwkv6_scan import rwkv6_chunk_scan as _rwkv6_chunk_scan

_REGISTRY: Optional[ScheduleRegistry] = None

DEFAULT_MM_BLOCK: Dict[str, int] = {"m": 128, "k": 128, "n": 128}


def set_registry(reg: Union[str, ScheduleRegistry, None]) -> None:
    global _REGISTRY
    if isinstance(reg, str):
        reg = ScheduleRegistry(reg)
    _REGISTRY = reg


def get_registry() -> Optional[ScheduleRegistry]:
    return _REGISTRY


# --------------------------------------------------------------------------
# Tuned serving: trace-time registry context + per-contraction counters
# --------------------------------------------------------------------------

_SERVING: Optional[ScheduleRegistry] = None
_SERVING_STATS: Dict[str, Dict[str, int]] = {}


@contextlib.contextmanager
def serving(registry: Union[str, ScheduleRegistry, None]):
    """Activate a tuned-schedule registry for model tracing.

    The model zoo's matmul sites go through :func:`tuned_einsum`, which
    consults the *active* serving registry.  Because the lookup happens in
    the jitted function body, the context only needs to cover tracing —
    launchers wrap the step-function body so retraces see it too.  ``None``
    deactivates (the default path is untouched ``@``/``einsum``).
    """
    global _SERVING
    if isinstance(registry, str):
        registry = ScheduleRegistry(registry)
    prev = _SERVING
    _SERVING = registry
    try:
        yield registry
    finally:
        _SERVING = prev


def serving_registry() -> Optional[ScheduleRegistry]:
    return _SERVING


def serving_stats(reset: bool = False) -> Dict[str, Any]:
    """Per-contraction registry hit/miss/routed counters (trace-time).

    ``hits``  — workload found in the registry;
    ``misses`` — matmul-shaped contraction with no entry (cold miss);
    ``routed`` — hits actually lowered through the Pallas tiled kernel
    (subset of hits: CPU hosts count the hit but keep the XLA lowering);
    ``stacked`` — routed calls whose kernel read its layer's weight
    straight out of the stacked parameters (subset of routed).
    """
    per_key = {k: dict(v) for k, v in _SERVING_STATS.items()}
    out = {
        "hits": sum(v.get("hits", 0) for v in per_key.values()),
        "misses": sum(v.get("misses", 0) for v in per_key.values()),
        "routed": sum(v.get("routed", 0) for v in per_key.values()),
        "stacked": sum(v.get("stacked", 0) for v in per_key.values()),
        "per_key": per_key,
    }
    if reset:
        reset_serving_stats()
    return out


def reset_serving_stats() -> None:
    _SERVING_STATS.clear()


def _count(key: str, field: str) -> None:
    slot = _SERVING_STATS.setdefault(key, {"hits": 0, "misses": 0,
                                           "routed": 0, "stacked": 0})
    slot[field] += 1


def _parse_matmul_spec(spec: str, a_shape, b_shape):
    """Match an einsum spec to a (batched-)matmul; None if not one.

    Accepts two-operand specs where the rhs is 2-D, exactly one index is
    contracted, the contracted index is the trailing lhs dim, and the
    output is ``lhs_free + rhs_free`` — i.e. ``...k,kn->...n`` and the
    transposed-weight form ``...k,nk->...n`` (logits against an embedding
    table).  An lhs/out ellipsis stands for the leading (batch) dims of
    ``a`` and folds into ``m`` exactly like explicit letters, so
    ``"...k,kn->...n"`` and ``"abk,kn->abn"`` on the same shapes resolve to
    the same ``(m, k, n)`` workload key.  Returns
    ``(m, k, n, transpose_rhs)`` with leading lhs dims folded into m,
    matching how ``launch/tune`` harvests workload keys.
    """
    if "->" not in spec:
        return None
    ins, out = spec.split("->")
    if ins.count(",") != 1:
        return None
    lhs, rhs = ins.split(",")
    ellipsis = lhs.startswith("...") and out.startswith("...")
    if ellipsis:
        lhs, out = lhs[3:], out[3:]
    # after stripping a matched lhs/out prefix, any remaining "..." (rhs
    # ellipsis, mid-spec, or one side only) is a shape we don't tune
    if "..." in lhs or "..." in rhs or "..." in out:
        return None
    if ellipsis:
        # the ellipsis absorbs len(a_shape) - len(lhs) leading batch dims;
        # the explicit letters must still cover at least the contracted dim
        if not lhs or len(lhs) > len(a_shape):
            return None
    elif len(lhs) != len(a_shape):
        return None
    if len(rhs) != 2 or len(rhs) != len(b_shape):
        return None
    if len(set(lhs)) != len(lhs) or len(set(rhs)) != len(rhs):
        return None
    contracted = (set(lhs) & set(rhs)) - set(out)
    if len(contracted) != 1:
        return None
    ck = contracted.pop()
    if lhs[-1] != ck:
        return None
    free_l = lhs[:-1]
    free_r = rhs.replace(ck, "")
    if out != free_l + free_r:
        return None
    m = 1
    for d in a_shape[:-1]:
        m *= int(d)
    k = int(a_shape[-1])
    n = int(b_shape[1] if rhs[0] == ck else b_shape[0])
    return m, k, n, rhs[0] != ck


def _route_pallas(pallas: str) -> Tuple[bool, bool]:
    """(route through Pallas?, interpret mode?) for a registry hit.

    ``"auto"`` routes only where Mosaic compiles (real TPU) — on CPU the
    interpret-mode kernel is a de-optimization, so hits keep the XLA
    lowering (still counted, proving the lookup path).  ``"interpret"``
    forces the interpreted kernel (tests), ``"on"`` the compiled one,
    ``"off"`` never routes.
    """
    if pallas == "off":
        return False, True
    if pallas == "interpret":
        return True, True
    if pallas == "on":
        return True, False
    return on_tpu(), False


def tuned_einsum(spec: str, a: jax.Array, b: jax.Array, *,
                 layer: Optional[jax.Array] = None,
                 registry: Optional[ScheduleRegistry] = None,
                 pallas: str = "auto",
                 preferred_element_type=None) -> jax.Array:
    """Registry-backed einsum: the model zoo's tuned-serving entry point.

    Looks the contraction's workload signature up in ``registry`` (default:
    the active :func:`serving` registry) at trace time.  On a hit with a
    tuned block, matmul-shaped contractions route through the Pallas tiled
    kernel with the tuned BlockSpec; cold misses, non-matmul shapes, and
    hosts where Mosaic can't compile fall back to ``jnp.einsum`` — always
    numerically interchangeable with the fallback.

    With ``layer`` (a traced int32), ``b`` is a stack of per-layer weights
    and the contraction takes ``b[layer]``.  A routed ``...k,kn`` hit
    whose block divides the weight gives the kernel the whole stack and
    the index (counted ``stacked``), so the layer's weight is not copied
    out first; every other path slices the layer and goes on as above.
    """
    reg = registry if registry is not None else _SERVING

    def weight():
        return b if layer is None else \
            jax.lax.dynamic_index_in_dim(b, layer, keepdims=False)

    def _fallback():
        return jnp.einsum(spec, a, weight(),
                          preferred_element_type=preferred_element_type)

    if reg is None:
        return _fallback()
    parsed = _parse_matmul_spec(spec, a.shape,
                                b.shape if layer is None else b.shape[1:])
    if parsed is None:
        return _fallback()
    m, k, n, transpose_rhs = parsed
    dtype = str(a.dtype)
    wl_key = ScheduleRegistry.key("mm", (m, k, n), dtype)
    entry = reg.get("mm", (m, k, n), dtype=dtype,
                    hardware=current_hardware())
    if not entry or "block" not in entry:
        _count(wl_key, "misses")
        return _fallback()
    _count(wl_key, "hits")
    route, interpret = _route_pallas(pallas)
    if not route:
        return _fallback()
    _count(wl_key, "routed")
    block = dict(DEFAULT_MM_BLOCK)
    block.update({kk: int(vv) for kk, vv in entry["block"].items()})
    go = [it for it in entry.get("grid_order", []) if it in ("m", "n")]
    order = "nm" if go and go[0] == "n" else "mn"
    a2 = a.reshape(m, k)
    out_dtype = preferred_element_type if preferred_element_type is not None \
        else a.dtype
    kw = dict(bm=block["m"], bk=block["k"], bn=block["n"], grid_order=order,
              interpret=interpret, out_dtype=out_dtype)
    if (layer is not None and not transpose_rhs
            and stack_divides(k, n, block["k"], block["n"])):
        _count(wl_key, "stacked")
        out = _matmul(a2, b, layer=layer, **kw)
    else:
        b2 = weight()
        out = _matmul(a2, b2.T if transpose_rhs else b2, **kw)
    return out.reshape(*a.shape[:-1], n)


def _mm_schedule(m: int, k: int, n: int):
    """(block sizes, grid order) for an (m, k, n) matmul from the registry."""
    block = dict(DEFAULT_MM_BLOCK)
    order = "mn"
    if _REGISTRY is not None:
        entry = _REGISTRY.get("mm", (m, k, n))
        if entry and "block" in entry:
            block.update({kk: int(vv) for kk, vv in entry["block"].items()})
            go = [it for it in entry.get("grid_order", []) if it in ("m", "n")]
            if go and go[0] == "n":
                order = "nm"
    return block, order


def tuned_matmul(a: jax.Array, b: jax.Array, *,
                 interpret: Optional[bool] = None,
                 out_dtype=None) -> jax.Array:
    """Registry-tuned tiled matmul (falls back to 128^3 MXU blocks)."""
    m, k = a.shape
    n = b.shape[1]
    block, order = _mm_schedule(m, k, n)
    return _matmul(a, b, bm=block["m"], bk=block["k"], bn=block["n"],
                   grid_order=order, interpret=interpret, out_dtype=out_dtype)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    interpret: Optional[bool] = None):
    """Registry-tuned flash attention (block sizes under kernel id 'fa')."""
    bq, bk = 128, 128
    if _REGISTRY is not None:
        entry = _REGISTRY.get("fa", (q.shape[1], k.shape[1], q.shape[-1]))
        if entry and "block" in entry:
            bq = int(entry["block"].get("q", bq))
            bk = int(entry["block"].get("k", bk))
    return _flash_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, bq=bq, bk=bk,
                            interpret=interpret)


def rwkv6_chunk_scan(r, k, v, logw, u, *, chunk: int = 64,
                     interpret: Optional[bool] = None):
    if _REGISTRY is not None:
        entry = _REGISTRY.get("rwkv6", (r.shape[1], r.shape[2]))
        if entry and "block" in entry:
            chunk = int(entry["block"].get("l", chunk))
    return _rwkv6_chunk_scan(r, k, v, logw, u, chunk=chunk,
                             interpret=interpret)


def mamba_scan(dtx, da, b, c, *, chunk: int = 32, bd: int = 128,
               interpret: Optional[bool] = None):
    if _REGISTRY is not None:
        entry = _REGISTRY.get("mamba", (dtx.shape[1], dtx.shape[2]))
        if entry and "block" in entry:
            chunk = int(entry["block"].get("l", chunk))
            bd = int(entry["block"].get("c", bd))
    return _mamba_scan(dtx, da, b, c, chunk=chunk, bd=bd, interpret=interpret)
