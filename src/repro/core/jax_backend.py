"""Compiled JAX executor — structure-cached JIT lowering of LoopNest schedules.

The measured reward path used to be interpreter-bound: ``cpu_backend.execute``
walks the blocked iteration space in Python, issuing one tiny ``np.einsum``
per slab — thousands of interpreter round-trips per measurement.  This module
lowers a :class:`LoopNest` to a *single jitted callable* instead:

1. The python-side loop levels are enumerated **once** into a static slab
   plan (offset/extent per slab) — by driving the exact same
   ``cpu_backend._run_section`` recursion the NumPy executor uses, so the
   plan is identical by construction.
2. Slabs are grouped by extent shape (tails form their own groups; JAX
   slices need static sizes).  Small groups unroll straight into the trace;
   large ones roll into a ``lax.fori_loop`` over the stacked slab offsets.
   Either way each slab's body is one fused ``jnp.einsum`` over its operand
   slices plus an in-place f32 accumulator window update, and the
   write-back section replays the same way (accumulator -> output in
   scheduled traversal order) — the compiled program performs the same
   traversal work as the interpreter, minus the interpreter.
3. Nests whose contraction matches a registered kernel shape route through
   the real Pallas kernel instead (``kernels/matmul.py``, block shape and
   grid order lowered from the schedule via
   :func:`~repro.core.registry.schedule_to_blockspec`; Mosaic-compiled on
   a TPU, interpret mode elsewhere).  See :func:`register_kernel_route`.

Executables are cached by ``structure_key`` in :class:`CompiledKernelCache`
(LRU — the same eviction discipline as :class:`ScheduleCache`), so
``evaluate_batch`` compiles each distinct structure once and every later
measurement only re-times.  Semantics parity with the NumPy executor
(`execute` == reference einsum for every reachable schedule) is
property-tested in ``tests/test_jax_backend.py``.

Compilation is additionally **persistent**, **fleet-deduped** and
**overlapped** when a cache dir is configured (``cache_dir=`` /
``LOOPTUNE_KERNEL_CACHE``):

* executables are serialized through ``jax.export`` into a
  :class:`~repro.core.kernel_store.PersistentKernelStore` keyed by
  ``(structure_key, vec_cap, route)`` under a JAX/device fingerprint, so a
  warm tuner run — and every :class:`~repro.core.measure.WorkerPool`
  worker — *loads* each key instead of re-tracing it;
* cold keys are built by exactly one process fleet-wide (file-locked);
  peers wait for the shared artifact rather than compiling redundantly;
* :meth:`prepare_batch` hands upcoming structures to a background compile
  thread, so compilation overlaps the current batch's measurement
  (AutoTVM's pipelined builder/runner split) instead of preceding it, and
  the worker pool dispatches already-compiled schedules first.
"""
from __future__ import annotations

import os
import queue as queue_mod
import threading
import time
from typing import (Any, Callable, Dict, Hashable, List, Optional, Sequence,
                    Tuple)

import numpy as np

from .cpu_backend import (INPUTS_CACHE_CAPACITY, VEC_CAP_DEFAULT,
                          _einsum_expr, _run_section, make_inputs)
from .kernel_store import PersistentKernelStore, open_store
from .loop_ir import Contraction, LoopNest
from .measure import MeasuredBackend, MeasurementPolicy
from .schedule_cache import LRUCache
from ..runtime.device import on_tpu
from ..runtime.spans import count, span, timed

#: environment fallback for the persistent kernel cache dir, so entry points
#: that never grew a ``cache_dir`` flag still share the fleet cache
CACHE_DIR_ENV = "LOOPTUNE_KERNEL_CACHE"

# compiled executables are heavyweight (traced + lowered programs); keep a
# bounded working set rather than ScheduleCache's 200k float entries
COMPILED_CACHE_CAPACITY = 1024


# ---------------------------------------------------------------------------
# Static slab plan
# ---------------------------------------------------------------------------


def _slab_plan(
    levels, c: Contraction, vec_cap: int
) -> List[Tuple[Dict[str, int], Dict[str, int]]]:
    """All ``(offsets, extents)`` slabs the blocked interpreter would visit,
    in traversal order — computed once per structure."""
    plan: List[Tuple[Dict[str, int], Dict[str, int]]] = []
    _run_section(levels, c,
                 lambda off, ext: plan.append((dict(off), dict(ext))),
                 vec_cap)
    return plan


def _group_slabs(
    plan: Sequence[Tuple[Dict[str, int], Dict[str, int]]],
    iters: Sequence[str],
) -> List[Tuple[Dict[str, int], List[Dict[str, int]]]]:
    """Group slabs by extent shape (insertion-ordered).  Returns
    ``[(extents, [offsets, ...]), ...]`` — every slab in a group shares its
    static shape, so the whole group runs as one batched op."""
    groups: Dict[Tuple[int, ...], List[Dict[str, int]]] = {}
    exts: Dict[Tuple[int, ...], Dict[str, int]] = {}
    for off, ext in plan:
        key = tuple(ext[it] for it in iters)
        groups.setdefault(key, []).append(off)
        exts[key] = ext
    return [(exts[k], offs) for k, offs in groups.items()]


def _tensor_slabs(offs: Sequence[Dict[str, int]], ext: Dict[str, int],
                  iterators: Sequence[str]) -> Tuple[np.ndarray, Tuple[int, ...]]:
    """Per-tensor slab addressing: ``(starts (K, d) int32, sizes (d,))``."""
    starts = np.array([[off[it] for it in iterators] for off in offs],
                      dtype=np.int32).reshape(len(offs), len(iterators))
    return starts, tuple(ext[it] for it in iterators)


# ---------------------------------------------------------------------------
# Lowering: LoopNest -> jitted callable
# ---------------------------------------------------------------------------


# groups at or below this slab count are unrolled straight into the trace
# (XLA fuses the static slices); larger groups roll into a fori_loop whose
# dynamic_update_slice accumulator XLA keeps in place
UNROLL_MAX = 64


def _build_slab_fn(nest: LoopNest, vec_cap: int,
                   unroll_max: int = UNROLL_MAX) -> Callable:
    """Lower the schedule's compute + write-back sections to one function
    ``fn(*operands) -> out`` of pure JAX ops (jit it to compile).

    Each slab group becomes either statically-unrolled slices (small groups)
    or a ``lax.fori_loop`` over the stacked slab offsets; every slab's body
    is one fused ``jnp.einsum`` over its operand slices plus an in-place
    accumulator window update — the compiled replacement for the
    interpreter's per-slab ``np.einsum`` round-trips.
    """
    import jax.numpy as jnp
    from jax import lax

    c = nest.contraction
    iters = list(c.iter_sizes)
    expr = _einsum_expr(c)

    compute_groups = []
    for ext, offs in _group_slabs(
            _slab_plan(nest.compute_loops, c, vec_cap), iters):
        in_slabs = [_tensor_slabs(offs, ext, t.iterators) for t in c.inputs()]
        out_slabs = _tensor_slabs(offs, ext, c.out.iterators)
        compute_groups.append((in_slabs, out_slabs, len(offs)))

    wb_groups = [
        (_tensor_slabs(offs, ext, c.out.iterators), len(offs))
        for ext, offs in _group_slabs(
            _slab_plan(nest.writeback_loops, c, vec_cap), iters)
    ]

    def fn(*operands):
        acc = jnp.zeros(c.out.dims, jnp.float32)
        for in_slabs, (out_starts, out_sizes), k in compute_groups:
            in_starts = [jnp.asarray(s) for s, _ in in_slabs]
            out_starts_j = jnp.asarray(out_starts)

            def body(i, acc, in_starts=in_starts, in_slabs=in_slabs,
                     out_starts=out_starts_j, out_sizes=out_sizes):
                slabs = [
                    lax.dynamic_slice(op, tuple(st[i]), sizes)
                    for op, st, (_, sizes) in zip(operands, in_starts, in_slabs)
                ]
                part = jnp.einsum(expr, *slabs)
                cur = lax.dynamic_slice(acc, tuple(out_starts[i]), out_sizes)
                return lax.dynamic_update_slice(acc, cur + part,
                                                tuple(out_starts[i]))

            if k <= unroll_max:
                for i in range(k):
                    acc = body(i, acc)
            else:
                acc = lax.fori_loop(0, k, body, acc)

        # write-back nest: copy the accumulator into the output buffer in
        # the scheduled traversal order (slabs partition the output exactly)
        out = jnp.zeros(c.out.dims, jnp.float32)
        for (wb_starts, wb_sizes), k in wb_groups:
            wb_starts_j = jnp.asarray(wb_starts)

            def wb_body(i, out, starts=wb_starts_j, sizes=wb_sizes):
                slab = lax.dynamic_slice(acc, tuple(starts[i]), sizes)
                return lax.dynamic_update_slice(out, slab, tuple(starts[i]))

            if k <= unroll_max:
                for i in range(k):
                    out = wb_body(i, out)
            else:
                out = lax.fori_loop(0, k, wb_body, out)
        return out

    return fn


# ---------------------------------------------------------------------------
# Kernel-shape routes (Pallas fast path)
# ---------------------------------------------------------------------------

_KERNEL_ROUTES: Dict[str, Tuple[Callable[[Contraction], bool],
                                Callable[[LoopNest, bool], Callable]]] = {}


def register_kernel_route(name: str,
                          match: Callable[[Contraction], bool],
                          lower: Callable[[LoopNest, bool], Callable]) -> None:
    """Register a hand-written kernel route: nests whose contraction
    satisfies ``match`` lower through ``lower(nest, interpret) -> fn`` (the
    returned ``fn(*operands)`` must be jit-compatible) instead of the
    generic slab path."""
    _KERNEL_ROUTES[name] = (match, lower)


def match_kernel_route(c: Contraction) -> Optional[str]:
    for name, (match, _) in _KERNEL_ROUTES.items():
        if match(c):
            return name
    return None


def _lower_matmul(nest: LoopNest, interpret: bool) -> Callable:
    """Schedule -> Pallas tiled matmul: the VMEM-resident suffix becomes the
    BlockSpec block shape and the outer levels the grid order (exactly how
    tuned schedules ship to the kernel layer via the registry)."""
    import jax.numpy as jnp

    from ..kernels.matmul import matmul
    from .registry import schedule_to_blockspec

    m_it, k_it, n_it = nest.contraction.matmul_iters()
    block, grid_order = schedule_to_blockspec(nest)
    order = "nm" if grid_order.index(n_it) < grid_order.index(m_it) else "mn"

    def fn(a, b):
        return matmul(a, b, bm=int(block[m_it]), bk=int(block[k_it]),
                      bn=int(block[n_it]), grid_order=order,
                      interpret=interpret, out_dtype=jnp.float32)

    return fn


register_kernel_route("matmul", lambda c: c.matmul_iters() is not None,
                      _lower_matmul)


# ---------------------------------------------------------------------------
# Compiled-executable cache
# ---------------------------------------------------------------------------


class CompiledKernelCache(LRUCache):
    """LRU map from ``(structure_key, vec_cap, route)`` to a jitted
    executable — shares the eviction discipline of :class:`ScheduleCache`
    (bounded, evict-coldest, never clear-all).  ``misses`` counts in-memory
    lookups that had to build *or load*: repeated ``evaluate_batch`` calls
    over the same structures trace once.  With a
    :class:`~repro.core.kernel_store.PersistentKernelStore` layered under
    it (see ``JaxJitBackend``), an evicted entry re-enters by
    deserialization, not re-tracing.

    ``evict_cb`` (optional) fires per evicted key — the backend uses it to
    drop warm-state bookkeeping that must never outlive the executable."""

    def __init__(self, capacity: int = COMPILED_CACHE_CAPACITY,
                 evict_cb: Optional[Callable[[Hashable], None]] = None):
        super().__init__(capacity)
        self.evict_cb = evict_cb

    def on_evict(self, key, value) -> None:
        if self.evict_cb is not None:
            self.evict_cb(key)


# ---------------------------------------------------------------------------
# Reference-parity execution surface (used by the property tests)
# ---------------------------------------------------------------------------


def execute_jax(
    nest: LoopNest,
    arrays: Dict[str, np.ndarray],
    vec_cap: int = VEC_CAP_DEFAULT,
    route: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> np.ndarray:
    """Execute the schedule through a freshly-built jitted callable; returns
    the output tensor as NumPy.  ``route`` forces a registered kernel route
    (e.g. ``"matmul"`` for the Pallas path); None uses the generic slab
    lowering."""
    import jax

    c = nest.contraction
    if route is not None:
        if not _KERNEL_ROUTES[route][0](c):
            raise ValueError(f"nest {c.name!r} does not match route {route!r}")
        fn = _KERNEL_ROUTES[route][1](nest, interpret)
    else:
        fn = jax.jit(_build_slab_fn(nest, vec_cap))
    ops = [np.asarray(arrays[t.name], np.float32) for t in c.inputs()]
    return np.asarray(fn(*ops))


# ---------------------------------------------------------------------------
# Timing backend
# ---------------------------------------------------------------------------


# peak GFLOPS of the XLA target is constant within a process: memoized per
# (device kind, process) so backend construction never re-times it
_PEAK_CACHE: Dict[str, float] = {}


class JaxJitBackend(MeasuredBackend):
    """Measured-GFLOPS reward backend over compiled executables — a *pure
    executor*.

    Execution lives here (:meth:`run_once` runs the cached jitted program,
    synchronized); warm-up, best-of-``repeats`` selection, variance
    guardrails and optional out-of-process pooling live in
    :class:`~repro.core.measure.MeasuredBackend` — the untimed warm-up run
    triggers (cached) compilation, every later evaluation of the same
    structure only re-times.

    ``pallas`` controls the kernel-route fast path: ``"auto"`` routes
    matching nests through Pallas only when compiled execution is available
    (i.e. on real TPU — interpret-mode timings are not meaningful),
    ``"on"`` forces it (interpret mode on CPU: correct results, trustworthy
    only for correctness), ``"off"`` always uses the generic slab lowering.

    ``cache_dir`` (default: the ``LOOPTUNE_KERNEL_CACHE`` env var) enables
    the persistent fleet-wide compile cache; ``prepare`` controls the
    compile-ahead hook (``"thread"`` = background compile thread hides
    compile latency behind measurement, ``"sync"`` = compile inline at
    ``prepare_batch`` time, ``"off"`` = hook is a no-op).
    """

    name = "jax"

    def __init__(
        self,
        vec_cap: int = VEC_CAP_DEFAULT,
        repeats: Optional[int] = None,
        seed: int = 0,
        pallas: str = "auto",
        kernel_cache: Optional[CompiledKernelCache] = None,
        policy: Optional[MeasurementPolicy] = None,
        measure: str = "inproc",
        pool_workers: Optional[int] = None,
        isolated: bool = False,
        cache_dir: Optional[str] = None,
        prepare: str = "thread",
        pool_timeout_s: Optional[float] = None,
    ):
        import jax  # noqa: F401 — ImportError here drives make_backend("auto") fallback

        if pallas not in ("auto", "on", "off"):
            raise ValueError(f"pallas must be auto|on|off, got {pallas!r}")
        if prepare not in ("thread", "sync", "off"):
            raise ValueError(f"prepare must be thread|sync|off, got {prepare!r}")
        if measure == "pool" and on_tpu():
            raise ValueError(
                "measure='pool' starts worker processes that each need the "
                "TPU, but a chip belongs to one process (this one, which "
                "already holds it); measure in-process with "
                "measure='inproc'")
        super().__init__(policy=policy, repeats=repeats, measure=measure,
                         pool_workers=pool_workers, isolated=isolated,
                         pool_timeout_s=pool_timeout_s)
        self.vec_cap = vec_cap
        self.seed = seed
        self.pallas = pallas
        self.prepare = prepare
        self.can_prepare = prepare != "off"
        self.interpret = not on_tpu()
        self.kernels = (kernel_cache if kernel_cache is not None
                        else CompiledKernelCache())
        # warm-state bookkeeping must never outlive the executable it
        # describes: a re-entered (rebuilt or re-loaded) program pays XLA
        # compilation again on its first call
        if self.kernels.evict_cb is None:
            self.kernels.evict_cb = self._on_kernel_evict
        self._inputs_cache = LRUCache(INPUTS_CACHE_CAPACITY)
        # persistent fleet cache (None = in-process JIT only)
        self.cache_dir = (cache_dir if cache_dir is not None
                          else os.environ.get(CACHE_DIR_ENV) or None)
        self.store: Optional[PersistentKernelStore] = open_store(
            self.cache_dir, self._fingerprint())
        # compile accounting — the "never wait on the compiler twice" ledger
        self.compiles = 0         # actual traces performed by this process
        self.compile_s = 0.0      # seconds spent tracing/exporting
        self.backend_compile_s = 0.0  # first calls: XLA's lazy compile
        self.persist_loads = 0    # executables deserialized, not traced
        self.persist_load_s = 0.0
        self.export_errors = 0    # unexportable builds (kept in-proc only)
        self.last_export_error: Optional[str] = None
        self.deser_errors = 0     # artifacts that failed to deserialize
        self.prepare_errors = 0   # background compile-ahead failures
        self.prepared = 0         # keys handed to the compile-ahead path
        # in-process compile dedup: one trace per key no matter how many
        # threads (measurement + compile-ahead) race on it
        self._compile_cv = threading.Condition()
        self._building: set = set()
        self._queued: set = set()
        # keys whose executable has actually run at least once in this
        # process (a loaded-but-never-called program still owes its XLA
        # compile; is_warm must not elide the warmup that would pay it)
        self._executed: set = set()
        self._compile_thread: Optional[threading.Thread] = None
        self._compile_q: Optional[queue_mod.Queue] = None

    def _fingerprint(self) -> Dict[str, Any]:
        import jax

        try:
            device = jax.devices()[0].device_kind
        except Exception:  # noqa: BLE001 — device query is observability only
            device = "unknown"
        return {"jax": jax.__version__, "platform": jax.default_backend(),
                "device": device, "interpret": self.interpret}

    def _on_kernel_evict(self, key: Hashable) -> None:
        self._executed.discard(key)

    # -- compilation ----------------------------------------------------------

    def _route(self, c: Contraction) -> Optional[str]:
        if self.pallas == "off":
            return None
        if self.pallas == "auto" and self.interpret:
            return None
        return match_kernel_route(c)

    def _compile_key(self, nest: LoopNest) -> Tuple:
        """THE compile key — every cache layer (in-memory LRU, persistent
        store, warm-state tracking, pool dispatch hints) must key off this
        one helper so they can never drift apart."""
        return (nest.structure_key(), self.vec_cap,
                self._route(nest.contraction))

    def _abstract_args(self, c: Contraction) -> List[Any]:
        import jax
        import jax.numpy as jnp

        return [jax.ShapeDtypeStruct(t.dims, jnp.float32) for t in c.inputs()]

    def _trace(self, nest: LoopNest, key: Tuple
               ) -> Tuple[Callable, Optional[bytes]]:
        """Build the executable the expensive way (trace + lower).  The
        program is traced through ``jax.export`` — with a store attached
        the serialized artifact ships fleet-wide; unexportable programs
        degrade to plain in-process JIT (counted, never fatal).  XLA's
        backend compile of the staged module stays lazy: it costs the same
        whether the module was traced here or loaded from the store and is
        left out of ``compile_s`` on both paths; the executable's first call
        pays it, under the ``looptune.compile.backend`` span
        (``backend_compile_s``)."""
        import jax

        route = key[2]
        with timed("looptune.compile.trace") as sp:
            if route is not None:
                fn = _KERNEL_ROUTES[route][1](nest, self.interpret)
            else:
                fn = _build_slab_fn(nest, self.vec_cap)
            data: Optional[bytes] = None
            try:
                from jax import export

                exp = export.export(jax.jit(fn))(
                    *self._abstract_args(nest.contraction))
                if self.store is not None:
                    data = exp.serialize()
                # run through the exported program in-process too — fleet
                # members time the exact same XLA module they load, and the
                # storeless path stages through export as well so the
                # expensive Python trace lands under the compile timer (not
                # inside the first warmup run) and ``compile_s`` means the
                # same thing in every mode
                fn = exp.call
            except Exception as e:  # noqa: BLE001 — export is best-effort
                self.export_errors += 1
                self.last_export_error = f"{type(e).__name__}: {e}"
                data = None
            jitted = jax.jit(fn)
        self.compiles += 1
        self.compile_s += sp.seconds
        if self.store is not None:
            self.store.log_compile(key, sp.seconds)
        return jitted, data

    def _deserialize(self, key: Tuple, data: bytes) -> Optional[Callable]:
        """A stored artifact turned back into an executable, or None when
        it fails to deserialize (the artifact is dropped, so the next
        builder replaces it)."""
        import jax
        from jax import export

        with timed("looptune.compile.load") as sp:
            try:
                fn = jax.jit(export.deserialize(data).call)
            except Exception:  # noqa: BLE001 — fall back to in-process JIT
                fn = None
        if fn is None:
            self.deser_errors += 1
            self.store.discard(key)
            return None
        self.persist_loads += 1
        self.persist_load_s += sp.seconds
        return fn

    def _load_from_store(self, key: Tuple) -> Optional[Callable]:
        """A shared artifact turned back into an executable, or None
        (missing, corrupt, or version-mismatched — mismatches drop the
        artifact so the next builder replaces it)."""
        if self.store is None:
            return None
        data = self.store.load(key)
        if data is None:
            return None
        fn = self._deserialize(key, data)
        if fn is None:
            from .kernel_store import _warn_once

            _warn_once(self.store.root, "artifact failed to deserialize",
                       "jax/device mismatch or truncated file")
        return fn

    def _make_executable(self, nest: LoopNest, key: Tuple) -> Callable:
        """Store-coordinated build: load the shared artifact if it exists;
        otherwise exactly one process fleet-wide traces (file lock) while
        peers wait for the artifact.  Every failure path lands on a plain
        in-process JIT — a measurement is never failed by the cache."""
        fn = self._load_from_store(key)
        if fn is not None:
            return fn
        if self.store is None or self.store.acquire_build_lock(key):
            try:
                fn, data = self._trace(nest, key)
                if data is not None and self.store is not None:
                    self.store.store(key, data)
            finally:
                if self.store is not None:
                    self.store.release_build_lock(key)
            return fn
        # a peer is already tracing this key: wait on the shared artifact
        with span("looptune.compile.wait"):
            data = self.store.wait_for(key)
        if data is not None:
            loaded = self._deserialize(key, data)
            if loaded is not None:
                return loaded
        fn, _ = self._trace(nest, key)  # builder died/timed out: build here
        return fn

    def executable(self, nest: LoopNest) -> Callable:
        """The jitted callable for this structure.  Thread-safe and deduped
        at every layer: per-process (memory LRU + in-flight set, so the
        measurement thread and the compile-ahead thread never trace the
        same key twice) and fleet-wide (persistent store + build lock, so
        pool workers and sibling tuner runs share one trace per key)."""
        key = self._compile_key(nest)
        with self._compile_cv:
            while True:
                fn = self.kernels.get(key)
                if fn is not None:
                    self.kernels.hits += 1
                    return fn
                if key in self._building:
                    with span("looptune.compile.wait"):
                        while key in self._building:
                            self._compile_cv.wait()
                    continue
                self.kernels.misses += 1
                self._building.add(key)
                break
        ok = False
        try:
            fn = self._make_executable(nest, key)
            ok = True
        finally:
            with self._compile_cv:
                if ok:
                    self.kernels.put(key, fn)
                self._building.discard(key)
                self._compile_cv.notify_all()
        return fn

    def is_compiled(self, nest: LoopNest) -> bool:
        """Whether measuring this structure would wait on the compiler —
        False only for keys that are neither in memory nor in the shared
        store.  The worker pool dispatches compiled schedules first so cold
        keys compile in the background while warm ones measure."""
        key = self._compile_key(nest)
        return (key in self.kernels
                or (self.store is not None and self.store.contains(key)))

    # -- compile-ahead (the AutoTVM builder/runner overlap) -------------------

    def _ensure_compile_thread(self) -> queue_mod.Queue:
        if self._compile_q is None:
            self._compile_q = queue_mod.Queue()
            # daemon on purpose: an in-flight background compile must never
            # hold the interpreter open after the tuner is done with it
            self._compile_thread = threading.Thread(
                target=self._compile_worker, name="looptune-compile-ahead",
                daemon=True)
            self._compile_thread.start()
        return self._compile_q

    def _compile_worker(self) -> None:
        while True:
            item = self._compile_q.get()
            if item is None:
                return
            key, nest = item
            with self._compile_cv:
                self._queued.discard(key)
            try:
                self.executable(nest)
            except Exception:  # noqa: BLE001 — ahead-of-time is best-effort;
                # the measurement path will surface the real error
                self.prepare_errors += 1

    def prepare_batch(self, nests: Sequence[LoopNest]) -> int:
        """Compile-ahead hook: queue the *next* frontier's cold structures
        so tracing overlaps the current batch's measurement instead of
        stalling it.  Returns the number of keys scheduled.  Duplicate and
        already-compiled keys are skipped; with a worker pool the parent
        compiles into the shared store while workers measure."""
        if self.prepare == "off" or not nests:
            return 0
        todo: List[Tuple[Tuple, LoopNest]] = []
        with self._compile_cv:
            for nest in nests:
                key = self._compile_key(nest)
                if (key in self.kernels or key in self._building
                        or key in self._queued):
                    continue
                self._queued.add(key)
                # clone: callers mutate nests in place between frontiers
                todo.append((key, nest.clone()))
        if not todo:
            return 0
        self.prepared += len(todo)
        if self.prepare == "sync":
            for key, nest in todo:
                with self._compile_cv:
                    self._queued.discard(key)
                try:
                    self.executable(nest)
                except Exception:  # noqa: BLE001
                    self.prepare_errors += 1
            return len(todo)
        q = self._ensure_compile_thread()
        for item in todo:
            q.put(item)
        return len(todo)

    def close(self) -> None:
        """Shut down the compile-ahead thread and the worker pool."""
        if self._compile_q is not None:
            self._compile_q.put(None)
            if self._compile_thread is not None:
                # the builds still queued finish first: a wait on them
                with span("looptune.compile.wait"):
                    self._compile_thread.join(timeout=5.0)
            self._compile_q = None
            self._compile_thread = None
        super().close()

    def _inputs(self, c: Contraction) -> Tuple:
        def build():
            import jax
            import jax.numpy as jnp

            with span("looptune.inputs"):
                arrays = make_inputs(c, self.seed)
                out = jax.block_until_ready(tuple(
                    jnp.asarray(arrays[t.name]) for t in c.inputs()))
            count("looptune.inputs.bytes",
                  sum(a.nbytes for a in arrays.values()))
            return out

        return self._inputs_cache.get_or_create(c.name, build)

    def _call(self, nest: LoopNest):
        """The (cached) executable on the backend's operand set.  Its first
        call in this process pays XLA's lazy backend compile (Mosaic's, for
        a kernel route), whether the program was traced here or loaded
        from the store."""
        fn = self.executable(nest)
        args = self._inputs(nest.contraction)
        key = self._compile_key(nest)
        if key in self._executed:
            return fn(*args)
        import jax

        with timed("looptune.compile.backend") as sp:
            out = jax.block_until_ready(fn(*args))
        self.backend_compile_s += sp.seconds
        self._executed.add(key)
        return out

    def execute(self, nest: LoopNest) -> np.ndarray:
        """Run the (cached) executable on the backend's operand set."""
        return np.asarray(self._call(nest))

    # -- executor surface (timing lives in MeasuredBackend) ------------------

    def run_once(self, nest: LoopNest) -> None:
        """One synchronized run of the compiled program (the untimed policy
        warm-up run pays any compilation — tracing *and* the lazy XLA
        compile a store-loaded program still owes at its first call)."""
        self._call(nest).block_until_ready()

    def is_warm(self, nest: LoopNest) -> bool:
        """Warm-up is elidable only once *this structure's* executable has
        actually run here — being cached (or prepared, or loaded from the
        persistent store) is not enough, because XLA compiles lazily at the
        first call and that cost must stay out of the timed runs."""
        return (super().is_warm(nest)
                and self._compile_key(nest) in self._executed)

    def pool_spec(self) -> Tuple[str, Dict[str, Any], Optional[str]]:
        # spawn, not fork: the parent's XLA runtime holds locks and threads
        # a forked child would inherit mid-flight.  Workers share the
        # parent's persistent cache dir (fleet-wide compile-once) but run
        # without a compile-ahead thread of their own — the parent prepares.
        return ("jax", {"vec_cap": self.vec_cap, "seed": self.seed,
                        "pallas": self.pallas, "cache_dir": self.cache_dir,
                        "prepare": "off"}, "spawn")

    def cost_hint(self, nest: LoopNest) -> float:
        """Slab count, like the interpreter's hint: compiled programs still
        spend their time iterating slabs, and every schedule of one
        contraction shares its FLOPs (the default hint would make the
        pool's longest-first ordering a no-op on same-contraction batches)."""
        from .cpu_backend import estimated_slab_count

        return estimated_slab_count(nest, self.vec_cap)

    def peak(self) -> float:
        """Empirical peak GFLOPS of the XLA target: best-of-5 timing of a
        high-arithmetic-intensity jitted matmul.  Memoized per (device
        kind, process)."""
        import jax

        device = jax.default_backend()
        peak = _PEAK_CACHE.get(device)
        if peak is None:
            import jax.numpy as jnp

            n = 512
            a = jnp.asarray(np.random.default_rng(0).standard_normal(
                (n, n), dtype=np.float32))
            b = jnp.asarray(np.random.default_rng(1).standard_normal(
                (n, n), dtype=np.float32))
            mm = jax.jit(jnp.matmul)
            mm(a, b).block_until_ready()  # warm-up / compile
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                mm(a, b).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            peak = 2 * n**3 / best / 1e9
            _PEAK_CACHE[device] = peak
        return peak

    def compile_stats(self) -> Dict[str, Any]:
        """Compile accounting: ``compile_misses`` = actual traces this
        process performed, ``compile_hits`` = executables served without one
        (in-memory kernel-cache hits + persistent-store loads)."""
        out = {
            "compile_misses": self.compiles,
            "compile_hits": self.kernels.hits + self.persist_loads,
            "compile_s": round(self.compile_s, 4),
            "backend_compile_s": round(self.backend_compile_s, 4),
            "persist_loads": self.persist_loads,
            "persist_load_s": round(self.persist_load_s, 4),
            "export_errors": self.export_errors,
            "last_export_error": self.last_export_error,
            "deser_errors": self.deser_errors,
            "prepared": self.prepared,
            "prepare_errors": self.prepare_errors,
        }
        if self.store is not None:
            out["store"] = self.store.stats()
        return out

    def stats(self) -> Dict[str, Any]:
        return {
            "compiles": self.compiles,
            "kernel_cache": self.kernels.stats(),
            "inputs_cache": self._inputs_cache.stats(),
            "compile": self.compile_stats(),
            "measure": self.measure_stats(),
        }
