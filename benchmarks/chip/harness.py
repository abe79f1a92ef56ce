"""The benchmark's runner, led by ``BENCHMARK.json`` and files found by name.

A workload names a configuration and a traffic mix.  The configuration's
file (``BENCHMARK.json`` gives its path) holds the sizes as run: each of
its keys is a field of the program's ``ModelConfig``, set over the zoo's
``arch`` entry, or one of :data:`META_KEYS`.  Its ``block`` names the
module ``reference/<block>.py`` (``dense`` when the key is absent) that
makes the weights, holds the plain reference and counts a step's work: a
new block is one new file there with the four :data:`BLOCK_FUNCTIONS`,
which ``reference/dense.py`` sets out.  The traffic mix is
``traffic/<name>.json``; the limits of the comparison that decides
``correct`` are ``limits/<workload>.json``; a per-layer metric is read by
``metrics/<name>.py``, whose ``read(run)`` returns a number or ``None``
when the run holds nothing for it to read, in the cells that its
``workloads`` lists.  Adding a cell, a metric or a block adds files; no
file here changes.

The traffic's ``kind`` picks the loop: ``serve`` (``serve_loop.py``) or
``tune`` (``tune_loop.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import time
import traceback
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
#: where the data files live, relative to the checkout's root
DATA = os.path.join("benchmarks", "chip")
#: the fields of the program's model configuration every config file sets
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "act", "frontend", "tie_embeddings",
              "rope_theta", "dtype")
#: the keys of a config file that describe it and set no field of the
#: program's model configuration (``norm_eps`` is the reference's)
META_KEYS = ("name", "source", "paper", "arch", "block", "reduced",
             "assumed", "departures", "norm_eps")
#: what a block module under ``reference/`` provides
BLOCK_FUNCTIONS = ("program_params", "logits", "sites", "model_flops")
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


class SpecError(Exception):
    """The benchmark's files do not describe the asked-for run."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def _load_module(name: str, path: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Spec:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: str):
        self.root = root
        self.data = os.path.join(root, DATA)
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise SpecError(f"unknown config {name!r}")

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.data, "traffic", name + ".json"))

    def limits(self, workload: str) -> dict:
        return _load_json(os.path.join(self.data, "limits",
                                       workload + ".json"))

    def end_to_end(self, workload: str) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> List[dict]:
        return [m for m in self.doc["per_layer"]
                if workload in m["workloads"]]

    def reader(self, metric: str) -> Callable:
        path = os.path.join(self.data, "metrics", metric + ".py")
        if not os.path.exists(path):
            raise SpecError(f"no reader {path} for metric {metric!r}")
        return _load_module("metric_" + metric.replace(".", "_"), path).read

    def block(self, cfg: dict):
        """The block module a config file names: ``reference/<block>.py``,
        ``dense`` when the file names none."""
        name = cfg.get("block", "dense")
        path = os.path.join(self.data, "reference", f"{name}.py")
        if not NAME.fullmatch(name) or not os.path.exists(path):
            raise SpecError(f"no block {path} for config {cfg.get('name')!r}")
        mod = _load_module("block_" + name.replace(".", "_"), path)
        missing = [f for f in BLOCK_FUNCTIONS
                   if not callable(getattr(mod, f, None))]
        if missing:
            raise SpecError(f"block {path} lacks {missing}")
        return mod

    def peaks(self, kind: str) -> dict:
        table = _load_json(os.path.join(self.data, "peaks.json"))
        if kind not in table:
            raise SpecError(f"no peaks for device kind {kind!r} in "
                            f"peaks.json (have {sorted(set(table) - {'source'})})")
        return table[kind]


def model_config(cfg: dict):
    """The program's configuration object for a config file: the zoo's
    ``arch`` entry with every field the file sets.  ``moe``, an object,
    sets the fields of the entry's ``MoEConfig`` that it names (all that
    have no default where the entry has none); ``period``, a list of
    ``{mixer, ffn, window}``, is the whole period.  A key that is neither a
    field nor one of :data:`META_KEYS`, or a missing one of
    :data:`MODEL_KEYS`, fails the run."""
    from repro.configs import ARCHS, LayerSpec, ModelConfig, MoEConfig

    name = cfg.get("name")
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(cfg) - fields - set(META_KEYS))
    missing = [k for k in ("name", "arch") + MODEL_KEYS if k not in cfg]
    if unknown or missing:
        raise SpecError(f"config {name!r}: unknown keys {unknown}, "
                        f"missing keys {missing}")
    if cfg["arch"] not in ARCHS:
        raise SpecError(f"config {name!r}: unknown arch {cfg['arch']!r}")
    base = ARCHS[cfg["arch"]]
    values = {k: v for k, v in cfg.items() if k in fields}
    try:
        if values.get("moe") is not None:
            values["moe"] = (MoEConfig(**values["moe"]) if base.moe is None
                             else dataclasses.replace(base.moe,
                                                      **values["moe"]))
        if "period" in values:
            values["period"] = tuple(LayerSpec(**s) for s in values["period"])
    except TypeError as e:
        raise SpecError(f"config {name!r}: {e}") from None
    return dataclasses.replace(base, **values)


def log(phase: str, **fields) -> None:
    print(f"[bench] {phase} {json.dumps(fields, default=str)}", flush=True)


def device_info(jax) -> Dict[str, Any]:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def use_compile_cache(jax, state: str, on: bool) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (every program, however quick to compile), or none at all."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    if on:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(state, "jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", on)
    cc.reset_cache()


def _spans(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the cells
# ---------------------------------------------------------------------------


def _served_table(registry_path: str) -> None:
    from repro.core.registry import ScheduleRegistry

    reg = ScheduleRegistry(registry_path)
    for rkey, e in sorted(reg.entries()):
        log("table", key=reg.split_key(rkey)[0], block=e.get("block"),
            grid_order=e.get("grid_order"), gflops=e.get("gflops"))


def tune_for_serving(model_cfg, traffic, path) -> None:
    """Tune the cell's table once per checkout; later runs serve from it.
    The traffic's ``tune`` names the tune's reward: the program's
    analytical cost model gives the same table to the same code on every
    machine, where a timed reward would give each checkout its own.  The
    table appears under ``path`` only when the tune has finished."""
    from tune_loop import least_wall_budget, tune_table

    partial = path + ".tuning"
    took, report = tune_table(model_cfg, traffic, partial, path + ".kernels",
                              least_wall_budget(model_cfg, traffic))
    os.replace(partial, path)
    log("setup.tune", seconds=took, n_harvested=report["n_harvested"],
        n_tuned=report["n_tuned"],
        flop_share_covered=report["flop_share_covered"])


class Run:
    """One run of one cell: set-up, the window, the check."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, t_start: float, require_chip: bool = True):
        self.spec = Spec(root)
        self.wl = self.spec.workload(workload)
        self.name, self.seed, self.seconds = workload, seed, seconds
        self.trace, self.t_start = trace, t_start
        self.require_chip = require_chip
        self.state = os.path.join(root, DATA, "state", workload)
        self.cfg = self.spec.config(self.wl["config"])
        self.block = None
        self.traffic = self.spec.traffic(self.wl["traffic"])
        self.limits = self.spec.limits(workload)
        self.setup_parts: Dict[str, float] = {}

    def _mark(self, part: str, t0: float) -> float:
        t = time.perf_counter()
        self.setup_parts[part] = t - t0
        return t

    def execute(self) -> Dict[str, Any]:
        t = time.perf_counter()
        try:
            import repro  # noqa: F401 - the system under test
        except ImportError as e:
            raise SpecError(f"the program is not beside the benchmark: {e}")
        import jax
        t = self._mark("import", t)
        dev = device_info(jax)
        log("device", **dev)
        if self.require_chip and dev["platform"] != "tpu":
            raise SpecError(f"needs a TPU; JAX found platform "
                            f"{dev['platform']!r}")
        if dev["count"] < self.wl["chips"]:
            raise SpecError(f"the cell asks for {self.wl['chips']} chips; "
                            f"JAX found {dev['count']}")
        peaks = (self.spec.peaks(dev["kind"]) if self.require_chip
                 else self.spec.peaks("TPU v5 lite"))
        os.makedirs(self.state, exist_ok=True)
        kind = self.traffic["kind"]
        use_compile_cache(jax, self.state,
                          on=kind != "tune" and dev["platform"] == "tpu")
        model_cfg = model_config(self.cfg)
        self.block = self.spec.block(self.cfg)
        if kind == "serve":
            return self._serve(jax, dev, peaks, model_cfg, t)
        if kind == "tune":
            return self._tune(jax, dev, peaks, model_cfg, t)
        raise SpecError(f"unknown traffic kind {kind!r}")

    # -- serving --------------------------------------------------------------

    def _serve(self, jax, dev, peaks, model_cfg, t):
        import correct
        from repro.core.registry import ScheduleRegistry
        from repro.kernels import ops as K
        from serve_loop import ServeLoop

        path = os.path.join(self.state, "registry.json")
        if not os.path.exists(path):
            tune_for_serving(model_cfg, self.traffic, path)
            t = self._mark("tune", t)
        _served_table(path)
        loop = ServeLoop(self.cfg, model_cfg, self.traffic, self.seed,
                         ScheduleRegistry(path), self.block)
        loop.make_weights()
        t = self._mark("weights", t)
        K.reset_serving_stats()
        loop.warm_up()
        stats = K.serving_stats(reset=True)
        t = self._mark("compile_and_warm_up", t)
        log("serving_stats", hits=stats["hits"], misses=stats["misses"],
            routed=stats["routed"], per_key=stats["per_key"])
        routed = {k for k, v in stats["per_key"].items() if v["routed"]}
        setup_s = time.perf_counter() - self.t_start
        log("setup", setup_s=setup_s, **self.setup_parts)

        res, reduced = self._window(jax, loop.run)
        peak = memory_peak(jax)
        e2e = loop.end_to_end(res)
        e2e["setup_s"] = setup_s
        log("window", elapsed_s=res["elapsed_s"], emitted=res["emitted"],
            admitted=res["admitted"], waves=res["waves"],
            finished_waves=len(res["finished"]),
            steps={k: v["count"] for k, v in res["steps"].items()},
            itl_p50_ms=_ms_percentile(res["itl"], 50),
            itl_max_ms=_ms_percentile(res["itl"], 100),
            ttft_p50_ms=_ms_percentile(res["ttft"], 50),
            ttft_p95_ms=_ms_percentile(res["ttft"], 95), **e2e)
        loop.free()
        jax.clear_caches()
        t0 = time.perf_counter()
        numbers = correct.serve_numbers(self.cfg, self.seed, loop, res,
                                        self.traffic, self.limits)
        log("reference", seconds=time.perf_counter() - t0)
        ctx = SimpleNamespace(cfg=self.cfg, block=self.block,
                              traffic=self.traffic, steps=res["steps"],
                              routed_keys=routed, peaks=peaks, trace=reduced)
        return self._result(dev, peak, e2e, ctx, reduced, numbers,
                            attempted=res["admitted"], failed=0)

    # -- tuning ---------------------------------------------------------------

    def _tune(self, jax, dev, peaks, model_cfg, t):
        import correct
        from tune_loop import TuneLoop

        loop = TuneLoop(model_cfg, self.traffic, self.state, log)
        loop.warm_up()
        t = self._mark("harvest", t)
        setup_s = time.perf_counter() - self.t_start
        log("setup", setup_s=setup_s, **self.setup_parts)
        res, reduced = self._window(jax, loop.run)
        peak = memory_peak(jax)
        e2e = loop.end_to_end(res)
        e2e["setup_s"] = setup_s
        log("window", tables=res["tables"], elapsed_s=res["elapsed_s"], **e2e)
        pallas = "on" if dev["platform"] == "tpu" else "interpret"
        numbers = correct.tune_numbers(loop.last_registry, self.seed,
                                       self.limits, pallas)
        shutil.rmtree(loop.dir, ignore_errors=True)
        ctx = SimpleNamespace(cfg=self.cfg, block=self.block,
                              traffic=self.traffic, steps=None,
                              routed_keys=set(), peaks=peaks, trace=reduced)
        return self._result(dev, peak, e2e, ctx, reduced, numbers,
                            attempted=len(res["tables"]), failed=0)

    # -- window and result ------------------------------------------------------

    def _window(self, jax, body):
        """Run ``body(seconds, span)``; traced, inside one ``window`` span.
        The set-up's objects are collected and frozen first, so no garbage
        collection inside the window walks them."""
        import trace_reduce

        gc.collect()
        gc.freeze()
        span = _spans(self.trace)
        if not self.trace:
            return body(self.seconds, span), None
        tdir = os.path.join(self.state, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                res = body(self.seconds, span)
        finally:
            jax.profiler.stop_trace()
        t0 = time.perf_counter()
        reduced = trace_reduce.reduce(
            trace_reduce.load(trace_reduce.find_xplane(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        log("trace", reduce_s=time.perf_counter() - t0,
            window_s=reduced.window_s, busy_s=reduced.busy_s,
            events=len(reduced.device_events))
        return res, reduced

    def _result(self, dev, peak, e2e, ctx, reduced, numbers, attempted,
                failed) -> Dict[str, Any]:
        import correct
        import trace_reduce

        device = dict(dev, memory_peak_bytes=peak)
        metrics: Dict[str, Dict[str, Any]] = {}
        out: Dict[str, Any] = {"correct": correct.is_correct(numbers),
                               "attempted": attempted, "failed": failed,
                               "metrics": metrics, "device": device}
        if reduced is None:
            for m in self.spec.end_to_end(self.name):
                if m["name"] not in e2e:
                    raise SpecError(f"the run produced no {m['name']!r}")
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
        else:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            for m in self.spec.per_layer(self.name):
                value = self.spec.reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            out["breakdown"] = trace_reduce.breakdown(reduced)
        out["compared"] = numbers
        return out


def _ms_percentile(values, q):
    from serve_loop import percentile
    return percentile(values, q) * 1e3 if values else None


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(HERE))
    try:
        run = Run(root, args.workload, args.seed, args.seconds,
                  bool(args.trace), t_start)
        out = run.execute()
    except SpecError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    except Exception:  # noqa: BLE001 - any fault ends the run without a result
        traceback.print_exc()
        return 1
    print(json.dumps(out), flush=True)
    for name, v in out["compared"].items():
        print(f"compared {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return 0
