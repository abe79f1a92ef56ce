"""The readers of the program's tuner spans (``metrics/tune.*.py``), and
that the program's spans leave the trace's own readings as they were.

``data/small.xplane.pb`` is the recorded v5e trace of
``test_trace_reduce.py``; the values pinned below are what the readers
and the breakdown read from it before the program had spans.
"""
import importlib.util
import os
import sys
import threading
from types import SimpleNamespace

import pytest

import harness
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
SMALL = os.path.join(HERE, "data", "small.xplane.pb")
READERS = ("tune.harvest_s", "tune.search_s", "tune.compile_s",
           "tune.inputs_s", "tune.timing_s", "tune.compiles")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _t(count, seconds=0.0, own=None):
    return {"count": count, "seconds": seconds,
            "self_seconds": seconds if own is None else own}


#: two tables: the tuning thread's totals and the compile-ahead thread's
TUNING = {
    "looptune.tune_model": _t(2, 176.0, 6.0),
    "looptune.harvest": _t(2, 18.0),
    "looptune.contraction": _t(24, 150.0, 30.0),
    "looptune.compile.trace": _t(40, 20.0),
    "looptune.compile.load": _t(2, 1.0),
    "looptune.compile.wait": _t(6, 3.0),
    "looptune.compile.backend": _t(70, 40.0),
    "looptune.inputs": _t(24, 8.0),
    "looptune.measure": _t(200, 90.0, 48.0),
    "looptune.registry.flush": _t(26, 2.0),
}
AHEAD = {"looptune.compile.trace": _t(116, 60.0),
         "looptune.compile.wait": _t(3, 0.5)}
EXPECTED = {"tune.harvest_s": 9.0, "tune.search_s": 15.0,
            "tune.compile_s": 32.0, "tune.inputs_s": 4.0,
            "tune.timing_s": 24.0, "tune.compiles": 78.0}


@pytest.fixture
def program_totals(monkeypatch):
    from repro.runtime import spans

    def totals(thread=None):
        if thread == threading.main_thread().name:
            return TUNING
        assert thread is None
        out = {k: dict(v) for k, v in TUNING.items()}
        for k, v in AHEAD.items():
            for f in ("count", "seconds", "self_seconds"):
                out[k][f] += v[f]
        return out

    monkeypatch.setattr(spans, "totals", totals)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_per_table(name, program_totals):
    run = SimpleNamespace(trace=object())
    assert _reader(name)(run) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_untraced(name, program_totals):
    assert _reader(name)(SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_with_no_table(name, monkeypatch):
    from repro.runtime import spans

    monkeypatch.setattr(spans, "totals", lambda thread=None: {})
    assert _reader(name)(SimpleNamespace(trace=object())) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_program_spans(name, monkeypatch):
    """A program that has no span module (the parent's) gives nothing to
    read, and the reader does not raise."""
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "spans", raising=False)
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    assert _reader(name)(SimpleNamespace(trace=object())) is None


def test_program_spans_are_not_benchmark_spans():
    from repro.runtime import spans

    names = set(spans.SPAN_NAMES) | set(spans.COUNTER_NAMES)
    assert not names & ({tr.WINDOW} | set(tr.SPANS))
    assert all(n.startswith("looptune.") for n in names)


def test_recorded_trace_reads_as_before():
    r = tr.reduce(tr.load(SMALL))
    assert r.window_s == pytest.approx(0.00723566, rel=1e-9)
    assert r.busy_s == pytest.approx(0.00010465, rel=1e-9)
    assert tr.breakdown(r) == {
        "device_ops": [["tanh_reduce_fusion f32[]", pytest.approx(5.3021e-05)],
                       ["matmul.1 bf16[16,2048]", pytest.approx(5.1629e-05)]],
        "idle_gaps": [["read_token", pytest.approx(0.006899034)],
                      ["decode", pytest.approx(0.000231976)]]}
    # one dense layer whose q, k, v and o are the trace's four
    # 16x2048x2048 products, all routed
    cfg = {"n_layers": 1, "d_model": 2048, "n_heads": 32, "n_kv_heads": 32,
           "head_dim": 64, "d_ff": 8192, "vocab": 2048, "dtype": "bfloat16"}
    run = SimpleNamespace(
        cfg=cfg, block=harness.Spec(ROOT).block(cfg), traffic={}, trace=r,
        steps={"decode": {"m": 16, "count": 1, "flops": 1e9}},
        routed_keys={"mm:16x2048x2048:bfloat16"},
        peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    assert _reader("mm_roofline")(run) == pytest.approx(100.89817340780945,
                                                        rel=1e-9)
    assert _reader("mfu")(run) == pytest.approx(
        100 * 1e9 / (0.00723566 * 197e12), rel=1e-9)
    assert _reader("idle_share.serve")(run) == pytest.approx(
        98.5536910247303, rel=1e-9)
    assert _reader("idle_share.tune")(run) == pytest.approx(
        98.5536910247303, rel=1e-9)


def test_program_spans_leave_the_idle_labels_alone(tmp_path):
    """A trace taken while the program's spans record inside a benchmark
    span: the reduction sees the benchmark's spans only, so each idle gap
    keeps the benchmark's label."""
    import jax
    import jax.numpy as jnp

    from repro.runtime import spans

    f = jax.jit(lambda x: jnp.tanh(x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    spans.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            with jax.profiler.TraceAnnotation("tune"):
                with spans.span("looptune.tune_model"):
                    with spans.span("looptune.measure"):
                        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert spans.totals()["looptune.measure"]["count"] == 1
    spans.reset()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    assert {s[0] for s in t.spans} == {tr.WINDOW, "tune"}
    r = tr.reduce(t)
    assert set(r.idle_by_span) <= {"tune", "outside_spans"}


def test_traced_tune_cell_reports_the_split(tmp_path, capsys):
    """A traced run of a small tune cell on the CPU reports the six parts;
    they add up to the table's seconds less what the table's own span
    keeps, and the compiles are the table's printed compile misses."""
    import json
    import time

    import harness
    import tiny

    root = tiny.make_root(str(tmp_path))
    out = harness.Run(root, "tiny-tokens.tune", 2 ** 31 + 5, 1.0, True,
                      time.perf_counter(), require_chip=False).execute()
    tables = [json.loads(line.split(" ", 2)[2])
              for line in capsys.readouterr().out.splitlines()
              if line.startswith("[bench] tune.table ")]
    assert len(tables) == 1
    got = {n: out["metrics"][n]["value"] for n in READERS}
    assert got["tune.compiles"] == tables[0]["compile"]["compile_misses"]
    parts = sum(v for n, v in got.items() if n != "tune.compiles")
    assert all(v >= 0 for v in got.values())
    assert 0.5 * tables[0]["seconds"] < parts <= tables[0]["seconds"]
