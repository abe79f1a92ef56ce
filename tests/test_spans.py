"""The program's spans and counters (``runtime/spans.py``): they record only
while a profiler session collects, self time leaves out child spans on the
same thread, and a traced tune records every layer of the tune path
without a span inside a timed run."""
import sys
import threading

import pytest

jax = pytest.importorskip("jax")

from repro.configs import get_config  # noqa: E402
from repro.core import LoopNest, MeasurementPolicy, matmul_benchmark  # noqa: E402
from repro.core.jax_backend import JaxJitBackend  # noqa: E402
from repro.core.registry import ScheduleRegistry  # noqa: E402
from repro.launch.tune import tune_model  # noqa: E402
from repro.runtime import spans  # noqa: E402


def _smoke_tune():
    return tune_model(get_config("musicgen-large").smoke(),
                      registry=ScheduleRegistry(), smoke=False, backend="jax",
                      budget_s=5, eval_budget=3, max_contractions=1, batch=2,
                      prompt_len=8, max_len=16, kinds=("decode",))


@pytest.fixture(scope="module")
def tunes(tmp_path_factory):
    """One smoke tune with no profiler session, then one under
    ``jax.profiler.start_trace``: (totals untraced, totals traced, the
    traced tune's report)."""
    spans.reset()
    _smoke_tune()
    untraced = spans.totals()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
    try:
        report = _smoke_tune()
    finally:
        jax.profiler.stop_trace()
    traced = spans.totals()
    spans.reset()
    return untraced, traced, report


@pytest.fixture
def fake_session(monkeypatch):
    """Spans record as under a profiler session, on a clock the test sets."""
    now = [0.0]
    monkeypatch.setattr(spans, "recording", lambda: True)
    monkeypatch.setattr(spans, "_clock", lambda: now[0])
    spans.reset()
    yield now
    spans.reset()


def test_recording_follows_the_profiler_session(tmp_path):
    assert not spans.recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert spans.recording()
    finally:
        jax.profiler.stop_trace()
    assert not spans.recording()


def test_no_session_records_nothing(tunes):
    untraced, _, _ = tunes
    assert untraced == {}


def test_traced_tune_records_each_layer(tunes):
    _, traced, _ = tunes
    for name in ("looptune.tune_model", "looptune.harvest",
                 "looptune.contraction", "looptune.compile.trace",
                 "looptune.compile.backend", "looptune.inputs",
                 "looptune.measure"):
        assert traced[name]["count"] >= 1, name
        assert traced[name]["seconds"] > 0, name
    assert traced["looptune.tune_model"]["count"] == 1
    assert traced["looptune.inputs.bytes"]["count"] == 4 * (2 * 64 + 64 * 256)
    assert traced["looptune.measure.runs"]["count"] >= 2
    assert set(traced) <= set(spans.SPAN_NAMES) | set(spans.COUNTER_NAMES)


def test_compile_trace_count_is_compile_misses(tunes):
    _, traced, report = tunes
    assert (traced["looptune.compile.trace"]["count"]
            == report["compile"]["compile_misses"])
    assert report["compile"]["backend_compile_s"] > 0


def test_traced_tune_splits_the_table(tunes):
    """The table's and the contraction's spans hold their children, so
    their self times are what is left of them."""
    _, traced, report = tunes
    table = traced["looptune.tune_model"]
    assert 0 < table["self_seconds"] < table["seconds"]
    assert table["seconds"] == pytest.approx(report["tune_time_s"], abs=0.01)
    contraction = traced["looptune.contraction"]
    assert 0 < contraction["self_seconds"] < contraction["seconds"]


def test_self_time_leaves_out_children(fake_session):
    now = fake_session
    with spans.span("looptune.tune_model"):
        now[0] += 1.0
        with spans.span("looptune.harvest"):
            now[0] += 2.0
        with spans.timed("looptune.contraction") as c:
            now[0] += 0.5
            with spans.span("looptune.measure"):
                now[0] += 3.0
            now[0] += 0.25
        now[0] += 4.0
    assert c.seconds == 3.75
    t = spans.totals()
    assert t["looptune.tune_model"] == {"count": 1, "seconds": 10.75,
                                        "self_seconds": 5.0}
    assert t["looptune.harvest"] == {"count": 1, "seconds": 2.0,
                                     "self_seconds": 2.0}
    assert t["looptune.contraction"] == {"count": 1, "seconds": 3.75,
                                         "self_seconds": 0.75}
    assert t["looptune.measure"]["self_seconds"] == 3.0


def test_timed_spans_time_without_a_session(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(spans, "_clock", lambda: now[0])
    with spans.timed("looptune.compile.trace") as sp:
        now[0] += 1.5
    assert sp.seconds == 1.5
    assert spans.totals() == {}
    spans.count("looptune.measure.runs", 3)
    assert spans.totals() == {}


def test_unknown_names_are_refused(fake_session):
    with pytest.raises(ValueError):
        spans.span("tune")
    with pytest.raises(ValueError):
        spans.count("looptune.tune_model")


def test_threads_keep_separate_stacks(fake_session):
    """A span another thread opens inside this thread's span is not its
    child: the outer span's self time stays its whole duration."""
    now = fake_session
    opened, closed = threading.Event(), threading.Event()

    def other():
        opened.wait(timeout=10)
        with spans.span("looptune.compile.trace"):
            now[0] += 2.0
        closed.set()

    t = threading.Thread(target=other, name="looptune-compile-ahead")
    t.start()
    with spans.span("looptune.contraction"):
        now[0] += 1.0
        opened.set()
        assert closed.wait(timeout=10)
        now[0] += 1.0
    t.join(timeout=10)
    assert not t.is_alive()
    mine = spans.totals(thread=threading.current_thread().name)
    assert mine == {"looptune.contraction": {"count": 1, "seconds": 4.0,
                                             "self_seconds": 4.0}}
    ahead = spans.totals(thread="looptune-compile-ahead")
    assert ahead == {"looptune.compile.trace": {"count": 1, "seconds": 2.0,
                                                "self_seconds": 2.0}}
    assert spans.totals()["looptune.compile.trace"]["count"] == 1


def test_concurrent_spans_lose_no_update(fake_session):
    n_threads, per_thread = 16, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with spans.span("looptune.measure"):
                    spans.count("looptune.measure.runs")

        threads = [threading.Thread(target=work, name=f"w{i}")
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    t = spans.totals()
    assert t["looptune.measure"]["count"] == n_threads * per_thread
    assert t["looptune.measure.runs"]["count"] == n_threads * per_thread


def test_no_span_between_the_clock_reads_of_a_timed_run(monkeypatch):
    """The policy's two clock reads around each timed run have no span
    event between them, though the warm-up opens compile and operand
    spans."""
    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    def clock():
        log.append(("clock", None))
        return float(len(log))

    monkeypatch.setattr(spans, "recording", lambda: True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    spans.reset()
    backend = JaxJitBackend(policy=MeasurementPolicy(repeats=3, clock=clock),
                            prepare="off")
    try:
        backend.measure(LoopNest(matmul_benchmark(16, 16, 16)))
    finally:
        backend.close()
        spans.reset()
    reads = [i for i, (kind, _) in enumerate(log) if kind == "clock"]
    assert len(reads) >= 6 and len(reads) % 2 == 0
    for a, b in zip(reads[::2], reads[1::2]):
        assert b == a + 1, log[a:b + 1]
    opened = {name for kind, name in log if kind == "enter"}
    assert {"looptune.measure", "looptune.compile.trace",
            "looptune.compile.backend", "looptune.inputs"} <= opened
