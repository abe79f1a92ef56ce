"""tune.compiles: programs traced per table tuned in the traced window
(``core/jax_backend._trace``), on any thread: the compile layer's work
count, the tuning thread's and the compile-ahead thread's together.

Read from the program's span totals (``repro.runtime.spans``), which
record only while the benchmark's trace collects: the count of
``looptune.compile.trace`` spans over the count of ``looptune.tune_model``
spans on the tuning thread (the benchmark's main thread, which runs the
window).  A program without those spans gives nothing to read.
"""
import threading


def read(run):
    if run.trace is None:
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    tuning = spans.totals(thread=threading.main_thread().name)
    tables = tuning.get("looptune.tune_model", {}).get("count", 0)
    if not tables:
        return None
    traced = spans.totals().get("looptune.compile.trace", {}).get("count", 0)
    return traced / tables
