"""tune.compile_s: compile seconds that hold up a table, per table tuned in
the traced window (``core/jax_backend``): tracing and exporting a
candidate's program, loading a stored one, waiting on a build another
thread or process has started, and the XLA/Mosaic compile its first call
pays.  Compiles the compile-ahead thread finishes while the chip times
other candidates are not counted here.

Read from the program's span totals (``repro.runtime.spans``), which
record only while the benchmark's trace collects: the seconds of the
``looptune.compile.*`` spans over the count of ``looptune.tune_model``
spans, both on the tuning thread (the benchmark's main thread, which runs
the window).  A program without those spans gives nothing to read.
"""
import threading

COMPILE_SPANS = ("looptune.compile.trace", "looptune.compile.load",
                 "looptune.compile.wait", "looptune.compile.backend")


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
    return sum(tuning.get(name, {}).get("seconds", 0.0)
               for name in COMPILE_SPANS) / tables
