"""tune.timing_s: seconds a table spends timing candidates on the chip
(``core/measure.MeasurementPolicy.measure``: the warm-up runs after an
executable's first call, and the timed repeats), per table tuned in the
traced window.

Read from the program's span totals (``repro.runtime.spans``), which
record only while the benchmark's trace collects: the self seconds of the
``looptune.measure`` spans (less the compile and operand spans a first
warm-up opens inside them) over the count of ``looptune.tune_model``
spans, both on the tuning thread (the benchmark's main thread, which runs
the window).  A program without those spans gives nothing to read.
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
    own = tuning.get("looptune.measure", {}).get("self_seconds", 0.0)
    return own / tables
