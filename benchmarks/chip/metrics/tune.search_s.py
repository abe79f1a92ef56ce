"""tune.search_s: the search's own host seconds per table tuned in the
traced window: proposing schedules, ranking them with the surrogate,
caching and recording (``core/tuner.LoopTuner.tune``, ``core/search``).

Read from the program's span totals (``repro.runtime.spans``), which
record only while the benchmark's trace collects: the self seconds of the
``looptune.contraction`` spans (each contraction's time less the compile,
operand and timing spans inside it) over the count of
``looptune.tune_model`` spans, both on the tuning thread (the benchmark's
main thread, which runs the window).  A program without those spans gives
nothing to read.
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
    own = tuning.get("looptune.contraction", {}).get("self_seconds", 0.0)
    return own / tables
