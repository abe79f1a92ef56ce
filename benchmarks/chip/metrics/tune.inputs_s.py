"""tune.inputs_s: seconds a table spends making each contraction's random
float32 operands on the host and copying them to the chip
(``core/jax_backend._inputs``), per table tuned in the traced window.

Read from the program's span totals (``repro.runtime.spans``), which
record only while the benchmark's trace collects: the ``looptune.inputs``
seconds over the count of ``looptune.tune_model`` spans, both on the
tuning thread (the benchmark's main thread, which runs the window).  A
program without those spans gives nothing to read.
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
    return tuning.get("looptune.inputs", {}).get("seconds", 0.0) / tables
