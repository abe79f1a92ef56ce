"""mfu: the served prefill and decode steps (``models/steps.py``) as a
share of the chip's bf16 peak, in %.

Model operations of every step in the traced window (the block's
``model_flops``: for ``dense``, two per weight per token, the logits
product, attention over the cache length actually filled) over the traced
window's seconds times the peak.
"""


def read(run):
    if run.trace is None or not run.steps:
        return None
    flops = sum(s["flops"] for s in run.steps.values())
    if flops <= 0:
        return None
    return 100.0 * flops / (run.trace.window_s * run.peaks["flops_bf16"])
