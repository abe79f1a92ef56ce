"""mm_roofline: the routed Pallas matmul (``kernels/matmul.py``) against
its roofline, in %.

The least time the chip could take for the routed products of the traced
steps, over the device time they took.  Each product's least time is the
larger of its operations over the bf16 peak and its unpadded operands and
output over HBM bandwidth (``work.py``, over the block's ``sites``), so the
same work counts whatever block or padding the kernel uses.  In decode
(m = batch) the bandwidth bound holds: about m operations per byte, far
below the chip's 240.  In prefill (m = batch x prompt) the compute bound
holds.

The device time is the kernel's own events plus XLA's copies of each
layer's weight out of the stacked parameters, which exist only to hand the
kernel its operand: the compiler may put that copy in VMEM, and the kernel
then reads no weight from HBM in its own events (on a v5e the kernel alone
read 112% of its bound in musicgen-large's decode).  Only products whose
registry key the served steps routed count; with none routed, or no
kernel event in the trace, there is nothing to read.
"""
import re

import work

#: the kernel's device events, as a TPU v5e trace names them: the HLO
#: instruction of the Mosaic custom call, named after the jitted
#: ``kernels.matmul.matmul`` (``%matmul.48 = bf16[...] custom-call(...),
#: custom_call_target="tpu_custom_call"``); ``pallas_call`` sets no name
KERNEL_EVENT = r'^%matmul(\.\d+)? = .*custom_call_target="tpu_custom_call"'
#: a layer's weight sliced out of the stack (``%dynamic-slice_bitcast_
#: fusion.20 = bf16[8192,2048]{...} fusion(bf16[48,8192,2048] ...)``)
STAGING_EVENT = re.compile(r"^%[\w.-]*dynamic-slice[\w.-]* = (\w+)\[(\d+),(\d+)\]")
HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def routed_weights(routed_keys):
    """(HLO dtype, k, n) of every routed product's weight."""
    out = set()
    for key in routed_keys:
        _, dims, dtype = key.split(":")
        _, k, n = (int(d) for d in dims.split("x"))
        out.add((HLO_DTYPE.get(dtype, dtype), k, n))
    return out


def staging_seconds(events, weights):
    total = 0.0
    for name, s, e in events:
        m = STAGING_EVENT.match(name)
        if m and (m.group(1), int(m.group(2)), int(m.group(3))) in weights:
            total += e - s
    return total * 1e-9


def read(run):
    if run.trace is None or not run.steps or not run.routed_keys:
        return None
    kernel_s = run.trace.seconds_matching(KERNEL_EVENT)
    if kernel_s <= 0:
        return None
    staged_s = staging_seconds(run.trace.device_events,
                               routed_weights(run.routed_keys))
    least = work.routed_least_seconds(run.block, run.cfg, run.steps,
                                      run.routed_keys, run.peaks)
    return 100.0 * least / (kernel_s + staged_s)
