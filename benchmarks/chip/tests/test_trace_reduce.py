"""The reduction from a trace to busy time, idle gaps and kernel time.

``data/small.xplane.pb`` is a trace recorded on one TPU v5e: four rounds
of the program's Pallas matmul (16x2048 @ 2048x2048, bf16) and an XLA
reduction, each round inside the benchmark's ``decode`` and
``read_token`` spans, all inside one ``window`` span.
"""
import os
import pytest

import trace_reduce as tr
import work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")


def _trace():
    return tr.Trace(
        devices={"/device:TPU:0": [("a.1", 10, 20), ("b.2", 15, 30),
                                   ("a.3", 50, 60), ("c", 95, 120)]},
        spans=[("window", 0, 100), ("decode", 0, 40), ("read_token", 40, 70),
               ("decode", 70, 100)])


def test_busy_is_the_union_inside_the_window():
    r = tr.reduce(_trace())
    assert r.window_s == pytest.approx(100e-9)
    # [10, 30) + [50, 60) + [95, 100)
    assert r.busy_s == pytest.approx(35e-9)
    assert r.op_seconds == pytest.approx({"a.1": 10e-9, "b.2": 15e-9,
                                          "a.3": 10e-9, "c": 5e-9})


def test_idle_gaps_are_labelled_by_the_host_span():
    r = tr.reduce(_trace())
    # gaps [0,10) decode, [30,50) mid 40 read_token, [60,95) mid 77.5 decode
    assert r.idle_by_span == pytest.approx({"decode": 45e-9,
                                            "read_token": 20e-9})
    b = tr.breakdown(r)
    assert b["idle_gaps"][0][0] == "decode"
    assert [k for k, _ in b["device_ops"]][0] == "b.2"


def test_kernel_time_matches_by_name():
    r = tr.reduce(_trace())
    assert r.seconds_matching(r"^a\.") == pytest.approx(20e-9)


def test_op_names_keep_the_instruction_and_its_result():
    hlo = ('%matmul.48 = bf16[8192,8192]{1,0:T(8,128)(2,1)} custom-call('
           'bf16[8192,3072]{1,0} %x), custom_call_target="tpu_custom_call"')
    assert tr.op_name(hlo) == "matmul.48 bf16[8192,8192]"
    loop = "%while.4 = (s32[], bf16[16,1,2048]) while(%t), body=%b"
    assert tr.op_name(loop) == "while.4"
    t = tr.Trace(devices={"d": [(loop, 0, 10), (hlo, 2, 5)]},
                 spans=[("window", 0, 10)])
    assert tr.reduce(t).op_seconds == pytest.approx(
        {"matmul.48 bf16[8192,8192]": 3e-9})


def test_recorded_trace():
    t = tr.load(SMALL)
    assert list(t.devices) == ["/device:TPU:0"]
    assert sum(1 for s in t.spans if s[0] == "decode") == 4
    assert sum(1 for s in t.spans if s[0] == "read_token") == 4
    r = tr.reduce(t)
    assert 0 < r.busy_s < r.window_s
    assert set(r.idle_by_span) <= {"decode", "read_token", "outside_spans"}
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mm_roofline", os.path.join(os.path.dirname(DATA), "..", "metrics",
                                    "mm_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    kernel_s = r.seconds_matching(mod.KERNEL_EVENT)
    assert kernel_s > 0
    # four calls of one 16x2048x2048 bf16 product: bandwidth-bound
    cfg = {"dtype": "bfloat16"}
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    least = 4 * work.least_seconds(cfg, (16, 2048, 2048), 2, peaks)
    assert 0 < least / kernel_s <= 1.0


def test_roofline_counts_the_weight_copies_that_feed_the_kernel():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "mm_roofline", os.path.join(os.path.dirname(DATA), "..", "metrics",
                                    "mm_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    weights = mod.routed_weights({"mm:16x2048x8192:bfloat16",
                                  "mm:16x8192x2048:bfloat16"})
    assert weights == {("bf16", 2048, 8192), ("bf16", 8192, 2048)}
    events = [
        ("%dynamic-slice_bitcast_fusion.20 = bf16[8192,2048]{1,0} fusion()",
         0, 4),
        ("%dynamic-slice_bitcast_fusion.3 = bf16[2048,2048]{1,0} fusion()",
         4, 10),
        ("%copy.17 = bf16[2048,8192]{1,0} copy()", 10, 20),
    ]
    assert mod.staging_seconds(events, weights) == pytest.approx(4e-9)
