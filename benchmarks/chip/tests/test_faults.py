"""Runs with the timed path broken underneath come out not correct, and the
float8 control fails the limits that sound runs meet.

Each fault test skips the harness's look for a chip and drives the rest of
a run of a small cell on the CPU, with one fault planted in the program:
a decode step that returns its cache unchanged, a served token altered
where the step produces it, and, in the tune cell, a kernel whose answer
is altered or left unwritten.
"""
import time

import jax.numpy as jnp
import pytest

import harness
import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("faults")))


def _run(root, workload, seed=2 ** 31 + 3, seconds=1.0):
    return harness.Run(root, workload, seed, seconds, False,
                       time.perf_counter(), require_chip=False).execute()


@pytest.mark.parametrize("workload", ["tiny-tokens.serve", "tiny-embeds.serve"])
def test_sound_serve_run_is_correct(root, workload):
    out = _run(root, workload)
    assert out["correct"] is True, out["compared"]


@pytest.mark.parametrize("fault", ["state", "token"])
@pytest.mark.parametrize("workload", ["tiny-tokens.serve", "tiny-embeds.serve"])
def test_broken_decode_is_not_correct(root, monkeypatch, workload, fault):
    tiny.break_decode(monkeypatch, fault)
    out = _run(root, workload)
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("fault", ["answer", "unwritten"])
def test_broken_kernel_is_not_correct(root, monkeypatch, fault):
    from repro.kernels import ops

    mm = ops._matmul

    def broken(a, b, **kw):
        out = mm(a, b, **kw)
        if fault == "unwritten":
            return jnp.zeros_like(out)
        return out.at[0, 0].add(jnp.abs(out).max())

    sound = _run(root, "tiny-tokens.tune")
    assert sound["correct"] is True, sound["compared"]
    monkeypatch.setattr(ops, "_matmul", broken)
    out = _run(root, "tiny-tokens.tune")
    assert out["correct"] is False, out["compared"]


def test_fp8_control_fails_where_the_program_passes(root):
    """The control's readings at a size a test can hold: the float8
    reference in the program's place fails the limit that the served
    bfloat16 tokens meet, on three seeds, by the harness's own
    comparison."""
    import control

    run = harness.Run(root, "tiny-tokens.serve", 1, 1.0, False,
                      time.perf_counter(), require_chip=False)
    recs = control.serve_readings(run, harness.model_config(run.cfg),
                                  [11, 2 ** 31 + 12, 13], 1.0)
    limit = run.limits["max_logit_gap"]
    assert max(r["program_max_logit_gap"] for r in recs) <= limit
    assert min(r["control_max_logit_gap"] for r in recs) > limit
    assert all(r["program_correct"] for r in recs)
    assert not any(r["control_correct"] for r in recs)
