#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program and its control.

    python3 benchmarks/chip/control.py --workload NAME --seeds 1,2,3 --seconds S

For each seed, in one process with one set-up:

* serve cells: a window of ``S`` seconds at the cell's own load (long
  enough to finish its requests), the sample of finished requests a run
  compares, and two numbers: the widest gap of a served token under the
  float32 reference (the program), and the widest gap of the token that
  the float8 reference puts first at each of the same positions (the
  control);
* tune cells: one table tuned once, then for each seed every entry through
  the kernel against the float32 product (the program) and the float8
  product of the same operands against it (the control).

The control is the reference computed in the precision below the served
bfloat16; a limit stands between the program's largest reading and the
control's smallest.  Each side's numbers also go through the harness's own
comparison against the cell's committed limits (``limits/<cell>.json``):
the program has to come out correct on every seed, the control not
correct on every seed.  The benchmark's own runs never run this.  The last
line of standard output is a JSON summary.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import harness  # noqa: E402


def _judged(rec, program, control):
    """``rec`` with each side's verdict under the committed limits."""
    import correct

    rec.update(program_correct=correct.is_correct(program),
               control_correct=correct.is_correct(control),
               control_compared=control)
    return rec


def serve_readings(run, model_cfg, seeds, seconds):
    import correct
    from repro.core.registry import ScheduleRegistry
    from serve_loop import ServeLoop

    path = os.path.join(run.state, "registry.json")
    if not os.path.exists(path):
        harness.tune_for_serving(model_cfg, run.traffic, path)
    loop = ServeLoop(run.cfg, model_cfg, run.traffic, seeds[0],
                     ScheduleRegistry(path), run.spec.block(run.cfg))
    out = []
    for seed in seeds:
        loop.seed, loop.key = seed, correct.W.base_key(seed)
        loop.params = None
        loop.make_weights()
        loop.warm_up()
        res = loop.run(seconds, harness._spans(False))
        picks = correct.sample_requests(res["finished"], loop.batch,
                                        run.traffic["check_requests"], seed)
        if not picks:
            raise RuntimeError(f"seed {seed}: no request finished in "
                               f"{seconds} s")
        served, control = correct.reference_gaps(
            run.cfg, seed, loop, res["finished"], picks, quant="fp8")
        rec = _judged(
            {"seed": seed, "requests": len(picks),
             "tokens": int(sum(g.size for g in served)),
             "program_max_logit_gap": float(max(g.max() for g in served)),
             "control_max_logit_gap": float(max(g.max() for g in control))},
            correct.gap_numbers(served, run.traffic, run.limits),
            correct.gap_numbers(control, run.traffic, run.limits))
        harness.log("control", **rec)
        out.append(rec)
    return out


def tune_readings(run, model_cfg, seeds):
    import correct
    import jax.numpy as jnp
    from reference import dense
    from repro.core.registry import ScheduleRegistry
    from tune_loop import TuneLoop

    loop = TuneLoop(model_cfg, run.traffic, run.state, harness.log)
    loop.warm_up()
    loop.run(1.0, harness._spans(False))
    reg = ScheduleRegistry(loop.last_registry)
    out = []
    for seed in seeds:
        prog = correct.tune_numbers(loop.last_registry, seed, run.limits,
                                    "on")
        worst = 0.0
        for a, b in correct.table_operands(reg, seed):
            low = dense.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                               "fp8")
            worst = max(worst, float(correct.rel_err(low, a, b)))
        rec = _judged(
            {"seed": seed,
             "program_kernel_rel_err": prog["kernel_rel_err"]["value"],
             "program_entries_not_routed":
                 prog["entries_not_routed"]["value"],
             "control_kernel_rel_err": worst},
            prog, correct.kernel_numbers(worst, 0, run.limits))
        harness.log("control", **rec)
        out.append(rec)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    import jax

    run = harness.Run(ROOT, args.workload, seeds[0], args.seconds, False,
                      T_START)
    dev = harness.device_info(jax)
    if dev["platform"] != "tpu":
        print(f"control: needs a TPU; JAX found {dev['platform']!r}",
              file=sys.stderr)
        return 1
    kind = run.traffic["kind"]
    os.makedirs(run.state, exist_ok=True)
    harness.use_compile_cache(jax, run.state, on=kind != "tune")
    model_cfg = harness.model_config(run.cfg)
    if kind == "serve":
        recs = serve_readings(run, model_cfg, seeds, args.seconds)
    else:
        recs = tune_readings(run, model_cfg, seeds)
    summary = {"workload": args.workload, "device": dev, "readings": recs,
               "program_correct_on_every_seed":
                   all(r["program_correct"] for r in recs),
               "control_not_correct_on_every_seed":
                   not any(r["control_correct"] for r in recs)}
    for name in recs[0]:
        if name.startswith(("program_", "control_")) and \
                isinstance(recs[0][name], float):
            vals = [r[name] for r in recs]
            summary[name] = {"max": max(vals), "min": min(vals)}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
