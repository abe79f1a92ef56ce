"""The harness is led by data: cells, traffic mixes and metrics are files
found by name, and a run without a chip ends with no result."""
import dataclasses
import filecmp
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import harness
import tiny

ROOT = tiny.ROOT
RUN = os.path.join("benchmarks", "chip", "run.py")


def _no_result(stdout):
    return not any(line.startswith("{") for line in stdout.splitlines())


def _cli(args, cwd=ROOT, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, RUN] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_added_files_are_found_by_name(tmp_path):
    """A throwaway config, traffic mix, limits and metric reader, added
    beside the benchmark's own files, make a cell that runs; no existing
    file is touched."""
    root = tiny.make_root(str(tmp_path), extra_metric="tiny_steps")
    data = os.path.join(root, harness.DATA)
    with open(os.path.join(data, "metrics", "tiny_steps.py"), "w") as f:
        f.write("def read(run):\n"
                "    return float(sum(s['count'] for s in run.steps.values()))\n")
    run = harness.Run(root, "tiny-tokens.serve", 3, 1.0, True,
                      time.perf_counter(), require_chip=False)
    out = run.execute()
    assert out["metrics"]["tiny_steps"]["value"] > 0
    assert out["device"]["window_s"] > 0 and out["device"]["busy_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    cmp = filecmp.dircmp(tiny.CHIP, data, ignore=["state", "__pycache__",
                                                  "tests"])
    assert cmp.diff_files == [] and cmp.left_only == []


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """A throwaway root with a block module added: the zoo's MoE FFN on
    every layer, with its own top-k reference (``tests/data``)."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("moe")))
    shutil.copy(os.path.join(tiny.HERE, "data", "moe_topk.py"),
                os.path.join(root, harness.DATA, "reference", "moe_topk.py"))
    return root


@pytest.mark.parametrize("fault", [None, "state", "token"])
def test_added_block_is_found_by_name(moe_root, monkeypatch, fault):
    """A config file that names an added block, and sets the program's
    ``moe``, makes a cell that serves it and is judged by that block's own
    reference: correct when sound, not correct with a decode fault.  No
    existing file is touched."""
    run = harness.Run(moe_root, "tiny-moe.serve", 2 ** 31 + 3, 1.0, False,
                      time.perf_counter(), require_chip=False)
    if fault:
        tiny.break_decode(monkeypatch, fault)
    out = run.execute()
    assert os.path.basename(run.block.__file__) == "moe_topk.py"
    assert out["correct"] is (fault is None), out["compared"]
    cmp = filecmp.dircmp(tiny.CHIP, os.path.join(moe_root, harness.DATA),
                         ignore=["state", "__pycache__", "tests"])
    assert cmp.diff_files == [] and cmp.left_only == []
    assert cmp.subdirs["reference"].right_only == ["moe_topk.py"]


def test_config_file_sizes_the_program(moe_root):
    """Every field the file sets reaches the program, nested ones too, and
    a key that is no field fails the run, naming it."""
    from repro.configs import LayerSpec, get_config
    from repro.models import transformer as T

    spec = harness.Spec(moe_root)
    cfg = spec.config("tiny-moe")
    model_cfg = harness.model_config(cfg)
    assert get_config("olmoe-1b-7b").moe.n_experts == 64
    assert (model_cfg.moe.n_experts, model_cfg.moe.top_k,
            model_cfg.moe.d_ff_expert, model_cfg.moe.capacity_factor) == \
        (4, 2, 32, 2.0)
    assert model_cfg.qk_norm and model_cfg.d_model == cfg["d_model"]
    want = jax.eval_shape(lambda: T.init_params(model_cfg,
                                                 jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: spec.block(cfg).program_params(cfg, 1))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(got) == jax.tree.leaves(want)
    period = harness.model_config(dict(cfg, period=[
        {"mixer": "attn", "ffn": "moe"}, {"mixer": "attn", "ffn": "dense",
                                          "window": None}])).period
    assert period == (LayerSpec("attn", "moe"), LayerSpec("attn", "dense"))
    for bad, named in ((dict(cfg, n_expert=8), "n_expert"),
                       (dict(cfg, moe=dict(cfg["moe"], n_expert=8)),
                        "n_expert"),
                       ({k: v for k, v in cfg.items() if k != "d_ff"},
                        "d_ff")):
        with pytest.raises(harness.SpecError, match=named):
            harness.model_config(bad)
    with pytest.raises(harness.SpecError, match="no_such_block"):
        spec.block(dict(cfg, block="no_such_block"))


@pytest.mark.parametrize("config", ["musicgen-large", "phi3-mini-3.8b"])
def test_benchmark_configs_size_the_program_as_before(config):
    """The committed configurations set only the fields they always set,
    and run the dense block."""
    from repro.configs import get_config

    spec = harness.Spec(ROOT)
    cfg = spec.config(config)
    before = dataclasses.replace(get_config(cfg["arch"]), name=cfg["name"],
                                 **{k: cfg[k] for k in harness.MODEL_KEYS})
    assert harness.model_config(cfg) == before
    assert os.path.basename(spec.block(cfg).__file__) == "dense.py"


def test_traffic_kind_selects_the_loop(tmp_path):
    root = tiny.make_root(str(tmp_path))
    run = harness.Run(root, "tiny-embeds.serve", 2 ** 31 + 9, 1.0, False,
                      time.perf_counter(), require_chip=False)
    out = run.execute()
    names = {m["name"] for m in run.spec.end_to_end("tiny-embeds.serve")}
    assert set(out["metrics"]) == names
    assert out["correct"] is True and out["attempted"] > 0


def test_served_table_is_repeatable(tmp_path):
    """Two checkouts of the same code tune the same served table: the
    serve cells' tune is rewarded by the program's cost model, not by
    timings."""
    root = tiny.make_root(str(tmp_path))
    spec = harness.Spec(root)
    wl = spec.workload("tiny-tokens.serve")
    traffic = spec.traffic(wl["traffic"])
    model_cfg = harness.model_config(spec.config(wl["config"]))
    tables = []
    for side in ("parent", "change"):
        path = str(tmp_path / side / "registry.json")
        os.makedirs(os.path.dirname(path))
        harness.tune_for_serving(model_cfg, traffic, path)
        with open(path) as f:
            tables.append(json.load(f))
    assert tables[0] == tables[1] and tables[0]["entries"]


def test_unknown_workload_fails():
    r = _cli(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1"])
    assert r.returncode != 0 and "unknown workload" in r.stderr
    assert _no_result(r.stdout)


def test_cpu_run_exits_nonzero_naming_the_platform():
    r = _cli(["--workload", "musicgen-large.decode", "--seed", "1",
              "--seconds", "1", "--trace", "0"])
    assert r.returncode != 0 and "'cpu'" in r.stderr
    assert _no_result(r.stdout)


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files has
    no program to measure."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.CHIP, os.path.join(tmp_path, harness.DATA),
                    ignore=shutil.ignore_patterns("state", "__pycache__"))
    r = _cli(["--workload", "musicgen-large.decode", "--seed", "1",
              "--seconds", "1", "--trace", "0"], cwd=str(tmp_path))
    assert r.returncode != 0 and _no_result(r.stdout)
    assert "the program is not beside the benchmark" in r.stderr
