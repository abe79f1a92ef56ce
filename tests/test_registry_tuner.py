"""Schedule registry + tuner integration (paper's 'tunes in seconds' path)."""
import json
import os

import numpy as np
import pytest

from repro.core import (
    LoopTuner,
    LoopNest,
    ScheduleRegistry,
    matmul_benchmark,
    schedule_to_blockspec,
)


def test_registry_roundtrip(tmp_path):
    path = str(tmp_path / "reg.json")
    reg = ScheduleRegistry(path)
    nest = LoopNest(matmul_benchmark(128, 128, 128))
    nest.split(0, 32)
    reg.put("mm", (128, 128, 128), 1234.5, ["split_32"], nest)
    reg.save()
    reg2 = ScheduleRegistry(path)
    e = reg2.get("mm", (128, 128, 128))
    assert e["gflops"] == 1234.5
    assert e["actions"] == ["split_32"]
    assert "block" in e and "grid_order" in e


def test_registry_keeps_best(tmp_path):
    reg = ScheduleRegistry()
    reg.put("mm", (64, 64, 64), 100.0, ["a"])
    reg.put("mm", (64, 64, 64), 50.0, ["b"])   # worse: ignored
    reg.put("mm", (64, 64, 64), 200.0, ["c"])  # better: replaces
    assert reg.get("mm", (64, 64, 64))["actions"] == ["c"]


def test_schedule_to_blockspec_resident_suffix():
    nest = LoopNest(matmul_benchmark(256, 256, 256))
    block, grid = schedule_to_blockspec(nest)
    # everything fits VMEM -> whole dims resident, grid order covers all iters
    assert block == {"m": 256, "k": 256, "n": 256}
    assert set(grid) == {"m", "k", "n"}


def test_tuner_search_policy_improves():
    tuner = LoopTuner(policy="search", backend="tpu", search_budget_s=2.0)
    e = tuner.tune_matmul(128, 128, 256)
    assert e["gflops"] >= e["base_gflops"]
    assert e["tune_time_s"] < 30
    assert len(tuner.registry) == 1


def test_tuner_default_policy_records_untuned():
    tuner = LoopTuner(policy="default", backend="tpu")
    e = tuner.tune_matmul(64, 64, 64)
    assert e["gflops"] == pytest.approx(e["base_gflops"])


def test_policy_checkpoint_tuner(tmp_path):
    """A (briefly) trained policy drives the tuner end-to-end."""
    from repro.core import LoopTuneEnv
    from repro.core.actions import TPU_SPLITS, build_action_space
    from repro.core.cost_model import TPUAnalyticalBackend
    from repro.core.dqn import DQNConfig, train_dqn

    env = LoopTuneEnv([matmul_benchmark(96, 96, 96)],
                      TPUAnalyticalBackend(),
                      actions=build_action_space(TPU_SPLITS), seed=0)
    res = train_dqn(env, n_iterations=3,
                    cfg=DQNConfig(hidden=(32,), warmup_steps=10))
    path = os.path.join(tmp_path, "p.pkl")
    res.save(path)
    tuner = LoopTuner.from_checkpoint(path, backend="tpu")
    e = tuner.tune_matmul(96, 96, 96)
    assert e["gflops"] > 0 and e["tune_time_s"] < 10


def test_current_hardware_raises_when_device_query_fails(monkeypatch):
    """A failed device query must not stamp a CPU descriptor: the chip would
    never look those records up."""
    import jax

    from repro.core import registry as R

    def broken():
        raise RuntimeError("no backend")

    monkeypatch.setattr(R, "_HARDWARE", None)
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="no backend"):
        R.current_hardware()
