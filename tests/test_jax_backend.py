"""Compiled JAX backend: exact-semantics parity with the NumPy executor,
structure-cached compilation, the backend registry, and the
config -> checkpoint -> tuner backend round-trip (ISSUE 4)."""
import numpy as np
import pytest

from repro.core import (
    CompiledKernelCache,
    JaxJitBackend,
    LoopNest,
    LoopTuneEnv,
    LoopTuner,
    ScheduleCache,
    VecLoopTuneEnv,
    backend_name,
    conv2d_benchmark,
    execute_jax,
    execute_reference,
    make_backend,
    make_inputs,
    match_kernel_route,
    matmul_benchmark,
    reduction_benchmark,
    transpose_benchmark,
)
from repro.core.actions import apply_action, build_action_space
from repro.core.jax_backend import _group_slabs, _slab_plan
from repro.core.schedule_cache import LRUCache

ACTIONS = build_action_space()


def _apply_random_actions(nest, seq, max_loops=14):
    for a_idx in seq:
        if len(nest.loops) >= max_loops:
            break
        apply_action(nest, ACTIONS[a_idx % len(ACTIONS)])
    return nest


# ---------------------------------------------------------------------------
# Semantics parity (deterministic grid — fast, always runs)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bench", [
    matmul_benchmark(13, 7, 9),
    conv2d_benchmark(9, 11, 3, 2),
    reduction_benchmark(17, 23),
    transpose_benchmark(12, 19),
])
def test_jax_matches_reference(bench):
    rng = np.random.default_rng(42)
    arrays = make_inputs(bench, seed=0)
    ref = execute_reference(bench, arrays)
    for _ in range(3):
        nest = _apply_random_actions(
            LoopNest(bench), rng.integers(0, 10, size=8))
        out = execute_jax(nest, arrays, vec_cap=32)  # small cap: deep blocking
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_jax_matches_reference_default_cap():
    bench = matmul_benchmark(48, 32, 40)
    nest = LoopNest(bench)
    nest.split(0, 16)
    nest.split(2, 8)
    arrays = make_inputs(bench, seed=0)
    np.testing.assert_allclose(
        execute_jax(nest, arrays),
        execute_reference(bench, arrays), rtol=2e-4, atol=2e-4)


def test_slab_plan_covers_iteration_space():
    """The static plan enumerates exactly the blocked interpreter's slabs:
    compute volume sums to the contraction volume times reduce revisits."""
    bench = matmul_benchmark(10, 6, 14)
    nest = LoopNest(bench)
    nest.split(0, 4)  # non-dividing: exercises tail clamping
    plan = _slab_plan(nest.compute_loops, bench, vec_cap=16)
    vol = sum(np.prod([ext[it] for it in bench.iter_sizes]) for _, ext in plan)
    assert vol == 10 * 6 * 14
    # grouping preserves every slab
    groups = _group_slabs(plan, list(bench.iter_sizes))
    assert sum(len(offs) for _, offs in groups) == len(plan)


# ---------------------------------------------------------------------------
# Property test (hypothesis): any reachable schedule computes the reference
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - optional dep
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @st.composite
    def benchmarks(draw):
        kind = draw(st.sampled_from(["mm", "conv", "red", "tr"]))
        dim = st.integers(3, 24)
        if kind == "mm":
            return matmul_benchmark(draw(dim), draw(dim), draw(dim))
        if kind == "conv":
            return conv2d_benchmark(draw(dim), draw(dim),
                                    draw(st.integers(1, 3)),
                                    draw(st.integers(1, 3)))
        if kind == "red":
            return reduction_benchmark(draw(dim), draw(dim))
        return transpose_benchmark(draw(dim), draw(dim))

    @given(benchmarks(), st.lists(st.integers(0, 9), max_size=10))
    @settings(max_examples=15, deadline=None)
    def test_any_schedule_compiles_to_reference(bench, seq):
        """Mirror of tests/test_property.py::test_any_schedule_computes_reference
        for the compiled executor (each example pays one XLA compile, so the
        example budget is smaller; the deterministic grid above adds
        breadth)."""
        nest = _apply_random_actions(LoopNest(bench), seq)
        arrays = make_inputs(bench, seed=0)
        out = execute_jax(nest, arrays, vec_cap=32)
        ref = execute_reference(bench, arrays)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Pallas kernel route
# ---------------------------------------------------------------------------


def test_matmul_route_matches_reference():
    bench = matmul_benchmark(48, 40, 56)
    assert match_kernel_route(bench) == "matmul"
    nest = LoopNest(bench)
    nest.split(0, 16)
    arrays = make_inputs(bench, seed=0)
    out = execute_jax(nest, arrays, route="matmul")
    np.testing.assert_allclose(
        out, execute_reference(bench, arrays), rtol=2e-4, atol=2e-4)


def test_non_matmul_has_no_route():
    assert match_kernel_route(reduction_benchmark(8, 8)) is None
    assert match_kernel_route(conv2d_benchmark(6, 6, 2, 2)) is None
    with pytest.raises(ValueError):
        execute_jax(LoopNest(reduction_benchmark(8, 8)),
                    make_inputs(reduction_benchmark(8, 8)), route="matmul")


def test_pallas_on_routes_matmul_and_evaluates():
    be = JaxJitBackend(repeats=1, pallas="on")
    nest = LoopNest(matmul_benchmark(32, 32, 32))
    assert be._route(nest.contraction) == "matmul"
    assert be.evaluate(nest) > 0
    # the interpret-mode Pallas executable still computes the contraction
    out = be.execute(nest)
    ref = execute_reference(nest.contraction, make_inputs(nest.contraction))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Compile cache: one trace per structure_key
# ---------------------------------------------------------------------------


def test_evaluate_batch_compiles_each_structure_once():
    be = JaxJitBackend(repeats=1)
    bench = matmul_benchmark(16, 16, 16)
    a, b = LoopNest(bench), LoopNest(bench)
    c = LoopNest(bench)
    c.split(0, 4)
    assert a.structure_key() == b.structure_key()
    assert c.structure_key() != a.structure_key()
    be.evaluate_batch([a, b, c, a, c])
    assert be.compiles == 2  # one trace per distinct structure_key
    be.evaluate_batch([a, b, c])
    be.evaluate(c)
    assert be.compiles == 2  # re-timing only; nothing re-traces
    assert be.kernels.misses == 2
    assert be.kernels.hits >= 6


def test_compiled_cache_is_lru_bounded():
    be = JaxJitBackend(repeats=1, kernel_cache=CompiledKernelCache(capacity=2))
    bench = matmul_benchmark(16, 16, 16)
    nests = []
    for f in (2, 4, 8):
        n = LoopNest(bench)
        n.split(0, f)
        nests.append(n)
    for n in nests:
        be.evaluate(n)
    assert be.compiles == 3
    assert len(be.kernels) == 2  # coldest executable evicted, not cleared
    assert be.kernels.evictions == 1
    be.evaluate(nests[0])  # evicted: compiles again
    assert be.compiles == 4


def test_inputs_cache_lru_not_clear_all():
    """The clear-all-on-overflow pathology is gone: overflowing by one
    evicts exactly one contraction's operands."""
    from repro.core.cpu_backend import CPUMeasuredBackend

    be = CPUMeasuredBackend(repeats=1)
    be._inputs_cache.capacity = 4
    benches = [matmul_benchmark(8, 8, 8 + 8 * i) for i in range(5)]
    for b in benches:
        be._inputs(b)
    assert len(be._inputs_cache) == 4
    assert be._inputs_cache.evictions == 1
    assert benches[0].name not in be._inputs_cache  # oldest went
    assert benches[-1].name in be._inputs_cache


def test_lru_cache_generic_discipline():
    c = LRUCache(capacity=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1  # refreshes recency
    c.put("c", 3)
    assert "b" not in c and "a" in c and "c" in c
    assert c.evictions == 1
    assert isinstance(ScheduleCache(), LRUCache)
    assert isinstance(CompiledKernelCache(), LRUCache)


# ---------------------------------------------------------------------------
# Backend registry + threading
# ---------------------------------------------------------------------------


def test_make_backend_names():
    assert make_backend("numpy").name == "numpy"
    assert make_backend("cpu").name == "numpy"  # historical alias
    assert make_backend("tpu").name == "tpu"
    assert make_backend("jax").name == "jax"
    assert make_backend("auto").name in ("jax", "numpy")
    be = make_backend("tpu")
    assert make_backend(be) is be  # instance pass-through
    with pytest.raises(ValueError):
        make_backend("no-such-backend")
    with pytest.raises(ValueError):
        make_backend(be, repeats=2)  # kwargs can't apply to an instance


def test_env_accepts_backend_by_name():
    env = LoopTuneEnv([matmul_benchmark(16, 16, 16)], "tpu")
    assert env.backend_name == "tpu"
    venv = VecLoopTuneEnv([matmul_benchmark(16, 16, 16)], "tpu", 2)
    assert venv.backend_name == "tpu"


def test_with_backend_cache_sharing():
    env = LoopTuneEnv([matmul_benchmark(16, 16, 16)], "tpu")
    same = env.with_backend("tpu")
    assert same.backend is env.backend and same.cache is env.cache
    other = env.with_backend("numpy")
    assert other.backend_name == "numpy"
    assert other.cache is not env.cache  # fresh: no cross-backend poisoning


def test_vec_ensure_backend_mismatch_is_error():
    venv = VecLoopTuneEnv([matmul_benchmark(16, 16, 16)], "tpu", 2)
    with pytest.raises(ValueError, match="backend"):
        VecLoopTuneEnv.ensure(venv, 2, backend="numpy")
    assert VecLoopTuneEnv.ensure(venv, 2, backend="tpu") is venv


def test_jax_backend_reward_loop():
    """The compiled executor serves as the env reward source end to end."""
    env = LoopTuneEnv([matmul_benchmark(16, 16, 16)],
                      JaxJitBackend(repeats=1))
    env.reset(0)
    g0 = env.current_gflops
    assert g0 > 0
    obs, r, done, info = env.step(env.actions.index(
        next(a for a in env.actions if a.name == "split_4")))
    assert np.isfinite(r)
    assert env.backend.compiles >= 1


# ---------------------------------------------------------------------------
# Backend choice round-trips config -> checkpoint meta -> tuner
# ---------------------------------------------------------------------------

_TRAINERS = ["dqn", "apex_dqn", "ppo", "a2c", "impala"]


def _train_tiny(algo: str, backend: str):
    from repro.core.a2c import A2CConfig, train_a2c
    from repro.core.apex_dqn import ApexConfig, train_apex
    from repro.core.dqn import DQNConfig, train_dqn
    from repro.core.impala import ImpalaConfig, train_impala
    from repro.core.ppo import PPOConfig, train_ppo

    def env_factory(_):
        return LoopTuneEnv([matmul_benchmark(8, 8, 8)], "tpu", seed=0)

    common = dict(hidden=(16,), backend=backend)
    if algo == "dqn":
        return train_dqn(env_factory(0), 1,
                         DQNConfig(n_envs=2, warmup_steps=4, **common))
    if algo == "apex_dqn":
        return train_apex(env_factory, 1,
                          ApexConfig(n_actors=2, warmup_steps=4, **common))
    if algo == "ppo":
        return train_ppo(env_factory, 1,
                         PPOConfig(n_envs=2, rollout_len=4, **common))
    if algo == "a2c":
        return train_a2c(env_factory, 1,
                         A2CConfig(n_envs=2, rollout_len=4, **common))
    return train_impala(env_factory, 1,
                        ImpalaConfig(n_envs=2, rollout_len=4, **common))


@pytest.mark.parametrize("algo", _TRAINERS)
def test_backend_roundtrip_all_trainers(algo, tmp_path):
    """config.backend -> checkpoint meta -> LoopTuner.from_checkpoint."""
    res = _train_tiny(algo, backend="tpu")
    assert res.meta["backend"] == "tpu"
    path = str(tmp_path / f"{algo}.pkl")
    res.save(path)
    tuner = LoopTuner.from_checkpoint(path)
    assert tuner.backend_kind == "tpu"
    assert backend_name(tuner.backend) == "tpu"
    # explicit override still wins
    tuner2 = LoopTuner.from_checkpoint(path, backend="numpy")
    assert tuner2.backend_kind == "numpy"


def test_config_backend_overrides_env_factory():
    """A trainer config naming a backend rebuilds the rollout fleet on it
    (fresh cache — rewards from another executor would be meaningless)."""
    res = _train_tiny("a2c", backend="numpy")
    assert res.meta["backend"] == "numpy"


def test_bench_backend_smoke(tmp_path, monkeypatch):
    """CI quick-mode smoke of the backend benchmark (artifacts to tmp).

    Correctness (max|err| <= 1e-3, asserted inside run()) is deterministic;
    the wall-clock ratio is only sanity-checked (> 1x) because a loaded
    shared runner can squeeze timings — the real >= 5x acceptance number is
    measured by ``python -m benchmarks.run --only backend --full`` and
    committed in results/bench_backend.json (41x locally)."""
    bench_mod = pytest.importorskip("benchmarks.bench_backend")
    import benchmarks.common as common

    monkeypatch.setattr(common, "RESULTS", tmp_path)
    result = bench_mod.run(n_benchmarks=2, per_bench=2, repeats=1,
                           out_name="bench_backend_ci")
    assert (tmp_path / "bench_backend_ci.json").exists()
    assert result["speedup_jax_over_numpy"] > 1.0
    for entry in result["backends"].values():
        assert entry["max_abs_error"] <= 1e-3


def test_meta_none_backend_uses_env(tmp_path):
    from repro.core.a2c import A2CConfig, train_a2c

    res = train_a2c(
        lambda _: LoopTuneEnv([matmul_benchmark(8, 8, 8)], "tpu", seed=0),
        1, A2CConfig(hidden=(16,), n_envs=2, rollout_len=4))
    assert res.meta["backend"] == "tpu"  # recorded from the env's executor


def test_jax_pool_refused_on_tpu(monkeypatch):
    """One process per chip: pool workers would each need the TPU the
    parent already holds, so the jax backend refuses the pool there."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="measure='inproc'"):
        make_backend("jax", measure="pool", pool_workers=2)
    with pytest.raises(ValueError, match="one process"):
        make_backend("auto", measure="pool")
