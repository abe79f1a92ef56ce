"""Tuned-schedule serving: registry v2 records, migration, consume path.

Covers the harvest→persist→consume loop the serve launcher runs: v2 record
round-trips (put → save → load → merge → block_for parity), legacy v1 table
migration, and `tuned_einsum` fallback parity — the tuned path must be
numerically interchangeable with plain `jnp.einsum` whether the registry
hits, misses, or is absent.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import LoopNest, ScheduleRegistry, matmul_benchmark
from repro.core.registry import ANY, current_hardware
from repro.kernels import ops as K


def _nest(m=128, k=128, n=128):
    nest = LoopNest(matmul_benchmark(m, k, n))
    nest.split(0, 32)
    return nest


# ---------------------------------------------------------------------------
# Registry v2: round-trip / merge / migration / save robustness
# ---------------------------------------------------------------------------


def test_v2_roundtrip_save_load_merge_block_for(tmp_path):
    path = str(tmp_path / "reg.json")
    reg = ScheduleRegistry()
    meas = {"gflops": 111.0, "best_s": 1e-3, "spread": 0.02, "repeats": 3,
            "escalations": 0, "noisy": False, "worker": 0}
    assert reg.put("mm", (128, 128, 128), 111.0, ["split_32"], _nest(),
                   backend="tpu", measurement=meas,
                   provenance={"policy": "search"})
    reg.save(path)

    doc = json.loads(open(path).read())
    assert doc["version"] == 2
    (key,) = doc["entries"].keys()
    sk, backend, hardware = ScheduleRegistry.split_key(key)
    assert sk == "mm:128x128x128:float32"
    assert backend == "tpu" and hardware == current_hardware()

    loaded = ScheduleRegistry(path)
    e = loaded.get("mm", (128, 128, 128))
    assert e["gflops"] == 111.0
    assert e["measurement"]["spread"] == 0.02
    assert e["provenance"]["policy"] == "search"
    assert loaded.block_for("mm", (128, 128, 128), {"m": 8}) == e["block"]

    # merge: best-gflops-wins per record key, new keys adopted
    other = ScheduleRegistry()
    other.put("mm", (128, 128, 128), 999.0, ["better"], backend="tpu")
    other.put("mm", (64, 64, 64), 10.0, ["new"], backend="tpu")
    assert loaded.merge(other) == 2
    assert loaded.get("mm", (128, 128, 128))["gflops"] == 999.0
    worse = ScheduleRegistry()
    worse.put("mm", (128, 128, 128), 1.0, ["worse"], backend="tpu")
    assert loaded.merge(worse) == 0


def test_legacy_v1_table_migrates(tmp_path):
    path = tmp_path / "v1.json"
    path.write_text(json.dumps({
        "mm:96x64x64:float32": {"gflops": 50.0, "actions": ["a"],
                                "block": {"m": 32, "k": 64, "n": 64}},
    }))
    reg = ScheduleRegistry(str(path))
    e = reg.get("mm", (96, 64, 64))
    assert e is not None and e["gflops"] == 50.0
    # migrated records are wildcard: any backend/hardware matches
    assert reg.get("mm", (96, 64, 64), backend="tpu",
                   hardware=current_hardware(), exact=True) is not None
    key = reg.record_key("mm:96x64x64:float32", ANY, ANY)
    assert key in dict(reg.entries())


def test_save_without_dirname_and_atomicity(tmp_path, monkeypatch):
    # regression: path with no directory component raised FileNotFoundError
    monkeypatch.chdir(tmp_path)
    reg = ScheduleRegistry()
    reg.put("mm", (64, 64, 64), 10.0, ["a"])
    reg.save("bare_name.json")
    assert ScheduleRegistry("bare_name.json").get("mm", (64, 64, 64))


def test_put_degrades_to_actions_only_on_lowering_failure():
    class Broken:
        loops = property(lambda self: (_ for _ in ()).throw(RuntimeError("x")))

    reg = ScheduleRegistry()
    with pytest.warns(UserWarning, match="actions-only"):
        assert reg.put("mm", (32, 32, 32), 5.0, ["a"], Broken())
    e = reg.get("mm", (32, 32, 32))
    assert e["actions"] == ["a"] and "block" not in e


def test_piped_hardware_string_survives_roundtrip(tmp_path):
    # regression: record keys are |-joined, and real device-kind strings
    # contain | — pre-escaping, reload shifted the key fields
    path = str(tmp_path / "reg.json")
    hw = "TPU v5 lite|2x2|podslice"
    reg = ScheduleRegistry()
    assert reg.put("mm", (64, 64, 64), 42.0, ["a"], _nest(64, 64, 64),
                   backend="tpu", hardware=hw)
    reg.save(path)
    raw_key = next(iter(json.loads(open(path).read())["entries"]))
    assert raw_key.count("|") == 2  # component pipes are escaped on disk
    sk, backend, hardware = ScheduleRegistry.split_key(raw_key)
    assert (sk, backend, hardware) == ("mm:64x64x64:float32", "tpu", hw)
    loaded = ScheduleRegistry(path)
    e = loaded.get("mm", (64, 64, 64), hardware=hw, exact=True)
    assert e is not None and e["hardware"] == hw
    # escape is involutive through merge too (re-keying uses record_key)
    other = ScheduleRegistry()
    assert other.merge(loaded) == 1
    assert other.get("mm", (64, 64, 64), hardware=hw, exact=True) is not None


def test_unparseable_record_keys_dropped_with_warning(tmp_path):
    path = tmp_path / "reg.json"
    good = ScheduleRegistry.record_key("mm:64x64x64:float32", "tpu", "hw")
    path.write_text(json.dumps({
        "version": 2,
        "entries": {
            good: {"gflops": 1.0, "actions": []},
            # a pre-escaping key written by an old writer with a piped
            # hardware string: 4 fields, unrecoverable
            "mm:8x8x8:float32|tpu|TPU|v5e": {"gflops": 2.0, "actions": []},
        },
    }))
    with pytest.warns(UserWarning, match="un-parseable"):
        reg = ScheduleRegistry(str(path))
    assert len(reg) == 1
    assert reg.get("mm", (64, 64, 64)) is not None
    with pytest.raises(ValueError, match="un-parseable"):
        ScheduleRegistry.split_key("a|b|c|d")


def test_specificity_ranked_lookup():
    reg = ScheduleRegistry()
    hw = current_hardware()
    reg.put("mm", (64, 64, 64), 100.0, ["wild"], backend=ANY, hardware=ANY)
    reg.put("mm", (64, 64, 64), 50.0, ["here"], backend="tpu", hardware=hw)
    # exact (backend, hardware) match beats a faster wildcard
    e = reg.get("mm", (64, 64, 64), backend="tpu", hardware=hw)
    assert e["actions"] == ["here"]
    # with no constraint, best gflops wins
    assert reg.get("mm", (64, 64, 64))["actions"] == ["wild"]


# ---------------------------------------------------------------------------
# Consume path: tuned_einsum parity + counters
# ---------------------------------------------------------------------------


def _tuned_registry(m, k, n, dtype="float32"):
    reg = ScheduleRegistry()
    nest = LoopNest(matmul_benchmark(m, k, n))
    reg.put("mm", (m, k, n), 100.0, [], nest, dtype=dtype, backend="tpu")
    return reg


def test_tuned_einsum_hit_routes_and_matches(tmp_path):
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 24, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 96))
    reg = _tuned_registry(4 * 24, 64, 96)
    K.reset_serving_stats()
    out = K.tuned_einsum("abk,kn->abn", a, b, registry=reg,
                         pallas="interpret")
    ref = jnp.einsum("abk,kn->abn", a, b)
    np.testing.assert_allclose(out, ref, atol=1e-5)
    stats = K.serving_stats(reset=True)
    assert stats["hits"] == 1 and stats["routed"] == 1
    assert "mm:96x64x96:float32" in stats["per_key"]


def test_tuned_einsum_cold_miss_falls_back():
    a = jnp.ones((7, 13))
    b = jnp.ones((13, 5))
    reg = ScheduleRegistry()  # empty: every lookup misses
    K.reset_serving_stats()
    out = K.tuned_einsum("ak,kn->an", a, b, registry=reg)
    np.testing.assert_allclose(out, jnp.einsum("ak,kn->an", a, b))
    stats = K.serving_stats(reset=True)
    assert stats["hits"] == 0 and stats["misses"] == 1


def test_tuned_einsum_transposed_rhs_logits_form():
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 24, 64))
    t = jax.random.normal(jax.random.PRNGKey(3), (256, 64))
    reg = _tuned_registry(4 * 24, 64, 256)
    out = K.tuned_einsum("bsd,vd->bsv", x, t, registry=reg,
                         pallas="interpret",
                         preferred_element_type=jnp.float32)
    ref = jnp.einsum("bsd,vd->bsv", x, t,
                     preferred_element_type=jnp.float32)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, ref, atol=1e-4)
    K.reset_serving_stats()


def test_tuned_einsum_ellipsis_and_explicit_forms_share_key():
    # regression: "...k,kn->...n" (the docstring's own example) was
    # rejected outright and silently cold-fell-back
    a = jax.random.normal(jax.random.PRNGKey(4), (4, 24, 64))
    b = jax.random.normal(jax.random.PRNGKey(5), (64, 96))
    reg = _tuned_registry(4 * 24, 64, 96)
    K.reset_serving_stats()
    out_ell = K.tuned_einsum("...k,kn->...n", a, b, registry=reg,
                             pallas="interpret")
    out_exp = K.tuned_einsum("abk,kn->abn", a, b, registry=reg,
                             pallas="interpret")
    ref = jnp.einsum("abk,kn->abn", a, b)
    np.testing.assert_allclose(out_ell, ref, atol=1e-5)
    np.testing.assert_allclose(out_exp, ref, atol=1e-5)
    stats = K.serving_stats(reset=True)
    # both spellings resolve to the SAME workload key: one key, two hits
    assert list(stats["per_key"]) == ["mm:96x64x96:float32"]
    assert stats["hits"] == 2 and stats["routed"] == 2
    # transposed-weight ellipsis form parses too
    t = jax.random.normal(jax.random.PRNGKey(6), (96, 64))
    reg2 = _tuned_registry(4 * 24, 64, 96)
    out_t = K.tuned_einsum("...k,nk->...n", a, t, registry=reg2,
                           pallas="interpret")
    np.testing.assert_allclose(out_t, jnp.einsum("...k,nk->...n", a, t),
                               atol=1e-4)
    K.reset_serving_stats()


def test_parse_matmul_spec_ellipsis_edge_cases():
    P = K._parse_matmul_spec
    # ellipsis folds batch dims into m, same as explicit letters
    assert P("...k,kn->...n", (4, 24, 64), (64, 96)) == \
        P("abk,kn->abn", (4, 24, 64), (64, 96)) == (96, 64, 96, False)
    # 2-D lhs: the ellipsis absorbs one dim
    assert P("...k,kn->...n", (8, 64), (64, 32)) == (8, 64, 32, False)
    # malformed/unsupported ellipsis placements stay rejected
    assert P("...k,kn->n", (4, 24, 64), (64, 96)) is None  # out lacks ...
    assert P("ak,kn->...n", (4, 64), (64, 96)) is None     # lhs lacks ...
    assert P("...k,...n->...n", (4, 64), (64, 96)) is None  # rhs ellipsis
    assert P("...,kn->...", (4, 64), (64, 96)) is None     # no contracted dim
    assert P("...abk,kn->...abn", (64,), (64, 96)) is None  # too few dims


def test_tuned_einsum_non_matmul_spec_falls_back():
    a = jnp.ones((3, 4, 5))
    b = jnp.ones((4, 5))
    reg = _tuned_registry(12, 5, 4)
    K.reset_serving_stats()
    # two contracted indices: not matmul-shaped, no counters touched
    out = K.tuned_einsum("abk,bk->a", a, b, registry=reg)
    np.testing.assert_allclose(out, jnp.einsum("abk,bk->a", a, b))
    stats = K.serving_stats(reset=True)
    assert stats["hits"] == 0 and stats["misses"] == 0


def test_serving_context_activates_dense(tmp_path):
    from repro.models import layers as L

    x = jax.random.normal(jax.random.PRNGKey(4), (4, 8, 64))
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 96))
    ref = x @ w
    reg = _tuned_registry(4 * 8, 64, 96)
    K.reset_serving_stats()
    assert K.serving_registry() is None
    with K.serving(reg):
        assert K.serving_registry() is reg
        out = L.dense(x, w)  # CPU: hit counted, XLA lowering kept
    assert K.serving_registry() is None
    np.testing.assert_allclose(out, ref, atol=1e-5)
    stats = K.serving_stats(reset=True)
    assert stats["hits"] == 1


def _blocks_registry(path, blocks, order="mn"):
    """A table giving each ``mm`` key of ``blocks`` its (bm, bk, bn)."""
    grid = ["m", "n", "k"] if order == "mn" else ["n", "m", "k"]
    path.write_text(json.dumps({
        key: {"gflops": 1.0, "actions": [], "grid_order": grid,
              "block": dict(zip("mkn", block))}
        for key, block in blocks.items()}))
    return ScheduleRegistry(str(path))


@pytest.mark.parametrize("bk,bn,stacked", [
    (32, 32, True),      # divides K = 64 and N = 96
    (48, 32, False),     # does not divide K: the stack would be padded
    (32, 40, False),     # nor N
])
def test_tuned_einsum_stacked_weight_takes_the_stack_only_when_it_divides(
        tmp_path, bk, bn, stacked):
    """A weight given as (stack, layer index): a block that divides the
    layer's K and N reads the stack in the kernel (counted ``stacked``);
    any other slices the layer first, as a scanned weight is."""
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 64))
    stack = jax.random.normal(jax.random.PRNGKey(7), (3, 64, 96))
    reg = _blocks_registry(tmp_path / "reg.json",
                           {"mm:16x64x96:float32": (8, bk, bn)})
    K.reset_serving_stats()
    for li in range(3):
        out = jax.jit(lambda x, s, i: K.tuned_einsum(
            "abk,kn->abn", x, s, layer=i, registry=reg,
            pallas="interpret"))(x, stack, jnp.int32(li))
        ref = K.tuned_einsum("abk,kn->abn", x, stack[li], registry=reg,
                             pallas="interpret")
        np.testing.assert_array_equal(out, ref)
    stats = K.serving_stats(reset=True)
    slot = stats["per_key"]["mm:16x64x96:float32"]
    assert slot["routed"] == 6
    assert slot["stacked"] == (3 if stacked else 0) == stats["stacked"]


def _serve_tiny(params, cfg, reg, tokens):
    """(prefill's last logits, one decode step's logits) through the
    served steps, with serving_stats of their traces."""
    from repro.models import steps as S

    K.reset_serving_stats()
    last, caches, clen = jax.jit(S.make_prefill_step(
        cfg, max_len=16, registry=reg))(params, {"tokens": tokens})
    nxt = jnp.argmax(last, -1).astype(jnp.int32)
    _, logits, _ = jax.jit(S.make_decode_step(cfg, registry=reg))(
        params, {"tokens": nxt[:, None]}, caches, clen)
    return last, logits, K.serving_stats(reset=True)


@pytest.mark.parametrize("order", ["mn", "nm"])
def test_served_steps_read_stacked_weights_bitwise(tmp_path, monkeypatch,
                                                   order):
    """A tiny dense model, every dense site routed through the interpreted
    kernel: the prefill and decode logits with each layer's weights read
    out of the stacks equal those of the scan that slices them, and every
    in-scan key is served by the stack (the LM head is not)."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = dataclasses.replace(get_config("phi3-mini-3.8b").smoke(),
                              n_layers=3)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    # the keys the steps look up, from a trace with an empty table
    *_, cold = _serve_tiny(params, cfg, ScheduleRegistry(), tokens)
    reg = _blocks_registry(tmp_path / "reg.json",
                           {key: (8, 32, 32) for key in cold["per_key"]},
                           order)
    monkeypatch.setattr(K, "_route_pallas", lambda pallas: (True, True))
    last, logits, stats = _serve_tiny(params, cfg, reg, tokens)
    with monkeypatch.context() as mp:
        mp.setattr(T, "_hold_dense_weights", lambda p: (p, {}))
        ref_last, ref_logits, ref = _serve_tiny(params, cfg, reg, tokens)
    np.testing.assert_array_equal(last, ref_last)
    np.testing.assert_array_equal(logits, ref_logits)
    assert ref["stacked"] == 0 and ref["routed"] == stats["routed"] > 0
    assert stats["misses"] == 0
    for key, slot in stats["per_key"].items():
        head = key.split(":")[1].endswith(f"x{cfg.vocab}")
        assert slot["routed"] > 0
        assert slot["stacked"] == (0 if head else slot["routed"]), key


# ---------------------------------------------------------------------------
# Harvest → tune: the offline pre-pass
# ---------------------------------------------------------------------------


def test_harvest_model_flop_shares():
    from repro.configs import get_config
    from repro.launch.tune import harvest_model

    cfg = get_config("musicgen-large").smoke()
    recs = harvest_model(cfg, batch=2, prompt_len=8, max_len=16,
                         kinds=("decode",))
    assert recs
    assert abs(sum(r["flop_share"] for r in recs) - 1.0) < 1e-6
    assert all(r["m"] > 0 and r["k"] > 0 and r["n"] > 0 and r["count"] >= 1
               for r in recs)
    # sorted by executed FLOPs, heaviest first
    flops = [r["flops"] for r in recs]
    assert flops == sorted(flops, reverse=True)


def test_tune_model_persists_consumable_entries(tmp_path):
    from repro.configs import get_config
    from repro.launch.tune import tune_model

    cfg = get_config("musicgen-large").smoke()
    reg = ScheduleRegistry()
    report = tune_model(cfg, registry=reg, smoke=False, budget_s=0.2,
                        eval_budget=6, max_contractions=2, batch=2,
                        prompt_len=8, max_len=16, kinds=("decode",))
    assert report["n_tuned"] == 2 and len(reg) == 2
    # harvested keys are the ones the consume path looks up
    top = report["contractions"][0]
    e = reg.get("mm", (top["m"], top["k"], top["n"]), dtype=top["dtype"])
    assert e is not None and "block" in e
    assert e["provenance"]["policy"] == "search"
    path = str(tmp_path / "tuned.json")
    reg.save(path)
    assert len(ScheduleRegistry(path)) == 2


@pytest.mark.slow
def test_serve_smoke_with_registry_hits():
    """End-to-end: tune a smoke config on a tiny budget, serve with the
    registry enabled, assert the traced steps hit the table."""
    from repro.configs import get_config
    from repro.core.registry import ScheduleRegistry
    from repro.launch.serve import serve_once
    from repro.launch.tune import tune_model

    cfg = get_config("musicgen-large").smoke()
    reg = ScheduleRegistry()
    report = tune_model(cfg, registry=reg, smoke=False, budget_s=0.5,
                        eval_budget=10, batch=2, prompt_len=8, max_len=32,
                        kinds=("decode",))
    assert report["n_tuned"] > 0
    summary = serve_once(cfg, requests=4, batch=2, prompt_len=8, gen_len=4,
                         max_len=32, registry=reg)
    assert summary["requests"] == 4
    assert np.isfinite(summary["tokens_per_s"])
    assert summary["tokens_per_s"] > 0
    assert summary["registry"]["serving"]["hits"] > 0
