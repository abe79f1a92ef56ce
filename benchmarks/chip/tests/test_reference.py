"""The dense block: its plain float32 reference against the program's own
forward, and its weights, sites and operations held to what they were.

At both configurations' ``smoke()`` sizes in float32, with the benchmark's
seeded weights, the reference's logits at every position match
``models.transformer.forward``: the same block, computed independently.
"""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
import weights as W

#: the block a config file that names none runs, found as the harness
#: finds it
dense = harness.Spec(tiny.ROOT).block({})


def _smoke(arch):
    from repro.configs import get_config

    m = get_config(arch).smoke()
    cfg = {k: getattr(m, k) for k in harness.MODEL_KEYS}
    cfg.update(name=m.name, arch=arch, head_dim=m.head_dim_, norm_eps=1e-6,
               n_layers=2)
    return cfg, dataclasses.replace(m, n_layers=2)


@pytest.mark.parametrize("arch", ["musicgen-large", "phi3-mini-3.8b"])
def test_reference_matches_program_forward(arch):
    from repro.models import transformer as T

    cfg, model_cfg = _smoke(arch)
    assert cfg["dtype"] == "float32"
    seed, n, s = 2 ** 33 + 5, 3, 24
    params = dense.program_params(cfg, seed)
    key = W.base_key(seed)
    if cfg["frontend"] == "tokens":
        inputs = jax.random.randint(key, (n, s), 0, cfg["vocab"])
        batch = {"tokens": inputs}
    else:
        inputs = jax.random.normal(key, (n, s, cfg["d_model"]))
        batch = {"embeds": inputs}
    got, _, _ = T.forward(params, model_cfg, batch)
    want = dense.logits(cfg, seed, inputs, range(s), rows=2)
    got = np.asarray(got)
    assert got.shape == want.shape == (n, s, cfg["vocab"])
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_layer_weights_match_the_stacked_ones():
    """The reference's one-layer weights are the served stack's layer."""
    cfg, _ = _smoke("phi3-mini-3.8b")
    cfg = dict(cfg, dtype="bfloat16")
    stacked = dense.program_params(cfg, 7)["blocks"][0]
    for i in range(cfg["n_layers"]):
        one = dense.reference_layer(cfg, 7, i)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b[i], np.float32))


def test_fp8_control_departs_from_float32():
    cfg, _ = _smoke("phi3-mini-3.8b")
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 128)) / 16
    exact = dense.matmul(x, w, None)
    low = dense.matmul(x, w, "fp8")
    rel = float(jnp.abs(low - exact).max() / jnp.abs(exact).max())
    assert 1e-3 < rel < 0.2


# What the dense block gave before a config file could name its block: the
# sha256 of the served weights' bytes, leaf by leaf (both tiny configs have
# the same sizes), and the sites and operations at the cells' shapes.
PARAMS_SHA256 = {
    7: "c664977ea7a8fd3de057d6d9f9a735e1576307a6d3efcba718ebe2bbbbaa2a0b",
    2 ** 33 + 5:
        "deed032f826eadcf948aa6f05fa5940c9d0547b28fc3f759d42b14ac030b078f"}
SITES = {
    ("musicgen-large", 16): [
        ((16, 2048, 2048), 48, 2), ((16, 2048, 2048), 96, 2),
        ((16, 2048, 2048), 48, 2), ((16, 2048, 8192), 96, 2),
        ((16, 8192, 2048), 48, 2), ((16, 2048, 2048), 1, 4)],
    ("phi3-mini-3.8b", 8192): [
        ((8192, 3072, 3072), 32, 2), ((8192, 3072, 3072), 64, 2),
        ((8192, 3072, 3072), 32, 2), ((8192, 3072, 8192), 64, 2),
        ((8192, 8192, 3072), 32, 2), ((8192, 3072, 32064), 1, 4)],
    ("phi3-mini-3.8b", 8): [
        ((8, 3072, 3072), 32, 2), ((8, 3072, 3072), 64, 2),
        ((8, 3072, 3072), 32, 2), ((8, 3072, 8192), 64, 2),
        ((8, 8192, 3072), 32, 2), ((8, 3072, 32064), 1, 4)]}
#: (config, batch, new tokens, kv_len): operations of the step
FLOPS = {
    ("musicgen-large", 16, 32, 32): 3306051076096.0,
    ("musicgen-large", 16, 1, 33): 103421050880.0,
    ("musicgen-large", 16, 1, 511): 106428366848.0,
    ("phi3-mini-3.8b", 8, 1024, 1024): 62636729303040.0,
    ("phi3-mini-3.8b", 8, 1, 1025): 62782439424.0,
    ("phi3-mini-3.8b", 8, 1, 1039): 62826479616.0}


def _config(name):
    with open(os.path.join(tiny.CHIP, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", sorted(PARAMS_SHA256))
@pytest.mark.parametrize("config", ["tiny-tokens", "tiny-embeds"])
def test_dense_weights_do_not_move(config, seed):
    digest = hashlib.sha256()
    cfg = dict(tiny.CONFIGS[config], name=config)
    for leaf in jax.tree.leaves(dense.program_params(cfg, seed)):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == PARAMS_SHA256[seed]


@pytest.mark.parametrize("config,m", sorted(SITES))
def test_dense_sites_do_not_move(config, m):
    assert dense.sites(_config(config), m) == SITES[(config, m)]


@pytest.mark.parametrize("step", sorted(FLOPS))
def test_dense_model_flops_do_not_move(step):
    config, batch, new, kv_len = step
    assert dense.model_flops(_config(config), batch, new, kv_len) == \
        FLOPS[step]
