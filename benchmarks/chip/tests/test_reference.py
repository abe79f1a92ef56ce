"""The plain float32 reference against the program's own forward.

At both configurations' ``smoke()`` sizes in float32, with the benchmark's
seeded weights, the reference's logits at every position match
``models.transformer.forward``: the same block, computed independently.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import weights as W
from reference import dense


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
    params = W.program_params(cfg, seed)
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
    stacked = W.program_params(cfg, 7)["blocks"][0]
    for i in range(cfg["n_layers"]):
        one = W.reference_layer(cfg, 7, i)
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
