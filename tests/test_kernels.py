"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode).

Each Pallas kernel gets (a) hypothesis-driven random shape/block sweeps and
(b) fixed parametrized cases covering the alignment edge cases (tails,
GQA groups, windows, softcaps).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, don't break collection
from hypothesis import given, settings, strategies as st

from repro.kernels import (
    flash_attention,
    mamba_scan,
    rwkv6_chunk_scan,
    set_registry,
    tuned_matmul,
)
from repro.kernels import ref as REF
from repro.kernels.matmul import matmul


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


@given(
    m=st.integers(1, 200), k=st.integers(1, 150), n=st.integers(1, 200),
    bm=st.sampled_from([8, 32, 128]), bk=st.sampled_from([8, 64, 128]),
    bn=st.sampled_from([16, 128]), order=st.sampled_from(["mn", "nm"]),
)
@settings(max_examples=25, deadline=None)
def test_matmul_shape_block_sweep(m, k, n, bm, bk, bn, order):
    a = _rand(jax.random.PRNGKey(m * 7 + k), (m, k))
    b = _rand(jax.random.PRNGKey(n * 13 + k), (k, n))
    out = matmul(a, b, bm=bm, bk=bk, bn=bn, grid_order=order)
    np.testing.assert_allclose(out, REF.matmul_ref(a, b), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_dtypes(dtype):
    a = _rand(jax.random.PRNGKey(0), (96, 64), dtype)
    b = _rand(jax.random.PRNGKey(1), (64, 80), dtype)
    out = matmul(a, b, bm=32, bk=32, bn=32)
    assert out.dtype == dtype
    ref = REF.matmul_ref(a, b)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(np.float32), ref.astype(np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("m", [16, 20])           # 20: A padded along m
@pytest.mark.parametrize("order", ["mn", "nm"])
@pytest.mark.parametrize("li", [0, 1, 2])
def test_stacked_matmul_reads_the_layer_bitwise(li, order, m):
    """B a stack [L, K, N] and a traced layer index: the kernel reads the
    layer's blocks out of the stack and gives exactly matmul(a, stack[li])."""
    stack = _rand(jax.random.PRNGKey(7), (3, 256, 384), jnp.bfloat16)
    a = _rand(jax.random.PRNGKey(8), (m, 256), jnp.bfloat16)
    kw = dict(bm=8, bk=128, bn=128, grid_order=order)
    out = jax.jit(lambda a, s, i: matmul(a, s, layer=i, **kw))(
        a, stack, jnp.int32(li))
    np.testing.assert_array_equal(out, matmul(a, stack[li], **kw))


@pytest.mark.parametrize("block", [(8, 96, 128), (8, 128, 160)])
def test_stacked_matmul_refuses_a_block_that_pads_the_stack(block):
    bm, bk, bn = block
    stack = jnp.zeros((2, 256, 384))
    with pytest.raises(ValueError, match="never padded"):
        matmul(jnp.zeros((8, 256)), stack, layer=jnp.int32(0), bm=bm, bk=bk,
               bn=bn)


def test_matmul_registry_integration(tmp_path):
    from repro.core import LoopTuner

    tuner = LoopTuner(policy="search", backend="tpu", search_budget_s=1.0)
    tuner.tune_matmul(64, 64, 64)
    set_registry(tuner.registry)
    try:
        a = _rand(jax.random.PRNGKey(2), (64, 64))
        b = _rand(jax.random.PRNGKey(3), (64, 64))
        np.testing.assert_allclose(tuned_matmul(a, b), REF.matmul_ref(a, b),
                                   rtol=2e-5, atol=2e-5)
    finally:
        set_registry(None)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@given(
    s=st.integers(2, 130), d=st.sampled_from([8, 16, 32]),
    hq=st.sampled_from([1, 2, 4]), g=st.sampled_from([1, 2]),
    bq=st.sampled_from([16, 64, 128]), bk=st.sampled_from([16, 128]),
    causal=st.booleans(),
)
@settings(max_examples=20, deadline=None)
def test_flash_attention_sweep(s, d, hq, g, bq, bk, causal):
    hkv = max(1, hq // g)
    hq = hkv * g
    ks = jax.random.split(jax.random.PRNGKey(s * 31 + d), 3)
    q = _rand(ks[0], (2, s, hq, d))
    k = _rand(ks[1], (2, s, hkv, d))
    v = _rand(ks[2], (2, s, hkv, d))
    out = flash_attention(q, k, v, causal=causal)
    ref = REF.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("window,softcap", [(None, None), (8, None),
                                            (None, 20.0), (16, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (1, 48, 4, 16))
    k = _rand(ks[1], (1, 48, 2, 16))
    v = _rand(ks[2], (1, 48, 2, 16))
    out = flash_attention(q, k, v, causal=True, window=window, softcap=softcap)
    ref = REF.attention_ref(q, k, v, causal=True, window=window,
                            softcap=softcap)
    np.testing.assert_allclose(out, ref, rtol=3e-5, atol=3e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (1, 64, 4, 16), jnp.bfloat16)
    k = _rand(ks[1], (1, 64, 4, 16), jnp.bfloat16)
    v = _rand(ks[2], (1, 64, 4, 16), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = REF.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(np.float32),
                               np.asarray(ref, np.float32), rtol=3e-2,
                               atol=3e-2)


# ---------------------------------------------------------------------------
# rwkv6 chunked scan
# ---------------------------------------------------------------------------


@given(
    s=st.integers(1, 70), n=st.sampled_from([4, 8, 16]),
    chunk=st.sampled_from([4, 16, 64]), bh=st.integers(1, 4),
)
@settings(max_examples=15, deadline=None)
def test_rwkv6_scan_sweep(s, n, chunk, bh):
    key = jax.random.PRNGKey(s * 17 + n)
    ks = jax.random.split(key, 5)
    r = _rand(ks[0], (bh, s, n), scale=0.5)
    k = _rand(ks[1], (bh, s, n), scale=0.5)
    v = _rand(ks[2], (bh, s, n), scale=0.5)
    logw = -jnp.exp(_rand(ks[3], (bh, s, n)) - 2.0)
    u = _rand(ks[4], (bh, n), scale=0.3)
    y, st_ = rwkv6_chunk_scan(r, k, v, logw, u, chunk=chunk)
    yr, sr = REF.rwkv6_ref(r, k, v, logw, u)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(st_, sr, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------


@given(
    s=st.integers(1, 40), c=st.sampled_from([8, 20, 32]),
    n=st.sampled_from([4, 8]), chunk=st.sampled_from([4, 8, 32]),
    bd=st.sampled_from([8, 16, 128]),
)
@settings(max_examples=15, deadline=None)
def test_mamba_scan_sweep(s, c, n, chunk, bd):
    key = jax.random.PRNGKey(s * 11 + c)
    ks = jax.random.split(key, 4)
    dtx = _rand(ks[0], (2, s, c), scale=0.3)
    da = -jnp.exp(_rand(ks[1], (2, s, c, n)) - 2.0)
    b = _rand(ks[2], (2, s, n), scale=0.5)
    cm = _rand(ks[3], (2, s, n), scale=0.5)
    y, h = mamba_scan(dtx, da, b, cm, chunk=chunk, bd=bd)
    yr, hr = REF.mamba_scan_ref(dtx, da, b, cm)
    np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, hr, rtol=2e-4, atol=2e-4)
