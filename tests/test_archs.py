"""Per-architecture smoke tests (assignment requirement): a REDUCED config of
the same family runs one forward + one train step on CPU; output shapes and
finiteness asserted.  Decode consistency checked for every mixer family."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import steps as S
from repro.models import transformer as T
from repro.optim import adamw_init
from repro.optim.schedules import constant

# several minutes of reduced-config training across every architecture
pytestmark = pytest.mark.slow

ARCH_IDS = sorted(ARCHS)


def _batch_for(cfg, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {}
    if cfg.frontend == "tokens":
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    else:
        batch["embeds"] = jnp.asarray(
            rng.standard_normal((b, s, cfg.d_model)), jnp.float32)
    if cfg.n_cross_tokens:
        batch["encoder"] = jnp.asarray(
            rng.standard_normal((b, cfg.n_cross_tokens, cfg.d_cross)),
            jnp.float32)
    batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch_for(cfg)
    logits, _, aux = T.forward(params, cfg, batch)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: NaN/inf logits"
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params, keep_master=False)
    step = S.make_train_step(cfg, constant(1e-3))
    batch = _batch_for(cfg)
    new_params, new_opt, metrics = jax.jit(step)(params, opt, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually changed
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert delta > 0, f"{arch}: train step was a no-op"
    assert int(new_opt.step) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_loss_decreases_three_steps(arch):
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params, keep_master=False)
    step = jax.jit(S.make_train_step(cfg, constant(5e-3)))
    batch = _batch_for(cfg)
    losses = []
    for _ in range(3):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], f"{arch}: loss did not decrease {losses}"


@pytest.mark.parametrize(
    "arch",
    ["phi3-mini-3.8b", "gemma2-27b", "rwkv6-7b", "jamba-v0.1-52b",
     "olmoe-1b-7b", "llama-3.2-vision-11b", "musicgen-large"],
)
def test_decode_matches_forward(arch):
    """Prefill s tokens then decode one: logits must match the full forward
    on s+1 tokens (per mixer family: attn/local/cross/mamba/rwkv/moe)."""
    cfg = get_config(arch).smoke()
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    b, s = 2, 12
    full = _batch_for(cfg, b, s + 1, seed=3)
    logits_full, _, _ = T.forward(params, cfg, full, remat=False)

    def cut(x, n):
        return x[:, :n] if x.ndim >= 2 and x.shape[1] >= s else x

    prefix = {k: (v[:, :s] if k in ("tokens", "embeds", "labels") else v)
              for k, v in full.items()}
    prefill = S.make_prefill_step(cfg, max_len=s + 4)
    last_logits, caches, cache_len = prefill(params, prefix)
    np.testing.assert_allclose(
        np.asarray(last_logits, np.float32),
        np.asarray(logits_full[:, s - 1], np.float32), rtol=2e-3, atol=2e-3)

    one = {k: v[:, s:s + 1] for k, v in full.items()
           if k in ("tokens", "embeds")}
    serve = S.make_decode_step(cfg)
    nxt, logits_one, _ = serve(params, one, caches, cache_len)
    np.testing.assert_allclose(
        np.asarray(logits_one[:, 0], np.float32),
        np.asarray(logits_full[:, s], np.float32), rtol=2e-3, atol=2e-3)
    assert nxt.shape == (b,)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact published hyper-parameters."""
    spec = {
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "rwkv6-7b": (32, 4096, None, None, 14336, 65536),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "olmoe-1b-7b": (16, 2048, 16, 16, 1024, 50304),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    }[arch]
    cfg = get_config(arch)
    nl, dm, nh, nkv, dff, vocab = spec
    assert cfg.n_layers == nl and cfg.d_model == dm and cfg.vocab == vocab
    if nh is not None:
        assert cfg.n_heads == nh and cfg.n_kv_heads == nkv
    if arch == "olmoe-1b-7b":
        assert cfg.moe.n_experts == 64 and cfg.moe.top_k == 8
        assert cfg.moe.d_ff_expert == dff
    elif arch == "jamba-v0.1-52b":
        assert cfg.moe.n_experts == 16 and cfg.moe.top_k == 2
        assert cfg.d_ff == dff
    elif arch == "llama4-scout-17b-a16e":
        assert cfg.moe.n_experts == 16 and cfg.moe.top_k == 1
        assert cfg.moe.shared_expert
    else:
        assert cfg.d_ff == dff


def test_param_counts_plausible():
    """Total parameter counts are in the advertised ballpark."""
    expect = {
        "phi3-mini-3.8b": (3.3e9, 4.3e9),
        "command-r-35b": (30e9, 40e9),
        "gemma2-27b": (22e9, 30e9),
        "gemma3-12b": (10e9, 14e9),
        "rwkv6-7b": (6e9, 9e9),
        "llama-3.2-vision-11b": (8.5e9, 11.5e9),  # backbone only (no vision tower)
        "jamba-v0.1-52b": (45e9, 58e9),
        "olmoe-1b-7b": (5.5e9, 8e9),
        "llama4-scout-17b-a16e": (95e9, 115e9),
        "musicgen-large": (2.5e9, 4e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params outside [{lo/1e9}, {hi/1e9}]B"


def test_active_params_moe():
    cfg = get_config("olmoe-1b-7b")
    active = cfg.active_param_count()
    total = cfg.param_count()
    assert active < 0.35 * total  # 64e top-8 => ~1/8 of expert params active


# ---------------------------------------------------------------------------
# The decode cache, updated in place in the layer scan
# ---------------------------------------------------------------------------

#: one arch per kind of cache: dense attention, windowed attention,
#: Mamba state, RWKV6 state, cross-attention K/V
CACHE_KINDS = {
    "dense": "phi3-mini-3.8b",
    "windowed": "gemma2-27b",
    "mamba": "jamba-v0.1-52b",
    "rwkv6": "rwkv6-7b",
    "cross": "llama-3.2-vision-11b",
}
SENTINEL = 7.0


def _two_periods(arch):
    """The smoke config with two layer periods, so the scan's layer index
    picks among several layers' slices."""
    cfg = get_config(arch).smoke()
    return dataclasses.replace(cfg, n_layers=2 * len(cfg.period))


def _seq_leaf(path):
    return jax.tree_util.keystr(path).endswith(("['k']", "['v']"))


@pytest.mark.parametrize("kind", sorted(CACHE_KINDS))
def test_decode_writes_cache_in_place(kind):
    """Prefill s tokens, then decode 3 with the cache donated: each step's
    logits match the full forward; the cache then holds what a prefill of
    all s + 3 tokens holds (each written slot the token's K/V, each state
    the state after s + 3 tokens); the slots past the last write keep what
    they held before the steps."""
    cfg = _two_periods(CACHE_KINDS[kind])
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    b, s, n, max_len = 2, 10, 3, 16
    full = _batch_for(cfg, b, s + n, seed=3)
    full.pop("labels")
    logits_full, _, _ = T.forward(params, cfg, full, remat=False)

    def upto(m):
        return {k: (v[:, :m] if k in ("tokens", "embeds") else v)
                for k, v in full.items()}

    prefill = jax.jit(S.make_prefill_step(cfg, max_len=max_len))
    last, caches, cache_len = prefill(params, upto(s))
    _, want, _ = prefill(params, upto(s + n))
    caches = jax.tree_util.tree_map_with_path(
        lambda p, c: c.at[:, :, s:].set(SENTINEL) if _seq_leaf(p) else c,
        caches)
    got = [last]
    serve = jax.jit(S.make_decode_step(cfg), donate_argnums=(2,))
    for j in range(n):
        one = {k: v[:, s + j:s + j + 1] for k, v in full.items()
               if k in ("tokens", "embeds")}
        _, logits, caches = serve(params, one, caches, cache_len + j)
        got.append(logits[:, 0])
    for j, lg in enumerate(got):
        np.testing.assert_allclose(np.asarray(lg), np.asarray(
            logits_full[:, s - 1 + j]), rtol=1e-4, atol=1e-4)
    for (path, c), w in zip(jax.tree_util.tree_leaves_with_path(caches),
                            jax.tree.leaves(want)):
        c, w = np.asarray(c), np.asarray(w)
        if _seq_leaf(path):
            np.testing.assert_allclose(c[:, :, :s + n], w[:, :, :s + n],
                                       rtol=1e-4, atol=1e-4)
            assert (c[:, :, s + n:] == SENTINEL).all(), path
        else:
            np.testing.assert_allclose(c, w, rtol=1e-4, atol=1e-4)


def test_windowed_prefill_keeps_the_tail():
    """A windowed layer's cache shorter than the prompt holds the prompt's
    last ``window`` tokens: layer 0's K/V equal the tail of the same
    layer's K/V in a cache long enough for the whole prompt."""
    cfg = _two_periods("gemma2-27b")
    short = dataclasses.replace(cfg, period=tuple(
        dataclasses.replace(sp, window=8) if sp.window else sp
        for sp in cfg.period))
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    batch = {"tokens": _batch_for(cfg, 2, 12, seed=3)["tokens"]}
    _, tail, _ = S.make_prefill_step(short, max_len=16)(params, batch)
    _, whole, _ = S.make_prefill_step(cfg, max_len=16)(params, batch)
    pos = [i for i, sp in enumerate(cfg.period) if sp.window][0]
    for name in ("k", "v"):
        assert tail[pos][name].shape[2] == 8
        np.testing.assert_allclose(np.asarray(tail[pos][name][0]),
                                   np.asarray(whole[pos][name][0, :, 4:12]),
                                   rtol=1e-5, atol=1e-5)


def _lowered_steps(cfg, b=3, prompt=10, max_len=16):
    """The prefill, and the decode step jitted as a server jits it with the
    cache donated: (lowered prefill, lowered decode, cache shapes)."""
    from repro.configs import ShapeCell, input_specs

    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    dec = input_specs(cfg, ShapeCell("serve", max_len, b, "decode"))
    pre = input_specs(cfg, ShapeCell("prefill", prompt, b, "prefill"))
    step = jax.jit(S.make_decode_step(cfg), donate_argnums=(2,))
    prefill = jax.jit(S.make_prefill_step(cfg, max_len=max_len))
    return (prefill.lower(params, pre["batch"]),
            step.lower(params, dec["batch"], dec["caches"], dec["cache_len"]),
            jax.tree.leaves(dec["caches"]))


def _hlo_shape(leaf):
    return f"{jnp.dtype(leaf.dtype).name.replace('float', 'f')}" \
           f"[{','.join(map(str, leaf.shape))}]"


@pytest.mark.parametrize("check", ["aliased", "carried_once",
                                   "prefill_fills_slices"])
def test_decode_step_updates_cache_in_place(check):
    """The structure that lets XLA write the cache in place, at a tiny
    jamba (attention K/V and Mamba state, two layer periods).  aliased:
    the compiled decode step returns every cache leaf in its input's
    buffer.  carried_once: the layer scan's loop state holds each stacked
    cache leaf once, as the carry, and no per-layer output stack beside
    it.  prefill_fills_slices: the prefill writes each K/V stack one whole
    layer slice at a time, so no slot is left to the stack's first fill."""
    cfg = _two_periods("jamba-v0.1-52b")
    prefill, decode, leaves = _lowered_steps(cfg)
    if check == "aliased":
        text = decode.compile().as_text()
        alias = dict(re.findall(r"\{(\d+)\}: \((\d+), \{\}, may-alias\)",
                                text.splitlines()[0]))
        params = sorted(int(n) for n in re.findall(
            r"%caches_\S* = \S+ parameter\((\d+)\)", text))
        assert len(params) == len(leaves) > 0
        # outputs: next token, logits, then the cache leaves in order
        assert {int(o): int(p) for o, p in alias.items()} == {
            2 + i: p for i, p in enumerate(params)}
    elif check == "carried_once":
        hlo = decode.compiler_ir("hlo").get_hlo_module().to_string()
        entry = hlo[hlo.index("\nENTRY"):]
        (loop,) = [ln for ln in entry.splitlines() if " while(" in ln]
        state = re.sub(r"\{[\d,]*\}", "", loop.split(" while(")[0])
        for leaf in leaves:
            shape = _hlo_shape(leaf)
            want = sum(_hlo_shape(o) == shape for o in leaves)
            assert state.count(shape + ",") + state.count(shape + ")") \
                == want, shape
    else:
        kv = {"x".join(map(str, lf.shape)) for lf in leaves if lf.ndim == 5}
        writes = re.findall(
            r"stablehlo.dynamic_update_slice .*: \(tensor<(\S+)xf32>, "
            r"tensor<(\S+)xf32>", prefill.as_text())
        into_kv = [(st, up) for st, up in writes if st in kv]
        assert len(into_kv) == 2 and len(kv) == 1  # one attention layer: k, v
        for stack, update in into_kv:
            assert update == "1x" + stack.split("x", 1)[1], (stack, update)
