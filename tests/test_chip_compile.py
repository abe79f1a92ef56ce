"""The compiled kernel path, checked without a chip.

The topology tests compile the Pallas matmul and musicgen-large's routed
serving steps for a described TPU v5e (no chip attached): the TPU compiler
installed here refuses what Mosaic would refuse on the chip — unaligned
blocks, blocks over the VMEM limit — which interpret mode never sees.  The
v5e topology is described inside a module fixture, so only the worker that
runs this file loads the TPU library.  The CPU tests cover the legality
check, the compile-cache placement and the harvest's platform independence.
"""
import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.core.actions import CPU_SPLITS, apply_action, build_action_space
from repro.core.loop_ir import LoopNest, matmul_benchmark
from repro.core.registry import schedule_to_blockspec
from repro.core.tiling import (VMEM_LIMIT_BYTES, block_error,
                               legalize_block)
from repro.kernels.matmul import matmul
from repro.runtime import device as D
from repro.runtime import sharding as SH

# musicgen-large's dense dots at batch 4, prompt 32 (launch.tune harvest):
# decode m = 4, prefill m = 4 * 32
MUSICGEN_DOTS = {
    "decode-ffn-up": (4, 2048, 8192),
    "decode-ffn-down": (4, 8192, 2048),
    "prefill-ffn-up": (128, 2048, 8192),
    "prefill-attn-proj": (128, 2048, 2048),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for the described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile_matmul(one_chip, shape, block, dtype, out_dtype):
    m, k, n = shape
    bm, bk, bn = block
    fn = jax.jit(lambda a, b: matmul(a, b, bm=bm, bk=bk, bn=bn,
                                     interpret=False, out_dtype=out_dtype))
    return fn.lower(jax.ShapeDtypeStruct((m, k), dtype, sharding=one_chip),
                    jax.ShapeDtypeStruct((k, n), dtype, sharding=one_chip)
                    ).compile()


@pytest.mark.parametrize("name", sorted(MUSICGEN_DOTS))
def test_tuned_matmul_compiles_for_v5e(one_chip, name):
    """The block schedule_to_blockspec emits for the untuned nest (whole
    arrays, the case that once ran out of VMEM) and for split nests from
    the measured backend's ladder compiles as served (bf16) and as timed
    (f32 in, f32 out)."""
    shape = MUSICGEN_DOTS[name]
    nests = [LoopNest(matmul_benchmark(*shape))]
    split = LoopNest(matmul_benchmark(*shape))
    for a in build_action_space(CPU_SPLITS):
        if a.name in ("split_64", "down"):
            apply_action(split, a)  # 64-wide blocks: unaligned to 128 lanes
    nests.append(split)
    for nest in nests:
        block, _ = schedule_to_blockspec(nest)
        bmkn = (block["m"], block["k"], block["n"])
        for dtype in (jnp.bfloat16, jnp.float32):
            compiled = _compile_matmul(one_chip, shape, bmkn, dtype, dtype)
            assert "tpu_custom_call" in compiled.as_text(), (name, bmkn)


def test_routed_decode_step_compiles_for_v5e(one_chip, monkeypatch):
    """musicgen-large's full-width decode step, every dense dot routed
    through the Pallas kernel with a registry block, compiles for v5e."""
    from repro.configs import ShapeCell, get_config, input_specs
    from repro.core.registry import ScheduleRegistry
    from repro.kernels import ops
    from repro.models import steps as S
    from repro.models import transformer as T

    cfg = get_config("musicgen-large")
    reg = ScheduleRegistry()
    for m, k, n in [(4, 2048, 2048), (4, 2048, 8192), (4, 8192, 2048)]:
        reg.put("mm", (m, k, n), 1.0, [], LoopNest(matmul_benchmark(m, k, n)),
                dtype=cfg.dtype)
    # on this CPU host "auto" keeps the XLA lowering: force the compiled route
    monkeypatch.setattr(ops, "_route_pallas", lambda pallas: (True, False))

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    specs = input_specs(cfg, ShapeCell("serve", 128, 4, "decode"))
    ops.reset_serving_stats()
    compiled = jax.jit(S.make_decode_step(cfg, registry=reg)).lower(
        params, place(specs["batch"]), place(specs["caches"]),
        place(specs["cache_len"])).compile()
    stats = ops.serving_stats(reset=True)
    assert stats["misses"] == 0 and stats["routed"] == stats["hits"] > 0
    assert "tpu_custom_call" in compiled.as_text()


#: musicgen-large's served decode blocks at batch 16 (the analytical
#: tune's table): (bm, bk, bn), m outermost
MUSICGEN_DECODE_BLOCKS = {(16, 2048, 2048): (16, 2048, 2048),
                          (16, 2048, 8192): (8, 128, 8192),
                          (16, 8192, 2048): (8, 128, 2048)}


#: as the benchmark's mm_roofline reads a v5e trace: the routed kernel's
#: custom call, and a layer's [K, N] weight sliced out of its stack
KERNEL_EVENT = r'^%matmul(\.\d+)? = .*custom_call_target="tpu_custom_call"'
STAGING_EVENT = re.compile(
    r"^%[\w.-]*dynamic-slice[\w.-]* = (\w+)\[(\d+),(\d+)\]")


def test_routed_decode_step_reads_weights_from_the_stack_on_v5e(
        topo, one_chip, tmp_path, monkeypatch):
    """musicgen-large's decode step at its served blocks, compiled for
    v5e: each routed kernel reads its layer's weight out of the
    [n_layers, K, N] stack, so no dynamic-slice fusion copies a layer's
    [K, N] weight out first (mm_roofline's staging events), and the
    kernel keeps the HLO name ``%matmul.N`` its roofline finds it by."""
    from repro.configs import ShapeCell, get_config, input_specs
    from repro.core.registry import ScheduleRegistry
    from repro.kernels import ops
    from repro.models import steps as S
    from repro.models import transformer as T

    cfg = get_config("musicgen-large")
    path = tmp_path / "served.json"
    path.write_text(json.dumps({
        f"mm:{m}x{k}x{n}:{cfg.dtype}": {
            "gflops": 1.0, "actions": [], "grid_order": ["m", "k", "n"],
            "block": {"m": bm, "k": bk, "n": bn}}
        for (m, k, n), (bm, bk, bn) in MUSICGEN_DECODE_BLOCKS.items()}))
    monkeypatch.setattr(ops, "_route_pallas", lambda pallas: (True, False))

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    specs = input_specs(cfg, ShapeCell("serve", 512, 16, "decode"))
    ops.reset_serving_stats()
    step = S.make_decode_step(cfg, registry=ScheduleRegistry(str(path)))
    with jax.default_device(topo.devices[0]):
        text = jax.jit(step, donate_argnums=(2,)).lower(
            params, place(specs["batch"]), place(specs["caches"]),
            place(specs["cache_len"])).compile().as_text()
    stats = ops.serving_stats(reset=True)
    assert stats["misses"] == 0
    # the LM head (transposed weight) shares 16x2048x2048 with q/k/v/o
    # and is the one routed call the stack does not serve
    assert stats["stacked"] == stats["routed"] - 1 > 0
    weights = {(k, n) for _, k, n in MUSICGEN_DECODE_BLOCKS}
    staged = []
    for ln in text.splitlines():
        m = STAGING_EVENT.match(ln.strip())
        if m and m.group(1) == "bf16" and \
                (int(m.group(2)), int(m.group(3))) in weights:
            staged.append(ln.strip())
    assert staged == []
    kernels = [ln for ln in text.splitlines()
               if re.match(KERNEL_EVENT, ln.strip())]
    assert len(kernels) >= len(MUSICGEN_DECODE_BLOCKS)


def _cache_copies(text, *dims):
    """Copies and gathers in optimized HLO ``text`` of an array whose
    shape ends in one of ``dims`` (K/V stacks, their shards, one layer's
    slice)."""
    ends = "|".join(",".join(map(str, d)) for d in dims)
    return [ln for ln in text.splitlines()
            if re.search(r"= \S+ (copy|all-gather)(-start|-done)?\(", ln)
            and re.search(rf"\[(\d+,)*({ends})\]", ln)]


@pytest.mark.parametrize("arch,periods,batch,max_len", [
    ("musicgen-large", None, 16, 512),   # served shapes; head dim 64
    ("gemma2-27b", 2, 8, 512),           # head dim 128, windowed layers
])
def test_decode_step_keeps_the_cache_in_place_on_v5e(topo, one_chip, arch,
                                                     periods, batch, max_len):
    """A decode step, cache donated, compiled for v5e as its default
    device: the K/V stacks keep the layout they enter with, so no copy of
    a stack or of a layer's slice is made."""
    from repro.configs import ShapeCell, get_config, input_specs
    from repro.models import steps as S
    from repro.models import transformer as T

    cfg = get_config(arch)
    if periods:
        cfg = dataclasses.replace(cfg, n_layers=periods * len(cfg.period))

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    specs = input_specs(cfg, ShapeCell("serve", max_len, batch, "decode"))
    with jax.default_device(topo.devices[0]):
        compiled = jax.jit(S.make_decode_step(cfg),
                           donate_argnums=(2,)).lower(
            params, place(specs["batch"]), place(specs["caches"]),
            place(specs["cache_len"])).compile()
    stack = specs["caches"][0]["k"].shape
    assert _cache_copies(compiled.as_text(), stack[1:]) == []


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_kv_layout_is_the_v5e_argument_layout(topo, one_chip, arch):
    """The layout keep_kv_layout holds each K/V stack to is the one v5e
    gives the stack as a step's argument, at two serving sizes: a step
    that only holds its donated argument compiles to no copy.  A layout
    that differed would copy the whole stack into and out of every
    decode step."""
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config(arch)
    for batch, max_len in [(8, 512), (16, 1040)]:
        caches = jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len))
        for pos in caches:
            if "k" not in pos:
                continue
            leaf = jax.ShapeDtypeStruct(pos["k"].shape, pos["k"].dtype,
                                        sharding=one_chip)
            with jax.default_device(topo.devices[0]):
                text = jax.jit(SH.keep_kv_layout, donate_argnums=(0,)) \
                    .lower(leaf).compile().as_text()
            assert _cache_copies(text, leaf.shape) == [], leaf.shape


def test_sharded_decode_step_keeps_cache_shards_in_place_on_v5e(topo,
                                                                one_chip):
    """musicgen-large's decode step on the v5e:2x2 mesh (data 2, model 2)
    under use_mesh, the cache sharded by cache_pspecs and donated: each
    device holds its shard of a K/V stack in place, with no copy and no
    gather of a stack, a shard or a layer's slice.  Held outside a
    shard_map, the partitioner gathers the stacks; left free, XLA copies
    each shard into and out of the layer loop."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import ShapeCell, get_config, input_specs
    from repro.models import steps as S
    from repro.models import transformer as T

    cfg = get_config("musicgen-large")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))

    def place(tree, spec):
        return jax.tree.map(lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)), tree, spec)

    specs = input_specs(cfg, ShapeCell("serve", 512, 16, "decode"))
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    cspecs = SH.cache_pspecs(specs["caches"], mesh, 16, cfg.n_kv_heads)
    with mesh, SH.use_mesh(mesh):
        compiled = jax.jit(S.make_decode_step(cfg),
                           donate_argnums=(2,)).lower(
            place(params, jax.tree.map(lambda _: P(), params)),
            place(specs["batch"], jax.tree.map(lambda _: P("data"),
                                               specs["batch"])),
            place(specs["caches"], cspecs),
            place(specs["cache_len"], P())).compile()
    stack = specs["caches"][0]["k"].shape                  # (48, 16, 512, 32, 64)
    shard = (stack[1] // 2, stack[2], stack[3] // 2, stack[4])
    assert cspecs[0]["k"] == P(None, "data", None, "model", None)
    assert _cache_copies(compiled.as_text(), stack[1:], shard) == []


def test_harvest_is_platform_independent(one_chip):
    """The harvest reads the lowered HLO: lowering for the TPU gives the
    same dots as lowering for the CPU (the TPU's optimized HLO turns them
    into convolutions, where the harvest would find none)."""
    from repro.analysis.hlo_parse import harvest_dots
    from repro.configs import ShapeCell, get_config, input_specs
    from repro.models import steps as S
    from repro.models import transformer as T

    cfg = get_config("musicgen-large").smoke()
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    specs = input_specs(cfg, ShapeCell("serve", 64, 4, "decode"))
    args = (params, specs["batch"], specs["caches"], specs["cache_len"])

    def dots(lowered):
        hlo = lowered.compiler_ir("hlo").get_hlo_module().to_string()
        return sorted((r["batch"] * r["m"], r["k"], r["n"], r["dtype"],
                       r["count"]) for r in harvest_dots(hlo))

    step = jax.jit(S.make_decode_step(cfg))
    on_tpu = step.lower(*jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), args))
    assert dots(on_tpu) == dots(step.lower(*args)) != []


# ---------------------------------------------------------------------------
# CPU only: the legality check and the compile-cache placement
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block,why", [
    ((4, 64, 8192), "bk=64"),     # not a multiple of the 128 lanes
    ((4, 2048, 200), "bn=200"),   # nor is this, and it is not n either
    ((4, 2048, 8192), "VMEM"),    # whole f32 arrays: over the VMEM limit
])
def test_compiled_matmul_refuses_illegal_block(block, why):
    a = jnp.zeros((4, 2048), jnp.float32)
    b = jnp.zeros((2048, 8192), jnp.float32)
    with pytest.raises(ValueError) as e:
        matmul(a, b, bm=block[0], bk=block[1], bn=block[2], interpret=False)
    msg = str(e.value)
    assert f"illegal block (bm, bk, bn)={block}" in msg
    assert "(4, 2048, 8192)" in msg or why == "bn=200"
    assert str(VMEM_LIMIT_BYTES) in msg and why in msg


def test_legalized_blocks_pass_the_check():
    rng = np.random.default_rng(0)
    for _ in range(300):
        shape = tuple(int(x) for x in rng.integers(1, 9000, 3))
        block = tuple(int(rng.integers(1, d + 1)) for d in shape)
        for in_b, out_b in ((4, 4), (2, 2), (2, 4)):
            legal = legalize_block(shape, block, in_b, out_b)
            padded = tuple(-(-d // b) * b for d, b in zip(shape, legal))
            assert block_error(padded, legal, in_b, out_b) is None


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(D.CACHE_ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert D.enable_compile_cache() == D.DEFAULT_COMPILE_CACHE
        assert jax.config.jax_compilation_cache_dir == D.DEFAULT_COMPILE_CACHE
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert D.DEFAULT_COMPILE_CACHE == os.path.join(root, ".jax_compile_cache")


_CACHE_CHILD = """
import jax, jax.numpy as jnp
from repro.runtime.device import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
path = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
print(path, jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_env_dir_is_the_only_cache(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _CACHE_CHILD], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path), str(tmp_path)]
    assert os.listdir(tmp_path), "nothing was cached in the env's directory"
