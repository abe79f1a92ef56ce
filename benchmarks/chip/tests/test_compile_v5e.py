"""The serve cells' routed steps, compiled for a described TPU v5e.

Each configuration's prefill and decode steps, at the shapes of its cell
and with every dense product routed through the Pallas kernel with the
block of the table the cell serves, compile
for one v5e chip with no chip attached; the compiler's memory analysis of
each must fit the chip's 16 GB beside the weights and the cache.  The
topology is described inside a module fixture, so only the process that
runs this file loads the TPU library.

    JAX_PLATFORMS=cpu python -m pytest -q -s benchmarks/chip/tests/test_compile_v5e.py
"""
import json
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

import harness

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16e9
CELLS = [("musicgen-large", "decode_b16_p32_g480"),
         ("phi3-mini-3.8b", "prefill_b8_p1024_g16")]


def _load(kind, name):
    with open(os.path.join(CHIP, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _registry(model_cfg, traffic, tmp):
    """The table the cell serves, tuned as its first run tunes it."""
    from repro.core.registry import ScheduleRegistry

    path = os.path.join(tmp, "registry.json")
    harness.tune_for_serving(model_cfg, traffic, path)
    return ScheduleRegistry(path)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_routed_steps_compile_and_fit(one_chip, monkeypatch, tmp_path, config,
                                      traffic):
    from repro.kernels import ops
    from repro.models import steps as S
    from repro.models import transformer as T

    cfg, t = _load("configs", config), _load("traffic", traffic)
    model_cfg = harness.model_config(cfg)
    b, p, max_len = t["batch"], t["prompt_len"], t["max_len"]
    reg = _registry(model_cfg, t, str(tmp_path))
    # on this CPU host "auto" keeps the XLA lowering: force the kernel
    monkeypatch.setattr(ops, "_route_pallas", lambda pallas: (True, False))

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    block = harness.Spec(os.path.dirname(os.path.dirname(CHIP))).block(cfg)
    params = jax.eval_shape(lambda: block.program_params(cfg, 0))
    assert (jax.tree.structure(params) == jax.tree.structure(jax.eval_shape(
        lambda: T.init_params(model_cfg, jax.random.PRNGKey(0)))))
    params = place(params)
    frontend = ((b, p), "int32") if cfg["frontend"] == "tokens" else \
        ((b, p, cfg["d_model"]), cfg["dtype"])
    key = "tokens" if cfg["frontend"] == "tokens" else "embeds"
    prompt = place({key: jax.ShapeDtypeStruct(*frontend)})
    one = place({key: jax.ShapeDtypeStruct(
        (b, 1) if key == "tokens" else (b, 1, cfg["d_model"]),
        frontend[1])})
    caches = place(jax.eval_shape(lambda: T.init_cache(model_cfg, b,
                                                       max_len)))
    ops.reset_serving_stats()
    # the decode step holds its K/V stacks to the default device's layout:
    # the described chip's, not this host's
    with jax.default_device(next(iter(one_chip.device_set))):
        prefill = jax.jit(S.make_prefill_step(model_cfg, max_len,
                                              registry=reg)
                          ).lower(params, prompt).compile()
        decode = jax.jit(S.make_decode_step(model_cfg, registry=reg),
                         donate_argnums=(2,)).lower(
            params, one, caches,
            place(jax.ShapeDtypeStruct((), "int32"))).compile()
    stats = ops.serving_stats(reset=True)
    assert stats["misses"] == 0 and stats["routed"] == stats["hits"] > 0
    for name, c in (("prefill", prefill), ("decode", decode)):
        assert "tpu_custom_call" in c.as_text()
        mem = c.memory_analysis()
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        print(f"\n{config} {name}: arguments {mem.argument_size_in_bytes} "
              f"outputs {mem.output_size_in_bytes} aliased "
              f"{mem.alias_size_in_bytes} temporaries "
              f"{mem.temp_size_in_bytes} total {total}")
        assert total < HBM
