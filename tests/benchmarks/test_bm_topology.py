"""``sflm_gpt2m`` still fits one TPU v5e: its decode step at every lane
and its longest prefill compile for a DESCRIBED v5e chip (no chip is
attached here), and weights + pool + the step's temporaries stay inside
the chip's memory.  A compile that passes is not a chip run: it says the
compiler would take the program, nothing about its speed.

One file, the topology described inside a fixture (only the worker that
runs this file loads the TPU compiler), skipped where it cannot be.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: what the v5e compiler reports as usable ("Used ... of 15.75G hbm")
USABLE_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")


@pytest.fixture(scope="module")
def described(topo):
    """The configuration as the element would build it, as shapes on one
    described chip; the persistent cache is off (a described-topology
    executable cannot be read back without a chip)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from benchmarks.manifest import Manifest
    from nnstreamer_tpu.filter.framework import FilterProperties
    from nnstreamer_tpu.llm.pool import dense_pool_shape
    from nnstreamer_tpu.models.streamformer_lm import config_from_custom
    from nnstreamer_tpu.parallel.train_step import init_params

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    config = Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(
        "sflm_gpt2m")
    custom = ",".join(f"{k}:{v}" for k, v in config["model"].items())
    cfg = config_from_custom(FilterProperties.parse_custom(custom))
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda x: on_chip(x.shape, x.dtype),
        jax.eval_shape(lambda: init_params(cfg, 0)))
    slots = config["element"]["slots"]
    # the pool as the element reserves it: the shape is the program's
    pool = on_chip(dense_pool_shape(cfg, slots), cfg.dtype)
    yield {"cfg": cfg, "config": config, "params": params, "pool": pool,
           "i32": lambda *shape: on_chip(shape, jnp.int32)}
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def resident(stats) -> int:
    """Bytes the program holds while it runs: its arguments, its outputs
    that do not alias an argument, and its temporaries."""
    return (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)


def test_decode_step_at_every_lane_fits_the_chip(described):
    import jax

    from nnstreamer_tpu.models.streamformer_lm import decode_step_pooled

    d = described
    lanes = d["config"]["element"]["batch"]

    def step(params, k, v, tokens, pos, slots):
        return decode_step_pooled(params, k, v, tokens, pos, slots,
                                  d["cfg"])

    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        d["params"], d["pool"], d["pool"], d["i32"](lanes),
        d["i32"](lanes), d["i32"](lanes)).compile()
    stats = compiled.memory_analysis()
    plan = d["config"]["memory_plan"]
    assert stats.argument_size_in_bytes >= (plan["weights_bytes"]
                                            + plan["kv_pool_bytes"])
    # the pool is donated: the step updates it in place
    assert stats.alias_size_in_bytes >= plan["kv_pool_bytes"]
    # what the cell holds while a 32-lane step runs: the weights and
    # the pool (6.56 GB; the file's two numbers), and temporaries that
    # no longer copy the pool (0.09 GB since PR 26, where the old
    # five-dimensional pool cost 2.2 times itself)
    assert (plan["weights_bytes"] + plan["kv_pool_bytes"]
            <= resident(stats) < USABLE_BYTES)
    assert stats.temp_size_in_bytes < plan["kv_pool_bytes"] // 8


def test_longest_prefill_fits_the_chip(described):
    import jax

    from nnstreamer_tpu.models.streamformer_lm import prefill_pooled

    d = described
    cfg = d["cfg"]

    def prefill(params, k_pool, v_pool, tokens, slot, true_len):
        return prefill_pooled(params, k_pool, v_pool, tokens, slot,
                              true_len, cfg)

    compiled = jax.jit(prefill, donate_argnums=(1, 2)).lower(
        d["params"], d["pool"], d["pool"], d["i32"](cfg.max_seq),
        d["i32"](), d["i32"]()).compile()
    assert resident(compiled.memory_analysis()) < USABLE_BYTES
    # 1024 tokens is under the flash gate: no Pallas kernel in this cell
    assert "tpu_custom_call" not in compiled.as_text()
