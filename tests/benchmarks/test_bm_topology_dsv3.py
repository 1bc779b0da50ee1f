"""``gigachat31_702b_a36b`` fits one TPU v5e: its 64-lane decode step and
its prefill chunk (the longest there is: every prompt goes through the
one executable) compile for a DESCRIBED v5e chip (no chip is attached
here), and weights + pool + the program's temporaries stay inside the
chip's memory.  A compile that passes is not a chip run.

A file of its own beside ``test_bm_topology.py`` (which is the
benchmark's and ``sflm_gpt2m``'s), the topology described inside a
fixture, skipped where it cannot be.
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
    from nnstreamer_tpu.llm.family import family_of_custom
    from nnstreamer_tpu.models import dsv3_lm

    # the default backend is the CPU here: take the chip's branch
    kernel, dsv3_lm.GROUPED_KERNEL = dsv3_lm.GROUPED_KERNEL, True
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    config = Manifest(os.path.join(ROOT, "BENCHMARK.json")).config(
        "gigachat31_702b_a36b")
    family, rest = family_of_custom(
        {k: str(v) for k, v in config["model"].items()})
    cfg = family.config_from_custom(rest)
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(lambda: family.init_params(cfg, 0)))
    state = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: family.init_state(cfg, config["element"]["slots"])))
    yield {"cfg": cfg, "config": config, "family": family,
           "params": params, "state": state,
           "i32": lambda *shape: on_chip(
               jax.ShapeDtypeStruct(shape, jnp.int32)),
           "flag": on_chip(jax.ShapeDtypeStruct((), jnp.bool_))}
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    dsv3_lm.GROUPED_KERNEL = kernel


def resident(stats) -> int:
    """Bytes the program holds while it runs: its arguments, its outputs
    that do not alias an argument, and its temporaries."""
    return (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)


def test_64_lane_decode_step_fits_the_chip(described):
    import jax

    d = described
    lanes = d["config"]["element"]["batch"]
    assert lanes == 64

    def step(params, state, tokens, pos, slots):
        return d["family"].decode_step(params, state, tokens, pos, slots,
                                       d["cfg"])

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        d["params"], d["state"], d["i32"](lanes), d["i32"](lanes),
        d["i32"](lanes)).compile()
    stats = compiled.memory_analysis()
    plan = d["config"]["memory_plan"]
    print("step", stats)
    # the compiler pads a row of 576 to its tiles: the pool it holds is
    # at least the plan's
    assert stats.argument_size_in_bytes >= (plan["weights_bytes"]
                                            + plan["pool_bytes"])
    # the pool is donated: the step updates it in place
    assert stats.alias_size_in_bytes >= plan["pool_bytes"]
    assert (plan["weights_bytes"] + plan["pool_bytes"]
            <= resident(stats) < USABLE_BYTES)
    # the routed experts are one grouped product each way, the megablox
    # kernel: the step holds no (tokens x experts) product of every held
    # expert
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ragged-dot" not in text


def test_prefill_chunk_fits_the_chip(described):
    import jax

    d = described
    cfg = d["cfg"]
    assert cfg.chunk == 512 and cfg.max_seq % cfg.chunk == 0

    def chunk(params, state, tokens, slot, start, true_len, last):
        return d["family"].prefill_chunk(params, state, tokens, slot,
                                         start, true_len, last, cfg)

    compiled = jax.jit(chunk, donate_argnums=(1,)).lower(
        d["params"], d["state"], d["i32"](cfg.chunk), d["i32"](),
        d["i32"](), d["i32"](), d["flag"]).compile()
    stats = compiled.memory_analysis()
    print("chunk", stats)
    assert resident(stats) < USABLE_BYTES
