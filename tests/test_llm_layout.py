"""The compiler keeps the dense KV pool still: the ENGINE's own decode
step and dense prefill, at ``sflm_gpt2m``'s widths, compiled for a
DESCRIBED TPU v5e (no chip is attached here), update the donated
``llm/pool.dense_pool_shape`` arrays in place — no conversion of the
pool between layouts (``remat_compressed`` / ``remat_uncompressed``),
and temporaries a fraction of the pool.  With the pool as ``(slots + 1,
L, T, H, Dh)`` the 32-lane step held 188 such conversions and 10.0 GB of
temporaries, three times the pool (PERF.md §6, PR 26).

A compile that passes is not a chip run: it says what the compiler would
do with the program, nothing about its speed.  The topology is described
inside a fixture (only the worker that runs this file loads the TPU
compiler), skipped where it cannot be.
"""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
    """The engine over ``sflm_gpt2m``'s configuration, and its operands
    as shapes on one described chip.  The engine itself is built over a
    one-slot pool and no weights: its jitted closures depend on ``cfg``
    alone, the shapes they are lowered for are the cell's.  The
    persistent cache is off (a described-topology executable cannot be
    read back without a chip)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from nnstreamer_tpu.llm.engine import DecodeEngine
    from nnstreamer_tpu.llm.pool import KVCachePool, dense_pool_shape
    from nnstreamer_tpu.models.streamformer_lm import config_from_custom
    from nnstreamer_tpu.parallel.train_step import init_params

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sflm_gpt2m.json")) as fh:
        config = json.load(fh)
    cfg = config_from_custom({k: str(v)
                              for k, v in config["model"].items()})
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda x: on_chip(*x.shape, dtype=x.dtype),
        jax.eval_shape(lambda: init_params(cfg, 0)))
    pool = on_chip(*dense_pool_shape(cfg, config["element"]["slots"]),
                   dtype=cfg.dtype)
    engine = DecodeEngine({}, cfg, KVCachePool(cfg, 1), capacity=1)
    yield {"engine": engine, "params": params, "pool": pool,
           "pool_bytes": 2 * pool.size * pool.dtype.itemsize,
           # a token a slot, sampled on the chip, beside the pools
           "sampled": on_chip(config["element"]["slots"] + 1),
           "on_chip": on_chip}
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _check(compiled, pool_bytes, temp_share):
    text = compiled.as_text()
    assert "remat_compressed" not in text
    assert "remat_uncompressed" not in text
    stats = compiled.memory_analysis()
    # both pools are donated and updated in place ...
    assert stats.alias_size_in_bytes >= pool_bytes
    # ... and nothing of the pool's size is held beside them
    assert stats.temp_size_in_bytes < pool_bytes * temp_share


def _compiled_step(d, lanes):
    """The engine's step at ``lanes`` lanes, compiled once a module."""
    steps = d.setdefault("steps", {})
    if lanes not in steps:
        vec = d["on_chip"](lanes)
        steps[lanes] = d["engine"]._step_fn(lanes).lower(
            d["params"], (d["pool"], d["pool"]), d["sampled"], vec,
            vec).compile()
    return steps[lanes]


@pytest.mark.parametrize("lanes", [1, 8, 32])
def test_decode_step_keeps_the_pool_still(described, lanes):
    _check(_compiled_step(described, lanes), described["pool_bytes"],
           1 / 5)                          # found: 0.04, 0.03, 0.09 GB


@pytest.mark.parametrize("lanes", [1, 8, 32])
def test_decode_step_reads_the_rows_as_they_lie(described, lanes):
    """The attention makes no float32 image of a lane's gathered rows:
    no ``f32[lanes, 1024, 1024]`` array anywhere in the compiled step
    and no float32 copy of that extent under another shape (there were
    48 ``copy f32[32,1024,1024]{1,2,0}`` in the 32-lane step, one a pool
    and layer, and 0.226 GB of temporaries; PERF.md section 6, PR 30)."""
    compiled = _compiled_step(described, lanes)
    text = compiled.as_text()
    assert f"f32[{lanes},1024,1024]" not in text
    assert not re.search(r"= f32\[[0-9,]*1024,1024\]\S* copy\(", text)
    if lanes == 32:
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.15e9                           # found: 0.089 GB


@pytest.mark.parametrize("padded_t", [64, 1024])
def test_dense_prefill_keeps_the_pool_still(described, padded_t):
    d = described
    compiled = d["engine"]._prefill_fn(padded_t).lower(
        d["params"], (d["pool"], d["pool"]), d["sampled"],
        d["on_chip"](padded_t), d["on_chip"](), d["on_chip"]()).compile()
    _check(compiled, d["pool_bytes"], 1 / 4)       # found: 0.04, 0.44 GB


# -- the second family, at Phi-4-mini-flash-reasoning's widths -----------
#: what the v5e compiler reports as usable ("Used ... of 15.75G hbm")
USABLE_BYTES = int(15.75 * 2 ** 30)


def _family_on_chip(described, name):
    """The engine over ``benchmarks/configs/<name>.json`` (a family
    behind ``arch:``): weights and pooled state as shapes on the
    described chip, the engine built over a small pool and no weights
    (its closures are made on first use, over ``cfg``)."""
    import jax

    from nnstreamer_tpu.llm.engine import DecodeEngine
    from nnstreamer_tpu.llm.family import family_of_custom
    from nnstreamer_tpu.llm.pool import KVCachePool

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           f"{name}.json")) as fh:
        config = json.load(fh)
    family, own = family_of_custom({k: str(v)
                                    for k, v in config["model"].items()})
    cfg = family.config_from_custom(own)
    on_chip = described["on_chip"]

    def shapes(make):
        return jax.tree_util.tree_map(
            lambda x: on_chip(*x.shape, dtype=x.dtype),
            jax.eval_shape(make))

    small = family.config_from_custom(dict(own, max_seq="512"))
    engine = DecodeEngine({}, small, KVCachePool(small, 1, family=family),
                          capacity=1)
    engine.cfg = cfg
    return {"engine": engine, "cfg": cfg, "config": config,
            "params": shapes(lambda: family.init_params(cfg, 0)),
            "state": shapes(lambda: family.init_state(
                cfg, config["element"]["slots"])),
            "sampled": on_chip(config["element"]["slots"] + 1),
            "on_chip": on_chip}


@pytest.fixture(scope="module")
def hybrid(topo, described):
    """``phi4_mini_flash`` (``arch:sambay_lm``): weights and the three
    kinds of pooled state (``described`` keeps the cache off), with the
    chip's branch of the decode step taken (``SHARED_KV_KERNEL``: the
    default backend is the CPU here): layer 17's rows are read by the
    kernel of ``ops/shared_kv_decode.py`` where they lie.  The prefill
    chunk has no such branch."""
    from nnstreamer_tpu.models import sambay_lm

    kernel, sambay_lm.SHARED_KV_KERNEL = sambay_lm.SHARED_KV_KERNEL, True
    yield _family_on_chip(described, "phi4_mini_flash")
    sambay_lm.SHARED_KV_KERNEL = kernel


def _resident(stats) -> int:
    return (stats.argument_size_in_bytes + stats.output_size_in_bytes
            - stats.alias_size_in_bytes + stats.temp_size_in_bytes)


#: a step's temporaries by lanes, bytes (found: 0.306, 0.037, 0.079 GB;
#: with XLA's gathered form 0.307 GB at one lane and 2.71 at 32).  At one
#: lane they are float32 images of a layer's SwiGLU weights, whatever
#: reads the cache
HYBRID_TEMP = {1: 0.35e9, 8: 0.1e9, 32: 0.1e9}


@pytest.mark.parametrize("lanes", sorted(HYBRID_TEMP))
def test_hybrid_decode_step_fits_the_chip_and_has_no_loop(hybrid, lanes):
    """The cell's step at 1, 8 and 32 lanes: weights + the three pools +
    the step's temporaries inside the chip; every pool updated in place;
    the ONE cached layer's rows read where they lie by eight calls of the
    kernel (the full layer and seven cross-attention layers), each handed
    both WHOLE pools: no ``copy`` of a pool's shape or of its one layer's,
    no gathered rows of every reserved position (``bf16[4096,128,1280]``
    at 32 lanes, 2.7 GB of temporaries with XLA's form) and no float32
    scores over them (``f32[32,40,16384]``); no ``while`` (a loop's device
    event would enclose its body's and count twice under
    ``unscoped:jit__step``)."""
    h = hybrid
    cfg, slots = h["cfg"], h["config"]["element"]["slots"]
    vec = h["on_chip"](lanes)
    compiled = h["engine"]._step_fn(lanes).lower(
        h["params"], h["state"], h["sampled"], vec, vec).compile()
    stats = compiled.memory_analysis()
    plan = h["config"]["memory_plan"]
    assert stats.argument_size_in_bytes >= (plan["weights_bytes"]
                                            + plan["pool_bytes"])
    assert stats.alias_size_in_bytes >= plan["pool_bytes"]
    assert stats.temp_size_in_bytes < HYBRID_TEMP[lanes]
    assert _resident(stats) < USABLE_BYTES
    assert _resident(stats) > 0.65 * 16e9
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 8
    assert "shared_kv_decode_attention" in text
    pool = f"1,{slots + 1},{cfg.max_seq},{cfg.kv_row}"
    one = f"{slots + 1},{cfg.max_seq},{cfg.kv_row}"
    assert f"bf16[{pool}]" in text
    assert not re.search(
        r"= bf16\[(%s|%s)\]\S* copy\(" % (pool, one), text)
    assert f"bf16[{lanes * 128},128,{cfg.kv_row}]" not in text
    assert f"bf16[{lanes},{cfg.max_seq},{cfg.kv_row}]" not in text
    assert f"f32[{lanes},{cfg.heads},{cfg.max_seq}]" not in text
    assert " while(" not in text and "remat_" not in text
    entry = text[text.index("ENTRY"):]
    assert "pad_maximum_fusion" not in entry


def test_hybrid_prefill_chunk_fits_beside_the_pools(hybrid):
    h = hybrid
    i32 = h["on_chip"]
    import jax.numpy as jnp

    compiled = h["engine"]._prefill_fn(h["cfg"].chunk).lower(
        h["params"], h["state"], h["sampled"], i32(h["cfg"].chunk), i32(),
        i32(), i32(), i32(dtype=jnp.bool_)).compile()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes >= h["config"]["memory_plan"][
        "pool_bytes"]
    assert stats.temp_size_in_bytes < 1.0e9            # found: 0.30 GB
    assert _resident(stats) < USABLE_BYTES


# -- the third family, at GigaChat3.1-702B-A36B's widths -----------------
@pytest.fixture(scope="module")
def latent(topo, described):
    """``gigachat31_702b_a36b`` (``arch:dsv3_lm``) with the chip's
    branch taken (``GROUPED_KERNEL``: the default backend is the CPU
    here): the decode attention is the kernel of
    ``ops/latent_decode.py`` over the pool where it lies."""
    from nnstreamer_tpu.models import dsv3_lm

    kernel, dsv3_lm.GROUPED_KERNEL = dsv3_lm.GROUPED_KERNEL, True
    yield _family_on_chip(described, "gigachat31_702b_a36b")
    dsv3_lm.GROUPED_KERNEL = kernel


@pytest.mark.parametrize("lanes", [1, 8, 64])
def test_latent_decode_step_attends_the_pool_where_it_lies(latent, lanes):
    """Every lane count runs the kernel: five custom calls beside the
    grouped products', each handed the WHOLE donated pool between one
    layer's scatter and the next's — and the pool stays still: aliased,
    no ``copy`` of its shape or of one layer's (a slice feeding a custom
    call is one), temporaries far under one layer's rows (0.68 GB), no
    float32 scores of every reserved position (the XLA form's ``f32[65,
    64,8192]``, 136 MB a layer)."""
    d = latent
    cfg, slots = d["cfg"], d["config"]["element"]["slots"]
    vec = d["on_chip"](lanes)
    compiled = d["engine"]._step_fn(lanes).lower(
        d["params"], d["state"], d["sampled"], vec, vec).compile()
    stats = compiled.memory_analysis()
    layer_rows = (slots + 1) * cfg.max_seq * cfg.row_held * 2
    assert layer_rows == 681574400
    assert stats.alias_size_in_bytes >= cfg.layers * layer_rows
    assert stats.temp_size_in_bytes < layer_rows / 4   # found: 0.01 GB
    assert _resident(stats) < USABLE_BYTES
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        >= cfg.layers + 2 * cfg.expert_layers
    assert "latent_decode_attention" in text
    pool = f"{cfg.layers},{slots + 1},{cfg.max_seq},{cfg.row_held}"
    one = f"{slots + 1},{cfg.max_seq},{cfg.row_held}"
    assert f"bf16[{pool}]" in text
    assert not re.search(
        r"= bf16\[(%s|%s)\]\S* copy\(" % (pool, one), text)
    assert "remat_" not in text
    assert f"f32[{slots + 1},{cfg.heads},{cfg.max_seq}]" not in text
    assert f"f32[{lanes},{cfg.heads},{cfg.max_seq}]" not in text
