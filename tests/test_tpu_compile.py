"""Compiles for a described (not attached) TPU v5e: what the chip's
compiler would refuse fails here, at no chip time.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and the suite runs
under several workers. Keep every such compile in this one file. The
persistent compile cache is off around these compiles: a TPU entry
written here cannot be read back without a chip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_step(step, state, sharding):
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        state)
    stop = jax.ShapeDtypeStruct((), jnp.int64, sharding=sharding)
    return jax.jit(step).lower(abstract, stop).compile()


def test_phold_window_step_compiles(one_chip):
    from shadow_tpu.models import phold

    eng, init = phold.build(256, capacity=64, msgs_per_host=8, seed=1234)
    c = _compile_step(eng.step_window, jax.eval_shape(init), one_chip)
    assert c.memory_analysis().argument_size_in_bytes > 0


def test_tgen_test_config_step_compiles(one_chip):
    from shadow_tpu.config import parse_config
    from shadow_tpu.examples import example_config
    from shadow_tpu.sim import build_simulation

    sim = build_simulation(parse_config(example_config()), seed=1)
    c = _compile_step(lambda st, stop: sim.engine.step_window(st, stop),
                      sim.state0, one_chip)
    assert c.memory_analysis().argument_size_in_bytes > 0


def test_pallas_merge_refused_by_mosaic(one_chip):
    """The fused merge kernel does not compile for the chip; ROADMAP C2
    decides whether to rewrite it or delete it. Until then the engine
    refuses kernel="pallas" off the CPU with these same reasons."""
    from shadow_tpu.core.merge_pallas import MOSAIC_REFUSAL, fused_merge

    h, hc, w, nw = 256, 64, 8, 6

    def spec(shape, dtype=jnp.int64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (spec((h, hc)), spec((h, hc)), spec((h, hc, nw)),
            spec((h * w,)), spec((h * w,)), spec((h, w, nw)),
            spec((h,), jnp.int32), spec((h,), jnp.int32))
    with pytest.raises(Exception) as ei:
        jax.jit(lambda *a: fused_merge(*a, interpret=False)).lower(
            *args).compile()
    msg = str(ei.value)
    assert ("Only 2D gather is supported" in msg
            or "64-bit types are not supported" in msg), msg[:2000]
    assert "64-bit types" in MOSAIC_REFUSAL and "2-D" in MOSAIC_REFUSAL
