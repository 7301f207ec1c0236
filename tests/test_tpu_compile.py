"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing runs: each test compiles one kernel, at the published widths that
``chip_smoke.py`` runs it at on the chip, with the installed TPU compiler
for a chip that is described and not attached. This catches what interpret
mode accepts and the chip refuses: blocks that break the (8, 128) tiling,
layouts Mosaic cannot match, primitives Mosaic cannot lower. Where no v5e
can be described, the tests skip.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke

CASES = {c.name: c for c in chip_smoke.kernel_cases()}


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
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_cases_cover_every_kernel_and_the_refused_widths():
    names = " ".join(CASES)
    for kernel in ("flash", "decode", "gmm", "ssd"):
        assert kernel in names
    assert {"decode qwen2.5-3b B4 T1024", "decode qwen2.5-3b B4 T2048",
            "gmm deepseek-moe-16b down"} <= set(CASES)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    case = CASES[name]
    shapes = jax.eval_shape(case.make,
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(functools.partial(case.kernel, interpret=False)
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
