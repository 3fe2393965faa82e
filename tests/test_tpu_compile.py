"""Main-path Pallas kernels compile for a TPU v5e at the shapes the chip runs.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
scalar stores to VMEM, node-batched dot_generals, tiles that are not
aligned. These cases hand each kernel to the TPU compiler for a v5e that is
described, not attached, and check that the compiled program holds the
kernel (`tpu_custom_call`). The topology is described inside a fixture,
never at import: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.mixing import schedule
from repro.kernels.consensus import gossip_mix_pallas, gossip_mix_quant_pallas
from repro.kernels.krasulina_update import (krasulina_xi_gossip_pallas,
                                            krasulina_xi_pallas)

N = 16
D_GOSSIP = 1 << 20  # a packed gradient buffer of 2^20 columns per node
D_PCA = 3072  # configs/paper_pca.py:HIGHD
RING = schedule("ring", N)
SHIFTS = tuple(s for s, _ in RING)
WEIGHTS = tuple(w for _, w in RING)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but not read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def test_gossip_mix_pallas_compiles(one_chip):
    hlo = _compile(lambda x: gossip_mix_pallas(x, SHIFTS, WEIGHTS, 4,
                                               interpret=False),
                   _spec(one_chip, N, D_GOSSIP))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("quant", ["sign", "int8"])
def test_gossip_mix_quant_pallas_compiles(one_chip, quant):
    hlo = _compile(lambda x: gossip_mix_quant_pallas(
        x, SHIFTS, WEIGHTS, 4, quant, interpret=False),
        _spec(one_chip, N, D_GOSSIP))
    assert "tpu_custom_call" in hlo


def test_krasulina_xi_pallas_compiles(one_chip):
    hlo = _compile(lambda w, z: krasulina_xi_pallas(w, z, interpret=False),
                   _spec(one_chip, D_PCA), _spec(one_chip, 4096, D_PCA))
    assert "tpu_custom_call" in hlo


def test_krasulina_xi_gossip_pallas_compiles(one_chip):
    hlo = _compile(lambda w, z: krasulina_xi_gossip_pallas(
        w, z, SHIFTS, WEIGHTS, 4, interpret=False),
        _spec(one_chip, N, D_PCA), _spec(one_chip, N, 16, D_PCA))
    assert "tpu_custom_call" in hlo
