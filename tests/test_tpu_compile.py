"""K1 compiles for a TPU v5e chip, described and not attached.

The TPU compiler is installed next to JAX, so a kernel can be compiled for a
described ``v5e:2x2`` topology without the chip. That catches what interpret
mode cannot: primitives Mosaic does not lower (an in-kernel scatter did not)
and operands that overflow fast memory (the scalar-prefetched column table,
sized by pool capacity, once outgrew SMEM). Capacities: 65,536 and
8,388,608, the oncology deployment's capacity at 1,048,576 seed agents.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import collision_force as k1

ADH = ((0.5, 0.1), (0.1, 0.7))
MAXB = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("adhesion", [None, ADH], ids=["repulsion", "adhesion"])
@pytest.mark.parametrize("capacity", [65_536, 8_388_608])
def test_k1_compiles_for_v5e(one_chip, capacity, adhesion):
    n_pad = k1.padded_size(capacity, MAXB)
    data = jax.ShapeDtypeStruct((8, n_pad), jnp.float32, sharding=one_chip)
    cols = jax.ShapeDtypeStruct((n_pad // k1.BLOCK, MAXB), jnp.int32,
                                sharding=one_chip)
    step = jax.jit(lambda d, c: k1.collision_force_kernel(
        d, c, k_rep=2.0, adhesion=adhesion, adhesion_band=0.4,
        interpret=False))
    compiled = step.lower(data, cols).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 8 * n_pad * 4

