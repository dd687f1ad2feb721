"""``dp_aggregate`` compiles for a described TPU v5e at the widths it serves.

Interpret mode cannot see what Mosaic refuses (1-D row vectors it cannot
relayout, uint32 -> float32 casts, tiles over the scoped VMEM limit), so
each case lowers the public wrapper with ``interpret=False`` and compiles it
for one chip of a described ``v5e:2x2`` topology.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dp_aggregate.kernel import KERNEL_NAME, NOISE_KERNEL_NAME
from repro.kernels.dp_aggregate.ops import dp_aggregate, generate_ldp_noise

# (M, d): the paper's CDP CNN (d = 5,046 -> 5,120 lanes) and LDP CNN
# (d = 237 -> 256) over M = 1000 clients, and the e7 engine geometry.
WIDTHS = [(1000, 5046), (1000, 237), (300, 4096)]
MODES = ["none", "operand", "fused", "noise"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _lowered(mode, m, d, sharding):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    u, scalar, key = sds((m, d)), sds(()), sds((2,), jnp.uint32)
    if mode == "noise":
        return jax.jit(lambda k, s: generate_ldp_noise(
            m, d, k, s, interpret=False)).lower(key, scalar)
    if mode == "operand":
        return jax.jit(lambda x, n, c: dp_aggregate(
            x, c, n, interpret=False).cbar).lower(u, u, scalar)
    if mode == "fused":
        return jax.jit(lambda x, c, k, s: dp_aggregate(
            x, c, noise_key=k, noise_sigma=s, interpret=False).cbar).lower(
                u, scalar, key, scalar)
    return jax.jit(lambda x, c: dp_aggregate(
        x, c, interpret=False).cbar).lower(u, scalar)


def _kernel_lines(text: str, name: str) -> list[str]:
    """The compiled HLO's Mosaic calls whose instruction is ``name.N``."""
    return [line for line in text.splitlines()
            if re.match(rf"\s*(ROOT )?%{name}(\.\d+)? = ", line)
            and 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("m,d", WIDTHS, ids=[f"{m}x{d}" for m, d in WIDTHS])
@pytest.mark.parametrize("mode", MODES)
def test_dp_aggregate_compiles_for_v5e(one_chip, mode, m, d):
    text = _lowered(mode, m, d, one_chip).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel keeps its stable name through compilation, so a profiler
    # trace's device op is found by name, not as "the only Mosaic call"
    name = NOISE_KERNEL_NAME if mode == "noise" else KERNEL_NAME
    assert len(_kernel_lines(text, name)) == 1, name
