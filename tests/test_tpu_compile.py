"""Compile-only guards: the prover's Pallas kernels at circuit size must be
accepted by the TPU compiler.

Interpret mode (the rest of the suite) checks the kernels' values but not
their tiling, their VMEM use or the integer widths Mosaic accepts.  These
tests compile each kernel for a described TPU v5e — no chip is attached and
nothing runs — at the sizes a 2^16-row circuit proves: ``permute`` over
2^16 states, the NTT of a 2^16-point batch (its fused early stages and each
late stage) and ``grand_product_ext`` over 2^16 rows.  The process-wide
``jax_enable_x64`` that ``repro.core.field`` sets is on, as it is in a
prove.

The topology is described inside a fixture of this file, never at import
time: only one process at a time may load the TPU library, and every test
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import field  # noqa: F401  (turns jax_enable_x64 on)
from repro.kernels.grand_product import ops as gp_ops
from repro.kernels.ntt import ops as ntt_ops
from repro.kernels.poseidon import ops as pos_ops

ROWS = 1 << 16
DEVICE_BYTES = 16 << 30          # HBM of one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shape, sharding):
    arg = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)
    compiled = jax.jit(fn).lower(arg).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Pallas kernel
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes + \
        mem.output_size_in_bytes
    assert used < DEVICE_BYTES
    return compiled


def test_permute_compiles_at_circuit_size(one_chip):
    _compile(lambda s: pos_ops.permute(s, interpret=False), (ROWS, 16),
             one_chip)


@pytest.mark.parametrize("inverse", [False, True])
def test_ntt_compiles_at_circuit_size(one_chip, inverse):
    compiled = _compile(
        lambda x: ntt_ops.ntt(x, inverse=inverse, interpret=False),
        (ntt_ops.BATCH_TILE, ROWS), one_chip)
    # one fused call for the stages inside a LOCAL-lane row, one per
    # later stage
    late = (ROWS // ntt_ops.LOCAL).bit_length() - 1
    assert compiled.as_text().count("tpu_custom_call") >= 1 + late


def test_grand_product_ext_compiles_at_circuit_size(one_chip):
    _compile(lambda x: gp_ops.grand_product_ext(x, interpret=False),
             (ROWS, 4), one_chip)
