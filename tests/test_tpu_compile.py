"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
``v5e:2x2`` topology that is described and not attached, and refuses what the
chip would refuse — block shapes the lowering cannot tile, and programs that
do not fit the chip's memory. Interpret-mode kernel tests cannot see either.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compact import prefix_sum, stream_compact
from repro.kernels.segsum import segment_sum_sorted
from repro.stream.fused import DENSE_NODE_CAP, _batched_dense_warm_peel_jit

V5E_HBM_BYTES = 16 * 2**30
LANES = 1 << 23      # directed edge lanes of an RMAT scale-18 graph
SEGMENTS = 1 << 18   # its vertex count


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _fits_one_chip(compiled) -> None:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used} B of arguments and temporaries"


@pytest.mark.parametrize("op", ["segment_sum_sorted", "prefix_sum",
                                "stream_compact"])
def test_kernel_compiles_for_v5e(one_chip, op):
    if op == "segment_sum_sorted":
        compiled = _compile(
            lambda v, s: segment_sum_sorted(
                v, s, num_segments=SEGMENTS, interpret=False),
            one_chip, ((LANES,), jnp.float32), ((LANES,), jnp.int32))
    elif op == "prefix_sum":
        compiled = _compile(lambda x: prefix_sum(x, interpret=False),
                            one_chip, ((LANES,), jnp.int32))
    else:
        compiled = _compile(
            lambda v, live: stream_compact(
                v, live, out_size=LANES // 2, fill=SEGMENTS, interpret=False),
            one_chip, ((LANES, 2), jnp.int32), ((LANES,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_batched_dense_warm_peel_compiles_for_v5e(one_chip):
    t, v = 16, DENSE_NODE_CAP
    compiled = _compile(
        lambda adj, deg, ne, pm: _batched_dense_warm_peel_jit(
            adj, deg, ne, pm, eps=0.0),
        one_chip, ((t, v, v), jnp.float32), ((t, v), jnp.int32),
        ((t,), jnp.int32), ((t, v), jnp.bool_))
    _fits_one_chip(compiled)
