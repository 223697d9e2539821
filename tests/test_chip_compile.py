"""Compile the main path's programs for a TPU v5e chip, without one.

The TPU compiler is installed here and compiles for a described
`v5e:2x2` topology: what it refuses (a tile the kernel may not use,
more VMEM than allowed, a program past the chip's 16 GiB) fails here
at no chip time. Nothing runs, so these tests say nothing about
results or speed. Shapes are the flagship's: Prio3SumVec length 1000,
bits 16, at jit bucket 512 (one 500-report aggregation job).

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu.
"""

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from janus_tpu.aggregator import engine_cache as ec
from janus_tpu.ops import expand_pallas, keccak_pallas
from janus_tpu.vdaf.registry import VdafInstance
from janus_tpu.vdaf.testing import zero_report_batch

INST = VdafInstance.sum_vec(length=1000, bits=16)
JOB = 500  # rows of one job; the engine pads them to bucket 512
HBM_BYTES = 16 << 30
# the kernel shapes the bucket-512 leader program calls
SINGLE_BLOCK_ROWS = 8936
EXPAND_GEOMETRY = (5, 504, 18, 128)  # prefix lanes, padded reports, blocks, tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / lock held: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tpu_compile(one_chip):
    """compile(fn, *args) for the described chip, with the Pallas
    kernels on and the persistent compile cache off (a TPU program
    written there could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = bool(jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(keccak_pallas, "_mode", lambda: "tpu")

        def compile_(fn, *args):
            shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=one_chip),
                args,
            )
            return jax.jit(fn).lower(*shapes).compile()

        yield compile_
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _fits_and_has_kernel(compiled):
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
    assert used < HBM_BYTES, used
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def engine_programs():
    """The init programs the serving engine jits for one job
    (EngineCache.init_step), with zero arguments at its bucket."""
    eng = ec.EngineCache(INST, bytes(range(16)))
    b = ec.bucket_size(JOB)
    nonce, parts, meas, proof, blind0, hseed, blind1 = zero_report_batch(INST, b)
    leader = eng.init_step("leader_init")
    leader_args = (nonce, parts, meas, proof, blind0)
    _, _, ver0, part0 = jax.eval_shape(leader, *leader_args)
    ver0, part0 = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype), (ver0, part0))
    helper_args = (nonce, parts, hseed, blind1, ver0, part0, np.ones(b, dtype=bool))
    return {
        "leader_init": (leader, leader_args),
        "helper_init": (eng.init_step("helper_init"), helper_args),
    }


@pytest.mark.parametrize("op", ["leader_init", "helper_init"])
def test_init_program_compiles_for_v5e(tpu_compile, engine_programs, op):
    fn, args = engine_programs[op]
    _fits_and_has_kernel(tpu_compile(fn, *args))


def test_keccak_permutation_kernel_compiles_for_v5e(tpu_compile):
    kernel = keccak_pallas._call(SINGLE_BLOCK_ROWS, False)
    _fits_and_has_kernel(
        tpu_compile(kernel, np.zeros((50, SINGLE_BLOCK_ROWS, 128), np.uint32))
    )


def test_keccak_single_block_kernel_compiles_for_v5e(tpu_compile):
    kernel = keccak_pallas._call_single(SINGLE_BLOCK_ROWS, False, 2)
    _fits_and_has_kernel(
        tpu_compile(kernel, np.zeros((42, SINGLE_BLOCK_ROWS, 128), np.uint32))
    )


def test_expand_kernel_compiles_for_v5e(tpu_compile):
    p_lanes, b8, nb, tile = EXPAND_GEOMETRY
    kernel = expand_pallas._call(p_lanes, b8, nb, tile, False)
    _fits_and_has_kernel(
        tpu_compile(kernel, np.zeros((1,), np.int32), np.zeros((b8, 128), np.uint32))
    )
