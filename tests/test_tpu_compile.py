"""Compile rehearsals for the TPU v5e: the main path's device programs at
real size, compiled for a described ``v5e:2x2`` topology with no chip
attached.

What the chip's compiler refuses here would fail on the chip, so these
tests guard every change at no chip time.  They run nothing: results and
times come only from a run on the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU's library, and the test workers
all import this file.  The persistent compilation cache is off around
these compiles, since an entry written for a described chip cannot be read
back without one.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import DesignSpace, LSMSystem
from repro.core import batch
from repro.kernels.dual_solve.kernel import LANE_TILE, dual_solve_warm_kernel
from repro.kernels.merge.kernel import two_way_merge_kernel
from repro.kernels.point_read.kernel import point_read_level_kernel

# The robust grid of bench_robust_vs_nominal.py: 15 expected workloads x
# 5 rhos, CLASSIC (2 x 64 starts folded), 250 Adam steps.
GRID = dict(design=DesignSpace.CLASSIC, sys=LSMSystem(), n_starts=64,
            steps=250, lr=0.25)
P_ROBUST = 75
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _key_shape(sharding):
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=sharding)


def _compile_grid(P, robust, in_sharding, key_sharding):
    W = jax.ShapeDtypeStruct((P, 4), jnp.float32, sharding=in_sharding)
    rhos = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=in_sharding)
    return batch._solve_many.lower(
        _key_shape(key_sharding), W, rhos, GRID["design"], GRID["sys"],
        GRID["n_starts"], GRID["steps"], GRID["lr"], robust).compile()


@pytest.mark.parametrize("robust,P", [(True, P_ROBUST), (False, 15)])
def test_tuner_grid_compiles_for_v5e(one_chip, robust, P):
    compiled = _compile_grid(P, robust, one_chip, one_chip)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes < HBM_BYTES


def test_sharded_tuner_grid_compiles_for_v5e_2x2(topo):
    """The sharded backend's program: the problem axis, padded to a
    multiple of 4, split over the four chips of one host."""
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(topo.devices), ("problem",))
    P = P_ROBUST + (-P_ROBUST) % len(topo.devices)
    compiled = _compile_grid(P, True, NamedSharding(mesh, PartitionSpec(
        "problem")), NamedSharding(mesh, PartitionSpec()))
    for s in compiled.output_shardings:
        assert s.spec == PartitionSpec("problem")


def test_dual_solve_kernel_compiles_for_v5e(one_chip):
    """The lane-tiled Pallas dual solve at the grid's lane count
    (75 problems x 128 starts, 128-lane tiles) lowers through Mosaic."""
    L = P_ROBUST * 2 * GRID["n_starts"]
    assert L % LANE_TILE == 0
    lanes = jax.ShapeDtypeStruct((L,), jnp.float32, sharding=one_chip)
    mat = jax.ShapeDtypeStruct((L, 4), jnp.float32, sharding=one_chip)
    compiled = dual_solve_warm_kernel.lower(
        mat, mat, lanes, lanes, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _u64(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint64, sharding=sharding)


def _i64(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int64, sharding=sharding)


def test_point_read_kernel_refused_for_v5e(one_chip):
    """The point-read kernel carries uint64 keys and int64 values, which
    Mosaic cannot lower (a uint32-limb port is still to come)."""
    fn = jax.jit(lambda k, ak, av, w: point_read_level_kernel(
        k, ak, av, w, starts=(0, 2048, 4096), n_bits=(16384, 16384),
        ks=(4, 4), fence_lo=(0, 0), fence_hi=(2 ** 60, 2 ** 60),
        interpret=False))
    # splitmix64's 64-bit constants do not fit a Mosaic integer attribute
    with jax.enable_x64(True), pytest.raises(TypeError):
        fn.lower(_u64((1024,), one_chip), _u64((4096,), one_chip),
                 _i64((4096,), one_chip), _u64((2, 256), one_chip)).compile()


def test_merge_kernel_refused_for_v5e(one_chip):
    """Same 64-bit refusal for the two-way merge kernel."""
    fn = jax.jit(lambda a, av, b, bv: two_way_merge_kernel(
        a, av, b, bv, interpret=False))
    with jax.enable_x64(True), \
            pytest.raises(Exception, match="64-bit types are not supported"):
        fn.lower(_u64((4096,), one_chip), _i64((4096,), one_chip),
                 _u64((4096,), one_chip), _i64((4096,), one_chip)).compile()
