"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: each test lowers a program at its production shape and
compiles it with the TPU compiler for a chip that is described, not
attached, which refuses what the chip would refuse (unaligned Pallas
blocks, types Mosaic lacks, programs that do not fit).  The topology is
described inside a fixture, never at import time, and the persistent
compilation cache is off around these compiles: an executable built for
a described chip cannot be read back here.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("num_bins,log2", [(4096, False), (32, True)],
                         ids=["4096-bins", "log2"])
def test_hist_kernel_compiles(one_chip, num_bins, log2):
    """The stats histogram kernel lowers through Mosaic under x64."""
    from repro.kernels.hist.hist import hist_counts

    fn = jax.jit(lambda v: hist_counts(v, num_bins=num_bins, log2=log2,
                                       interpret=False))
    compiled = fn.lower(_shape((1 << 16, 1), jnp.int32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dim,rows,points", [(2, 16, 1024), (3, 8, 1280)],
                         ids=["2d", "3d"])
def test_triangulator_compiles(one_chip, dim, rows, points):
    """RDG's f64 triangulator at the production row shapes fits a chip."""
    from repro.kernels.delaunay import batched_delaunay

    fn = jax.jit(lambda p, c: batched_delaunay(p, c, dim=dim))
    compiled = fn.lower(_shape((rows, points, dim), jnp.float64, one_chip),
                        _shape((rows,), jnp.int32, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_certificate_predicate_compiles(one_chip):
    """The GEOM_CERT circumsphere-in-box predicate, batched."""
    from repro.kernels.delaunay import circumsphere_in_box

    f64 = jnp.float64
    jax.jit(circumsphere_in_box).lower(
        _shape((4096, 3, 2), f64, one_chip), _shape((4096, 2), f64, one_chip),
        _shape((4096, 2), f64, one_chip)).compile()


def test_chunk_wave_step_compiles(topo):
    """One wave of a directed G(n, m) stream over 1024 PEs on a one-chip
    mesh, collective-free, in both forms: batched, and unbatched (the
    batch axis dropped in the program, as a batch-1 stream dispatches
    it).  m is cut to 2^20 (chunks of ~2^10 edges): the compile time
    grows with the chunk capacity, to about a minute for the README's
    2^30-edge stream."""
    from repro.analyze.hloscan import assert_communication_free
    from repro.api import GNM
    from repro.distrib import runtime

    plan = GNM(n=1 << 26, m=1 << 20, directed=True, seed=0).plan(1024)
    mesh = Mesh(np.array(topo.devices[:1]), ("pe",))
    ns = NamedSharding(mesh, PartitionSpec("pe"))
    tables = plan.input_arrays()
    for squeeze in (False, True):
        fn = runtime._wave_fn(plan, mesh, len(tables), squeeze)
        compiled = fn.lower(
            _shape((1, 1, 2), jnp.int32, ns), _shape((1, 1), jnp.bool_, ns),
            *(_shape(t.shape, t.dtype, ns) for t in tables)).compile()
        assert_communication_free(compiled)
        rows = (plan.capacity, 2) if squeeze else (1, plan.capacity, 2)
        assert compiled.out_info[0].shape == rows


def test_rmat_wave_step_compiles(topo):
    """One wave of the Graph 500 Toy stream (n = 2^26, m = 2^30, 1024
    chunks of 2^20 edge ids, one-chip mesh, batch 1), collective-free.
    The descent compares integers, with no emulated float64 and no int64
    remainder per edge and level: XLA's cost model counts about 8.3 k
    operations an edge and 0.5 MB of temporaries, and the bounds fail a
    descent on float64 uniforms with ``q % 2`` (55 k, 437 MB)."""
    from repro.analyze.hloscan import assert_communication_free
    from repro.api import RMAT
    from repro.distrib import runtime

    plan = RMAT(n=1 << 26, m=1 << 30, seed=0, chunks=1024).plan(1024)
    assert plan.capacity == 1 << 20
    mesh = Mesh(np.array(topo.devices[:1]), ("pe",))
    ns = NamedSharding(mesh, PartitionSpec("pe"))
    tables = plan.input_arrays()
    fn = runtime._wave_fn(plan, mesh, len(tables), True)
    compiled = fn.lower(
        _shape((1, 1, 2), jnp.int32, ns), _shape((1, 1), jnp.bool_, ns),
        *(_shape(t.shape, t.dtype, ns) for t in tables)).compile()
    assert_communication_free(compiled)
    assert compiled.out_info[0].shape == (plan.capacity, 2)
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["flops"] / plan.capacity < 20_000
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
