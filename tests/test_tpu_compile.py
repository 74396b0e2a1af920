"""Ahead-of-time compiles for a described (not attached) TPU v5e.

The TPU compiler is installed with jax, so the main-path programs are
compiled here for one chip of a ``v5e:2x2`` topology — what Mosaic or XLA
refuses (an unaligned block, a primitive with no lowering, a program that
does not fit 16 GB) fails in this file instead of on the chip.  Nothing runs:
these tests say nothing about results or times.

The topology is described inside a module fixture (never at import: only one
process at a time may load the TPU library, and every test worker imports
this file), and the persistent compile cache is off around the compiles (an
entry written for a described device cannot be read back without one).
"""
import os

import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

D_WIDTHS = (3, 768)
N_KERNEL = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=False)
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text(), compiled.memory_analysis()


def _kernel_case(name, d, s):
    from repro.kernels.gmm_topb import gmm_topb_pallas
    from repro.kernels.gmm_update import (gmm_grouped_topb_pallas,
                                          gmm_update_select_pallas)
    from repro.kernels.pairwise import pairwise_pallas

    n = N_KERNEL
    f32 = jnp.float32
    if name == "gmm_update_select":
        return (lambda p, c, mi, mk: gmm_update_select_pallas(
            p, c, mi, mk, mode="euclidean", bn=1024, interpret=False),
            s((n, d)), s((1, d)), s((n,)), s((n,), jnp.bool_))
    if name == "gmm_topb":
        return (lambda p, c, mi, mk: gmm_topb_pallas(
            p, c, mi, mk, mode="cosine", bn=1024, p=128, interpret=False),
            s((n, d)), s((8, d)), s((n,)), s((n,), jnp.bool_))
    if name == "gmm_grouped_topb":
        return (lambda p, c, mi, lb: gmm_grouped_topb_pallas(
            p, c, mi, lb, mode="euclidean", bn=1024, b=8, interpret=False),
            s((n, d)), s((16, 8, d)), s((n,)), s((n,), jnp.int32))
    assert name == "pairwise"
    return (lambda x, y: pairwise_pallas(x, y, mode="euclidean", bm=256,
                                         bn=256, interpret=False),
            s((4096, d), f32), s((8192, d), f32))


@pytest.mark.parametrize("d", D_WIDTHS)
@pytest.mark.parametrize("name", ["gmm_update_select", "gmm_topb",
                                  "gmm_grouped_topb", "pairwise"])
def test_kernel_compiles_for_v5e(one_chip, no_cache, name, d):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, *args = _kernel_case(name, d, s)
    text, mem = _compile(fn, *args)
    assert "tpu_custom_call" in text
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


@pytest.mark.parametrize("use_pallas", [False, True])
def test_schedule_engine_compiles_for_v5e(one_chip, no_cache, monkeypatch,
                                          use_pallas):
    """The one selection engine behind every mode, at n=2^22, d=768.  The
    process runs on the CPU, where the kernels would resolve to the
    interpreter: force them to compile, as they do on the chip."""
    from repro.core.gmm import _schedule_select_impl

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")

    n, d = 1 << 22, 768
    pts = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    lab = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    starts = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    text, mem = _compile(
        lambda p, lb, st: _schedule_select_impl(
            p, lb, st, 1, 128, ((8, 16),), 4096, "euclidean", use_pallas),
        pts, lab, starts)
    assert ("tpu_custom_call" in text) == use_pallas
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_mapreduce_round1_compiles_for_v5e_mesh(topo, no_cache):
    """Round 1 of the plain MapReduce body on a 4-chip mesh, built the way
    ``core.distributed.mr_coreset`` builds it: per-shard selection, then one
    all_gather of the core-sets and a pmax of the radii."""
    from jax import shard_map

    from repro.core.distributed import _local_coreset_plain

    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    n, kprime = 1 << 26, 128

    def body(shard):
        pts, radius = _local_coreset_plain(shard, kprime, "euclidean", False,
                                           8, 0, ((8, 16),))
        return (jax.lax.all_gather(pts, ("data",), tiled=True),
                jax.lax.pmax(radius, ("data",)))

    fn = shard_map(body, mesh=mesh, in_specs=P(("data",)),
                   out_specs=(P(), P()), check_vma=False)
    x = jax.ShapeDtypeStruct((n, 3), jnp.float32,
                             sharding=NamedSharding(mesh, P("data")))
    text, mem = _compile(fn, x)
    assert "all-gather" in text
    assert mem.argument_size_in_bytes < n * 3 * 4      # a shard per device
