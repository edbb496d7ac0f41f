"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers and compiles for a described (not
attached) ``v5e:2x2`` topology, so the chip's compiler refuses here what
interpret mode cannot see — unaligned slices, unsupported primitives,
blocks off the (8, 128) tiling — and each compiled program must hold the
kernel (``tpu_custom_call``).  The topology is described inside a fixture
(only one process may load the TPU library, so never at import), and the
persistent compile cache is off around these compiles: an entry written
for a described chip cannot be read back without one.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D = 128
N_HBM = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU library
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _max_vmem_n(dtype, with_scales: bool) -> int:
    """The largest point count ``fits_vmem`` admits at d=128."""
    from repro.kernels.gather_distance import fits_vmem

    def fits(n):
        pts = jax.ShapeDtypeStruct((n, D), dtype)
        extra = (jax.ShapeDtypeStruct((n,), jnp.float32),) \
            if with_scales else ()
        return fits_vmem(pts, *extra)

    lo, hi = 1, 1 << 24
    while lo + 1 < hi:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if fits(mid) else (lo, mid)
    return lo


def _gd():
    return importlib.import_module("repro.kernels.gather_distance")


@pytest.mark.parametrize("where", ["vmem", "hbm"])
def test_gather_distance_f32_compiles(one_chip, where):
    gd = _gd()
    n = _max_vmem_n(jnp.float32, False) if where == "vmem" else N_HBM
    fn = gd.gather_distance if where == "vmem" else gd.gather_distance_hbm
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    txt = _compiled_text(
        lambda p, nn, q, i: fn(p, nn, q, i),
        s((n, D), jnp.float32), s((n,), jnp.float32),
        s((64, D), jnp.float32), s((64, 256), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("where", ["vmem", "hbm"])
def test_gather_distance_int8_compiles(one_chip, where):
    gd = _gd()
    n = _max_vmem_n(jnp.int8, True) if where == "vmem" else N_HBM
    fn = gd.gather_distance_int8 if where == "vmem" else \
        gd.gather_distance_int8_hbm
    s = lambda shape, dt: _sds(one_chip, shape, dt)
    txt = _compiled_text(
        lambda p, sc, nn, q, qa, i: fn(p, sc, nn, q, qa, i),
        s((n, D), jnp.int8), s((n,), jnp.float32), s((n,), jnp.float32),
        s((64, D), jnp.float32), s((64,), jnp.float32),
        s((64, 256), jnp.int32))
    assert "tpu_custom_call" in txt


def test_merge_sorted_reservoirs_compiles(one_chip):
    from repro.kernels.segmented_merge import merge_sorted_reservoirs

    n, l = 65536, 64
    ids = _sds(one_chip, (n, l), jnp.int32)
    ds = _sds(one_chip, (n, l), jnp.float32)
    txt = _compiled_text(lambda *a: merge_sorted_reservoirs(*a),
                         ids, ids, ds, ids, ids, ds)
    assert "tpu_custom_call" in txt


def test_edge_hashes_compiles(one_chip):
    from repro.kernels.edge_hash import edge_hashes

    sk = _sds(one_chip, (1 << 20, 12), jnp.float32)
    txt = _compiled_text(lambda s, t: edge_hashes(s, t), sk, sk)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("dtype", ["f32", "int8"])
def test_beam_search_engine_hbm_compiles(one_chip, dtype):
    """One serving dispatch as ``ServeLoop`` issues it over a 1M-point
    shard: 32 queries, beam 128, E=4 over R=64 neighbors."""
    from repro.core.beam_search import _beam_search_multi

    s = lambda shape, dt: _sds(one_chip, shape, dt)
    n, r = 1_000_000, 64
    pts = s((n, D), jnp.int8 if dtype == "int8" else jnp.float32)
    scales = s((n,), jnp.float32) if dtype == "int8" else None
    compiled = _beam_search_multi.lower(
        s((n, r), jnp.int32), pts, s((n,), jnp.float32),
        s((32, D), jnp.float32), s((), jnp.int32), scales,
        beam=128, iters=66, metric="l2", expansions=4, early_exit=True,
        kernel_path="hbm", interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
