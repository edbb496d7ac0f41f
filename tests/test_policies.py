"""Sharding-policy rules checked against an AbstractMesh (no devices)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.distributed import sharding as shd


def _abstract_mesh(sizes, names):
    return AbstractMesh(sizes, names)


@pytest.fixture(scope="module")
def mesh():
    return _abstract_mesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def mp_mesh():
    return _abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def leaf(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_fsdp_tp_attention_specs(mesh):
    # wq [L, d, H*hd]: rows over data, cols (heads) over model
    s = shd.param_spec("blocks_attn_wq_w", leaf((28, 3584, 3584)), mesh,
                       "dense", "fsdp_tp")
    assert s == P(None, ("data",), "model")
    s = shd.param_spec("blocks_attn_wo_w", leaf((28, 3584, 3584)), mesh,
                       "dense", "fsdp_tp")
    assert s == P(None, "model", ("data",))


def test_fsdp_shards_largest_dim_over_all_axes(mesh):
    s = shd.param_spec("blocks_mlp_w_up_w", leaf((28, 3584, 18944)), mesh,
                       "dense", "fsdp")
    assert s == P(None, None, ("data", "model"))
    # embedding [vocab, d]
    s = shd.param_spec("embed_table", leaf((152064, 3584)), mesh, "dense",
                       "fsdp")
    assert s == P(("data", "model"), None)


def test_ep_dp_expert_stacks_over_model(mesh):
    s = shd.param_spec("blocks_moe_w_up", leaf((24, 32, 1024, 512)), mesh,
                       "moe", "ep_dp")
    assert s == P(None, "model", ("data",), None)


def test_fsdp_indivisible_falls_back(mesh):
    # 100 not divisible by 256 nor by 16 -> replicated
    s = shd.param_spec("blocks_mlp_w_up_w", leaf((2, 100, 100)), mesh,
                       "dense", "fsdp")
    assert s == P(None, None, None)


def test_batch_spec_uses_all_axes_under_fsdp(mesh):
    b = shd.batch_spec("tokens", leaf((256, 4096)), mesh, "fsdp")
    assert b == P(("data", "model"), None)
    # indivisible by 256 -> data only
    b = shd.batch_spec("tokens", leaf((32, 4096)), mesh, "fsdp")
    assert b == P(("data",), None)
    # fsdp_tp never uses the model axis for batch
    b = shd.batch_spec("tokens", leaf((256, 4096)), mesh, "fsdp_tp")
    assert b == P(("data",), None)


def test_multipod_adds_pod_axis(mp_mesh):
    b = shd.batch_spec("tokens", leaf((256, 4096)), mp_mesh, "fsdp_tp")
    assert b == P(("pod", "data"), None)
    s = shd.param_spec("blocks_attn_wq_w", leaf((28, 4096, 4096)), mp_mesh,
                       "dense", "fsdp_tp")
    assert s == P(None, ("pod", "data"), "model")


def test_cache_seq_over_model(mesh):
    s = shd.cache_spec("k", leaf((28, 128, 32768, 8, 128)), mesh, "fsdp_tp")
    assert s == P(None, ("data",), "model", None, None)
