"""Pallas TPU kernel: fused residual-hash bit packing for candidate edges.

Given pre-gathered sketch rows for each candidate edge (src = owning point
p, dst = candidate c), computes the paper's Eq. 1 hash

    h_p(c) = pack_bits( sign(Sketch(c) - Sketch(p)) )

in one VPU pass: subtract, threshold, weighted-sum with powers of two.
Edges arrive as [_BLOCK, m] row blocks and leave as one lane-dense
[1, _BLOCK] hash row per grid step; m <= 16 bits pack into an int32
(stored alongside the 8-byte reservoir slot layout the paper describes).

Wired into both PiPNN build paths via ``sketch.edge_hashes_from_ids`` when
``PiPNNParams.use_pallas_hash`` is set.  The auto default is the fused jnp
``hash_from_sketches`` on every backend: this kernel needs its [E, m]
inputs materialized (lane-padded) in HBM, which does not fit a chip at a
SIFT1M stream chunk (``pipnn._resolve_pallas``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK = 1024  # edges per grid step (8 lane tiles)


def _edge_hash_kernel(src_ref, dst_ref, o_ref):
    s = src_ref[...]                            # [_BLOCK, m]
    t = dst_ref[...]
    bits = ((t - s) >= 0.0).astype(jnp.int32)   # [_BLOCK, m]
    weights = jax.lax.shift_left(
        jnp.int32(1), jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1))
    o_ref[...] = jnp.sum(bits * weights, axis=1)[None, :]   # [1, _BLOCK]


@functools.partial(jax.jit, static_argnames=("interpret",))
def edge_hashes(
    src_sketch: jax.Array,   # [E, m] sketches of edge sources (owning points)
    dst_sketch: jax.Array,   # [E, m] sketches of edge destinations
    *,
    interpret: bool = False,
) -> jax.Array:
    """Packed residual hashes [E] int32.  The hashes leave the kernel as
    one lane-dense [1, E] row (a [1, _BLOCK] block per grid step)."""
    e, m = src_sketch.shape
    if m > 16:
        raise ValueError(f"m={m} hash bits do not pack into the paper's "
                         "16-bit reservoir slot")
    if e == 0:
        return jnp.zeros((0,), jnp.int32)
    pad = (-e) % _BLOCK
    if pad:
        src_sketch = jnp.pad(src_sketch, ((0, pad), (0, 0)))
        dst_sketch = jnp.pad(dst_sketch, ((0, pad), (0, 0)))
    ep = src_sketch.shape[0]
    out = pl.pallas_call(
        _edge_hash_kernel,
        out_shape=jax.ShapeDtypeStruct((1, ep), jnp.int32),
        grid=(ep // _BLOCK,),
        in_specs=[
            pl.BlockSpec((_BLOCK, m), lambda r: (r, 0)),
            pl.BlockSpec((_BLOCK, m), lambda r: (r, 0)),
        ],
        out_specs=pl.BlockSpec((1, _BLOCK), lambda r: (0, r)),
        interpret=interpret,
    )(src_sketch, dst_sketch)
    return out[0, :e]
