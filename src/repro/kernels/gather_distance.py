"""Pallas TPU kernel: fused neighbor gather + distance block for serving.

The multi-expansion beam search's per-step hot loop: given the ``E*R``
neighbor ids each query just expanded, gather their vectors and compute
the ``[Q, E*R]`` dissimilarity block.  One kernel body
(``_gather_ip_kernel``) serves all four public entries — f32 or int8
points, VMEM-resident (``gather_distance[_int8]``, shards that pass
``fits_vmem``) or left in HBM (``gather_distance[_int8]_hbm``,
``MemorySpace.ANY``).  Grid over query tiles; per step the kernel

  * takes the tile's ``[TQ, C]`` candidate ids as an SMEM block (DMA
    addresses are scalars),
  * brings the candidates' rows into a double-buffered VMEM scratch by
    per-row ``make_async_copy`` DMAs, 128 candidates per wave, the next
    wave in flight while the current one is reduced,
  * returns the inner products ``<query, row>``: f32 elementwise product
    + lane reduction, or exact int32 for int8 points against the query
    quantized with the packing's own scheme.

The wrappers apply the norm expansion with the PRECOMPUTED f32 point
norms (``metrics.point_norms``, computed before any downcast or
quantization) in the op order of the ``kernels.ref`` oracles, and write
+inf for ``-1``-padded ids.  The serving engine's kernel-path resolution
(``beam_search.resolve_kernel_path``) selects vmem vs hbm per shard size
on TPU; the XLA gather (``kernels.ref.gather_distance_ref``) is the CPU
path.  All four entries are interpret-mode tested against their oracles
on CPU and compiled for a described v5e by ``tests/test_tpu_compile.py``.

Mosaic limits that shape the kernel: a 32-bit row can be DMA'd alone,
but a packed (int8/bf16) array is tiled in 8-row groups whose rows share
32-bit words, so a packed candidate fetches its whole tile (1 KiB for
int8 at d=128) and its row is unpacked in registers; rows can only be
sliced out of arrays whose lane-padded width is 128 (d <= 128).

``gather_distance_int8`` is the scalar-quantized path (paper Sec. 6:
"quantized GEMM operations on scalar-quantized points"): int8 points +
per-point f32 scales packed by ``ServingIndex(dtype="int8")``; the 4x
smaller points block lets ``fits_vmem`` admit shards 4x larger before
HBM streaming is needed.

The VMEM points budget is configurable: ``fits_vmem(budget=...)`` per
call (``ServingIndex(vmem_budget=...)`` threads it through), or the
``PIPNN_VMEM_POINTS_BUDGET`` environment variable to override the
default globally.
"""
from __future__ import annotations

import functools
import logging
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.tiling import LANE, padded_bytes

logger = logging.getLogger(__name__)
_TQ = 8  # query rows per grid step (f32 sublane tile)
_CB = LANE  # candidate rows per DMA wave (one output lane tile)
# rows of one packed (int8/bf16) memory tile: the smallest row slice
# Mosaic can DMA out of a packed points array
_PACKED_TILE_ROWS = 8

# points bytes budget for auto-enabling the VMEM-resident kernel (leave
# headroom out of ~16 MB/core for the query/id/output tiles)
_VMEM_POINTS_BUDGET = 8 * 1024 * 1024


def vmem_points_budget() -> int:
    """The effective VMEM points budget in bytes: the
    ``PIPNN_VMEM_POINTS_BUDGET`` environment variable when set, else the
    8 MiB default.  Read per call so tests (and deployments sizing for a
    different accelerator generation) can adjust it without reimports.

    A malformed or negative override is IGNORED with a warning (a serving
    process must not crash at dispatch time over an env typo); ``0`` is a
    valid budget meaning "nothing fits" — it forces the HBM-streaming
    path wherever Pallas is requested."""
    env = os.environ.get("PIPNN_VMEM_POINTS_BUDGET", "")
    if not env:
        return _VMEM_POINTS_BUDGET
    try:
        value = int(env)
    except ValueError:
        logger.warning(
            "ignoring malformed PIPNN_VMEM_POINTS_BUDGET=%r "
            "(not an int); using the %d-byte default",
            env, _VMEM_POINTS_BUDGET)
        return _VMEM_POINTS_BUDGET
    if value < 0:
        logger.warning(
            "ignoring negative PIPNN_VMEM_POINTS_BUDGET=%d; "
            "using the %d-byte default", value, _VMEM_POINTS_BUDGET)
        return _VMEM_POINTS_BUDGET
    return value


def fits_vmem(points: jax.Array, *extras: jax.Array,
              budget: int | None = None) -> bool:
    """True when the points block (plus any ``extras`` that must ride along
    VMEM-resident, e.g. the int8 packing's per-point scales) fits the
    budget (``None``: ``vmem_points_budget()``).  The check is
    itemsize-aware, so an int8 serving copy gets 4x the f32 headroom: a
    shard that needed HBM streaming at f32 may serve fully VMEM-resident
    once scalar-quantized.

    Bytes are priced at the TPU-tile-padded footprint
    (``tiling.padded_bytes``): the kernels lane-pad d to 128 and
    sublane-pad n to the dtype tile before ``pallas_call``, so a narrow-d
    block occupies far more VMEM than ``size * itemsize`` suggests — a
    [262144, 8] f32 block is 8 MiB of payload but 128 MiB once lane-padded.
    Pricing the unpadded size here would admit shards that cannot compile
    on real hardware (the static contract checker in ``repro.analysis``
    verifies this predicate against total VMEM for exactly that reason)."""
    if budget is None:
        budget = vmem_points_budget()
    total = sum(padded_bytes(a.shape, a.dtype) for a in (points,) + extras)
    return total <= int(budget)


# ---------------------------------------------------------------------------
# The shared row-gather inner-product kernel
# ---------------------------------------------------------------------------

def _pad(x, axis, mult, value):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w, constant_values=value)


def _unpack_rows(words, sub, pack: int):
    """Recover each candidate's own row from the packed 32-bit words of
    its DMA'd tile.  ``words``: [_CB, dp] int32, word-row ``sub // pack``
    of the candidate's tile already selected; ``sub``: [_CB, 1] row index
    within the tile.  int8 rows come back sign-extended to int32, bf16
    rows as their exact f32 values."""
    k = sub % pack
    if pack == 4:
        return jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(words, 24 - 8 * k), jnp.int32(24))
    hi = jax.lax.shift_left(words, 16 - 16 * k) & jnp.int32(-65536)
    return jax.lax.bitcast_convert_type(hi, jnp.float32)


def _gather_ip_kernel(ids_ref, q_ref, pts_ref, o_ref, buf, sub_ref, sem, *,
                      pack: int):
    """One grid step: ``tq`` query rows x ``cp`` candidate ids (SMEM).

    The candidate rows arrive ``_CB`` at a time through per-row DMAs into
    a double-buffered VMEM scratch: while wave ``s`` is reduced, wave
    ``s+1``'s copies are in flight.  A 32-bit row is one DMA; a packed
    int8/bf16 row lives interleaved with its tile-mates in 32-bit words
    (Mosaic cannot slice a packed memref below its 8-row tile), so such
    a candidate fetches its whole 8-row tile and the row is unpacked in
    registers (``_unpack_rows``).  -1 ids fetch row 0; the caller masks
    them."""
    tq, cp = ids_ref.shape
    nb = o_ref.shape[0]
    tile = 1 if pack == 1 else _PACKED_TILE_ROWS
    wrows = 1 if pack == 1 else tile // pack     # word rows per DMA
    src = pts_ref if pack == 1 else pts_ref.bitcast(jnp.int32)

    def each_copy(t, j, slot, fn):
        def one(c, carry):
            sid = jnp.maximum(ids_ref[t, j * _CB + c], 0)
            copy = pltpu.make_async_copy(
                src.at[pl.ds(sid // tile * wrows, wrows), :],
                buf.at[slot, pl.ds(c * wrows, wrows), :],
                sem.at[slot])
            fn(c, sid, copy)
            return carry

        jax.lax.fori_loop(0, _CB, one, 0)

    def start(t, j, slot):
        def fn(c, sid, copy):
            copy.start()
            if pack > 1:
                sub_ref[slot, pl.ds(c, 1), :] = jnp.full(
                    (1, LANE), sid % tile, jnp.int32)
        each_copy(t, j, slot, fn)

    def wait(t, j, slot):
        each_copy(t, j, slot, lambda c, sid, copy: copy.wait())

    def row(t, carry):
        # wave (t, j) is number t * nb + j; waves alternate buffer slots.
        # j is static and selects a whole [tq, _CB] output tile, so each
        # store has one dynamic (sublane) offset — Mosaic refuses a row
        # store at a dynamic sublane into a block wider than one tile
        for j in range(nb):
            slot = (t * nb + j) % 2
            if j + 1 < nb:
                start(t, j + 1, 1 - slot)
            else:
                @pl.when(t + 1 < tq)
                def _prefetch_next_row():
                    start(t + 1, 0, 1 - slot)

            wait(t, j, slot)
            if pack == 1:
                g = buf[slot].astype(jnp.float32)           # [_CB, dp]
            else:
                sub = sub_ref[slot][:, :1]                  # [_CB, 1]
                words = buf[slot, pl.ds(0, _CB, stride=wrows), :]
                for w in range(1, wrows):
                    words = jnp.where(
                        sub // pack == w,
                        buf[slot, pl.ds(w, _CB, stride=wrows), :], words)
                g = _unpack_rows(words, sub, pack)
            q = q_ref[pl.ds(t, 1), :].astype(g.dtype)       # [1, dp]
            # elementwise product + lane reduction: the f32 oracle
            # (``ref.gather_distance_hbm_ref``) reduces the same padded
            # extent the same way; int32 accumulation is exact
            ip = jnp.sum(g * q, axis=-1)[None, :]           # [1, _CB]
            o_ref[j, pl.ds(t, 1), :] = ip
        return carry

    start(0, 0, 0)
    jax.lax.fori_loop(0, tq, row, 0)


def _gather_ip(points, queries, nbr_ids, *, hbm: bool, tq: int,
               interpret: bool, name: str) -> jax.Array:
    """``ip[q, c] = <queries[q], points[max(nbr_ids[q, c], 0)]>`` [Q, C]:
    f32 for float points (f32/bf16), exact int32 for int8 points with
    int32 (quantized) queries.  ``hbm`` leaves the points in HBM
    (``MemorySpace.ANY``); otherwise the whole block is VMEM-resident.
    ``name`` names the kernel's custom call, and so its ops in a trace,
    whatever the function that calls it is named."""
    nq, c = nbr_ids.shape
    pack = 4 // points.dtype.itemsize
    if pack > 1:
        points = _pad(points, 0, _PACKED_TILE_ROWS, 0)   # whole DMA tiles
    points = _pad(points, 1, LANE, 0)
    queries = _pad(_pad(queries, 0, tq, 0), 1, LANE, 0)
    nbr_ids = _pad(_pad(nbr_ids, 0, tq, -1), 1, _CB, -1)
    qp, dp = queries.shape
    cp = nbr_ids.shape[1]
    nb = cp // _CB
    wrows = 1 if pack == 1 else _PACKED_TILE_ROWS // pack
    acc = jnp.int32 if points.dtype == jnp.int8 else jnp.float32
    pts_spec = (pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY) if hbm
                else pl.BlockSpec(points.shape, lambda r: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_gather_ip_kernel, pack=pack),
        out_shape=jax.ShapeDtypeStruct((nb, qp, _CB), acc),
        grid=(qp // tq,),
        in_specs=[
            pl.BlockSpec((tq, cp), lambda r: (r, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tq, dp), lambda r: (r, 0)),
            pts_spec,
        ],
        out_specs=pl.BlockSpec((nb, tq, _CB), lambda r: (0, r, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, _CB * wrows, dp),
                       points.dtype if pack == 1 else jnp.int32),
            pltpu.VMEM((2, _CB, LANE), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
        name=name,
    )(nbr_ids, queries, points)
    return jnp.swapaxes(out, 0, 1).reshape(qp, cp)[:nq, :c]


def _f32_distance(ip, norms, queries, nbr_ids, metric):
    """The norm expansion around an f32 inner-product block, in the op
    order of ``ref.gather_distance_hbm_ref`` (lane-padded query norms)."""
    q32 = _pad(queries.astype(jnp.float32), 1, LANE, 0)
    n2 = norms.astype(jnp.float32)[jnp.maximum(nbr_ids, 0)]
    if metric == "mips":
        d = -ip
    elif metric == "cosine":
        qn = jnp.sqrt(jnp.sum(q32 * q32, axis=-1))
        d = 1.0 - ip / jnp.maximum(qn[:, None] * n2, 1e-30)
    else:
        q2 = jnp.sum(q32 * q32, axis=-1)
        d = jnp.maximum(q2[:, None] + n2 - 2.0 * ip, 0.0)
    return jnp.where(nbr_ids >= 0, d, jnp.inf)


def _int8_distance(points, scales, norms, queries, q_norms, nbr_ids, *,
                   metric, hbm, tq, interpret):
    """Quantized distance block: the query is quantized with the packing's
    own scheme, the kernel returns the exact int32 inner products, and
    the rescale + exact-norm expansion follow in the op order of
    ``ref.gather_distance_int8_core``."""
    if points.dtype != jnp.int8:
        raise TypeError("the int8 gather-distance kernels expect int8 points")
    q8, sq = _ref.quantize_symmetric(queries.astype(jnp.float32))
    ip = _gather_ip(points, q8.astype(jnp.int32), nbr_ids, hbm=hbm, tq=tq,
                    interpret=interpret,
                    name="gather_distance_int8_hbm" if hbm
                    else "gather_distance_int8")
    safe = jnp.maximum(nbr_ids, 0)
    sg = scales.astype(jnp.float32)[safe]
    n2 = norms.astype(jnp.float32)[safe]
    qa = q_norms.astype(jnp.float32)
    ipf = ip.astype(jnp.float32) * (sq[:, None] * sg)
    if metric == "mips":
        d = -ipf
    elif metric == "cosine":
        d = 1.0 - ipf / jnp.maximum(qa[:, None] * n2, 1e-30)
    else:
        d = jnp.maximum(qa[:, None] + n2 - 2.0 * ipf, 0.0)
    return jnp.where(nbr_ids >= 0, d, jnp.inf)


def _empty(nbr_ids):
    return jnp.full(nbr_ids.shape, jnp.inf, jnp.float32)


# ---------------------------------------------------------------------------
# Public entries: {f32, int8} x {VMEM-resident, HBM-streaming}
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "tq", "interpret"))
def gather_distance(
    points: jax.Array,   # [n, d] (f32 or downcast serving copy)
    norms: jax.Array,    # [n] f32 metric-dependent norms (metrics.point_norms)
    queries: jax.Array,  # [Q, d]
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
    tq: int = _TQ,
    interpret: bool = False,
) -> jax.Array:
    """Fused gather-distance block [Q, C] f32; +inf where ``nbr_ids < 0``,
    with the points block VMEM-resident.  Semantics of
    ``kernels.ref.gather_distance_ref``; bits identical to
    ``gather_distance_hbm`` (the same kernel body, only the points'
    memory space differs)."""
    if 0 in nbr_ids.shape:
        return _empty(nbr_ids)
    ip = _gather_ip(points, queries.astype(jnp.float32), nbr_ids, hbm=False,
                    tq=tq, interpret=interpret, name="gather_distance")
    return _f32_distance(ip, norms, queries, nbr_ids, metric)


@functools.partial(jax.jit, static_argnames=("metric", "tq", "interpret"))
def gather_distance_hbm(
    points: jax.Array,   # [n, d] (f32 or downcast serving copy) — stays in HBM
    norms: jax.Array,    # [n] f32 metric-dependent norms (metrics.point_norms)
    queries: jax.Array,  # [Q, d]
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
    tq: int = _TQ,
    interpret: bool = False,
) -> jax.Array:
    """HBM-streaming gather-distance block [Q, C] f32; +inf at ``-1`` ids.

    The over-VMEM-budget twin of ``gather_distance``: the points stay in
    ``MemorySpace.ANY`` (HBM) and only the candidates' rows move, by
    double-buffered per-row DMAs.  Bit-identical in interpret mode to
    ``kernels.ref.gather_distance_hbm_ref``, which reduces the same
    lane-padded extent in the same order."""
    if 0 in nbr_ids.shape:
        return _empty(nbr_ids)
    ip = _gather_ip(points, queries.astype(jnp.float32), nbr_ids, hbm=True,
                    tq=tq, interpret=interpret, name="gather_distance_hbm")
    return _f32_distance(ip, norms, queries, nbr_ids, metric)


@functools.partial(jax.jit, static_argnames=("metric", "tq", "interpret"))
def gather_distance_int8(
    points: jax.Array,   # [n, d] int8 (quantize_symmetric packing)
    scales: jax.Array,   # [n] f32 per-point dequantization scales
    norms: jax.Array,    # [n] f32 EXACT norms (computed pre-quantization)
    queries: jax.Array,  # [Q, d] f32
    q_norms: jax.Array,  # [Q] f32 query norm terms (metrics.point_norms)
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
    tq: int = _TQ,
    interpret: bool = False,
) -> jax.Array:
    """Quantized fused gather-distance block [Q, C] f32 (+inf at pads),
    int8 points VMEM-resident at 1/4 the f32 footprint.  The query is
    quantized with the packing's own symmetric scheme, the inner product
    is exact in int32, and both norm halves stay full-precision.
    Bit-identical in interpret mode to ``kernels.ref.
    gather_distance_int8_ref``."""
    if 0 in nbr_ids.shape:
        return _empty(nbr_ids)
    return _int8_distance(points, scales, norms, queries, q_norms, nbr_ids,
                          metric=metric, hbm=False, tq=tq,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("metric", "tq", "interpret"))
def gather_distance_int8_hbm(
    points: jax.Array,   # [n, d] int8 (quantize_symmetric packing) — in HBM
    scales: jax.Array,   # [n] f32 per-point dequantization scales
    norms: jax.Array,    # [n] f32 EXACT norms (computed pre-quantization)
    queries: jax.Array,  # [Q, d] f32
    q_norms: jax.Array,  # [Q] f32 query norm terms (metrics.point_norms)
    nbr_ids: jax.Array,  # [Q, C] int32, -1 = padding
    *,
    metric: str = "l2",
    tq: int = _TQ,
    interpret: bool = False,
) -> jax.Array:
    """HBM-streaming quantized gather-distance block [Q, C] f32: int8
    points stay in HBM and each candidate's 8-row int8 tile is DMA'd
    (``_gather_ip_kernel``).  Shares ``kernels.ref.
    gather_distance_int8_ref`` with the VMEM-resident twin, bit-for-bit
    in interpret mode."""
    if 0 in nbr_ids.shape:
        return _empty(nbr_ids)
    return _int8_distance(points, scales, norms, queries, q_norms, nbr_ids,
                          metric=metric, hbm=True, tq=tq,
                          interpret=interpret)
