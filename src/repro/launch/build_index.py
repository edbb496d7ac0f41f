"""Distributed PiPNN index build — the paper's technique as a static,
multi-pod SPMD program (DESIGN.md §4, and the paper's §6 future-work item:
"PiPNN's approach is a natural fit for distributed data processing").

The build is a bulk-synchronous pipeline of two jitted supersteps, each
expressed with ``jax.shard_map`` + explicit ``all_to_all`` routing so the
dry-run compiles the EXACT collective schedule a 512-chip run would use:

  tile step (``make_tile_step``), per 2^24-point tile:
    1. local sketches + level-0 leader GEMM -> top-f0 bucket ids   [local]
    2. capacity-routed all_to_all: point replicas -> bucket owners [A2A #1]
    3. level-1 leader GEMM + top-f1 -> leaf grouping               [local]
    4. batched leaf all-pairs GEMM + top-k -> bidirected edges     [local]
    5. capacity-routed all_to_all: edges -> src owner              [A2A #2]
    6. HashPrune closed form + reservoir merge (Thm 3.1 licenses
       the per-tile streaming — mergeability)                      [local]

  final prune step (``make_final_prune_step``):
    7. request/response all_to_all for candidate vectors           [A2A #3,4]
    8. batched RobustPrune over each reservoir                     [local]

Everything is static-shape: routing uses MoE-style per-destination
capacities with slack; overflow is dropped (counted in stats).  The same
code runs on 1 CPU device (S=1 collectives are identity) — tests compare
its output quality against the host-orchestrated build.

Variants (the §Perf hillclimb knobs for the paper's own workload):
  * ``baseline``  — f32 vectors routed, f32 leaf GEMM (paper-faithful).
  * ``quantized`` — int8 vectors + f32 scale routed (4x less wire), int8
    leaf GEMM with i32 accumulation (paper §6 future-work, realized).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import sketch as _sketch
from repro.core.hashprune import (INVALID_ID, Reservoir, merge_flat_edges,
                                  merge_segmented_edges, reservoir_init)
from repro.core.leader_assign import leader_assign
from repro.core.metrics import GEMM_PRECISION
from repro.core.robust_prune import prune_reservoir_block
from repro.distributed.routing import group_by_capacity

INF = jnp.float32(jnp.inf)


# ---------------------------------------------------------------------------
# Static configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DistBuildParams:
    dim: int = 128
    n_tile: int = 1 << 24        # points per superstep tile
    m_bits: int = 12
    l0: int = 1024               # level-0 leaders (global, paper cap 1000)
    f0: int = 10                 # top-level fanout      (paper Sec. 4.1)
    l1: int = 1152               # level-1 leaders per bucket (sized so the
    #                              target leaf fill is ~55%: skewed leaves
    #                              stay under the hard c_max cap)
    f1: int = 3                  # second-level fanout   (paper: ~3)
    c_max: int = 1024            # leaf size cap
    k: int = 2                   # leaf k-NN (paper default, Fig. 11)
    l_max: int = 64              # HashPrune reservoir
    max_deg: int = 64
    alpha: float = 1.44          # RobustPrune alpha^2 (squared-l2 space)
    bucket_slack: float = 1.3
    leaf_slack: float = 1.0      # leaves already have c_max as the hard cap
    edge_slack: float = 1.3
    assign_chunk: int = 2048     # level-1 GEMM chunk rows
    leaf_chunk: int = 8          # leaves per batched GEMM launch
    prune_chunk: int = 2048
    route_dtype: str = "f32"     # "f32" | "int8" (quantized variant)
    leaf_dtype: str = "f32"      # "f32" | "bf16": dtype of the materialized
    #                              leaf distance matrix (bf16 halves the
    #                              dominant HBM traffic; ranking-only use)
    merge: str = "segmented"     # reservoir fold in the tile step:
    #                              "segmented" sorts only the received edge
    #                              chunk and does a bounded per-row merge;
    #                              "flat" is the global-re-sort oracle

    @classmethod
    def tiny(cls, **kw) -> "DistBuildParams":
        """CPU-test scale."""
        base = dict(dim=16, n_tile=2048, l0=16, f0=3, l1=32, f1=2,
                    c_max=128, k=2, l_max=32, max_deg=24,
                    assign_chunk=256, leaf_chunk=4, prune_chunk=256,
                    bucket_slack=2.0, edge_slack=2.0)
        base.update(kw)
        return cls(**base)

    def derived(self, n_shards: int) -> dict[str, int]:
        assert self.n_tile % n_shards == 0, (self.n_tile, n_shards)
        assert self.l0 % n_shards == 0, "l0 must divide over shards"
        n_loc = self.n_tile // n_shards
        nb_loc = self.l0 // n_shards
        # level-0 dispatch capacity per destination shard
        cap_send = _round_up(
            int(n_loc * self.f0 / n_shards * self.bucket_slack) + 1, 8)
        # per-bucket capacity (points landing in one level-0 bucket)
        cap_b = _round_up(
            int(self.n_tile * self.f0 / self.l0 * self.bucket_slack) + 1,
            self.assign_chunk)
        n_leaf = nb_loc * self.l1
        n_leaf = _round_up(n_leaf, self.leaf_chunk)
        e_loc = nb_loc * cap_b  # leaf instances before fanout
        n_edges = n_leaf * self.c_max * self.k * 2
        cap_edge = _round_up(
            int(n_edges / n_shards * self.edge_slack) + 1, 8)
        cap_req = _round_up(
            int(n_loc * self.l_max / n_shards * self.edge_slack) + 1, 8)
        return dict(n_loc=n_loc, nb_loc=nb_loc, cap_send=cap_send,
                    cap_b=cap_b, n_leaf=n_leaf, n_edges=n_edges,
                    cap_edge=cap_edge, cap_req=cap_req, e_loc=e_loc)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


# ---------------------------------------------------------------------------
# Tile superstep
# ---------------------------------------------------------------------------



def _quantize(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    # the repo's ONE symmetric int8 scheme — shared with the quantized
    # ServingIndex packing and the gather-distance kernel's query side
    from repro.kernels.ref import quantize_symmetric

    return quantize_symmetric(v)


def _route_pack(v: jax.Array, p: DistBuildParams):
    if p.route_dtype == "int8":
        return _quantize(v)
    return v, None


def _route_unpack(v: jax.Array, scale, p: DistBuildParams) -> jax.Array:
    if p.route_dtype == "int8":
        return v.astype(jnp.float32) * scale[..., None]
    return v


def _leaf_pair_dists_neg(vecs: jax.Array, p: DistBuildParams) -> jax.Array:
    """NEGATED all-pairs squared-L2 for a [B, C, d] leaf batch
    (2<a,b> - |a|^2 - |b|^2), so ``lax.top_k`` selects nearest neighbors
    directly — the separate negate pass over the [C, C] matrix was 25% of
    the tile step's HBM bytes.  ``leaf_dtype=bf16`` halves the rest (the
    matrix is only ever used for ranking).

    quantized variant: int8 x int8 GEMM with i32 accumulation, rescaled —
    the MXU-native path the paper lists as future work.
    """
    if p.route_dtype == "int8":
        q, scale = _quantize(vecs)
        ip = jnp.einsum("bcd,bed->bce", q.astype(jnp.int32),
                        q.astype(jnp.int32),
                        preferred_element_type=jnp.int32)
        ip = ip.astype(jnp.float32) * scale[:, :, None] * scale[:, None, :]
        v = vecs.astype(jnp.float32)
        n2 = jnp.sum(v * v, axis=-1)
    else:
        v = vecs
        ip = jnp.einsum("bcd,bed->bce", v, v, precision=GEMM_PRECISION)
        n2 = jnp.sum(v * v, axis=-1)
    neg = jnp.minimum(2.0 * ip - n2[:, :, None] - n2[:, None, :], 0.0)
    if p.leaf_dtype == "bf16":
        neg = neg.astype(jnp.bfloat16)
    return neg


def make_tile_step(mesh: Mesh, p: DistBuildParams):
    """Returns tile_step(points, hyperplanes, reservoir) -> (reservoir, stats).

    points [n_tile, d] and the reservoir are sharded over ALL mesh axes
    (dim 0); hyperplanes [m, d] replicated.
    """
    axes = mesh_axes(mesh)
    S = int(np.prod([mesh.shape[a] for a in axes]))
    dv = p.derived(S)
    n_loc, nb_loc = dv["n_loc"], dv["nb_loc"]

    def shard_body(points, hyperplanes, res_ids, res_hash, res_dist):
        points = points.astype(jnp.float32)
        me = jax.lax.axis_index(axes)
        gid0 = me * n_loc + jnp.arange(n_loc, dtype=jnp.int32)

        # ---- 1. sketches + level-0 leaders --------------------------------
        sk = points @ hyperplanes.T                       # [n_loc, m]
        lead_stride = n_loc // (p.l0 // S)
        lead_local = points[::lead_stride][: p.l0 // S]   # [l0/S, d]
        leaders0 = jax.lax.all_gather(
            lead_local, axes, axis=0, tiled=True)         # [l0, d]
        bucket = leader_assign(points, leaders0, p.f0)    # [n_loc, f0]

        # ---- 2. route point replicas to bucket owners ---------------------
        flat_bucket = bucket.reshape(-1)                  # [n_loc*f0]
        owner = flat_bucket % S
        rep = lambda a: jnp.repeat(a, p.f0, axis=0)
        vec_r, scale_r = _route_pack(rep(points), p)
        pay = [vec_r, rep(sk), rep(gid0), flat_bucket]
        if scale_r is not None:
            pay.append(scale_r)
        (sent, sent_valid) = group_by_capacity(
            owner, jnp.ones_like(owner, bool), S, dv["cap_send"], pay)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axes,
                                split_axis=0, concat_axis=0, tiled=True)
        recv = [a2a(x) for x in sent]
        recv_valid = a2a(sent_valid)
        n_recv = S * dv["cap_send"]
        recv = [x.reshape((n_recv,) + x.shape[2:]) for x in recv]
        recv_valid = recv_valid.reshape(-1)
        if scale_r is not None:
            r_vec, r_sk, r_gid, r_bucket, r_scale = recv
        else:
            (r_vec, r_sk, r_gid, r_bucket), r_scale = recv, None
        # dispatch capacity overflow (dropped replicas)
        drop_dispatch = (jnp.int32(n_loc * p.f0)
                         - jnp.sum(sent_valid.astype(jnp.int32)))

        # regroup into my local buckets: bucket b lives at slot b // S
        bslot = jnp.where(recv_valid, r_bucket // S, nb_loc)
        pay2 = [r_vec, r_sk, r_gid]
        if r_scale is not None:
            pay2.append(r_scale)
        grouped, g_valid = group_by_capacity(
            bslot, recv_valid, nb_loc, dv["cap_b"], pay2)
        if r_scale is not None:
            b_vec, b_sk, b_gid, b_scale = grouped
        else:
            (b_vec, b_sk, b_gid), b_scale = grouped, None
        b_vecf = _route_unpack(b_vec, b_scale, p)         # [nb, capB, d] f32
        b_vecf = jnp.where(g_valid[..., None], b_vecf, 0.0)

        # ---- 3. level-1 leaders + leaf assignment -------------------------
        l1_stride = max(dv["cap_b"] // p.l1, 1)
        lead1 = b_vecf[:, ::l1_stride][:, : p.l1]          # [nb, l1, d]
        lead1_ok = g_valid[:, ::l1_stride][:, : p.l1]      # [nb, l1]

        def assign_chunk(chunk_vec, chunk_valid):
            # shared Stage-1 leader-assignment step: batched GEMM + top-f1
            return leader_assign(
                chunk_vec, lead1, p.f1, point_valid=chunk_valid,
                leader_valid=lead1_ok)                    # [nb, ch, f1]

        n_chunks = dv["cap_b"] // p.assign_chunk
        cvecs = b_vecf.reshape(nb_loc, n_chunks, p.assign_chunk, p.dim)
        cval = g_valid.reshape(nb_loc, n_chunks, p.assign_chunk)
        leader1 = jax.lax.map(
            lambda t: assign_chunk(t[0], t[1]),
            (jnp.swapaxes(cvecs, 0, 1), jnp.swapaxes(cval, 0, 1)),
        )                                                  # [nc, nb, ch, f1]
        leader1 = jnp.swapaxes(leader1, 0, 1).reshape(
            nb_loc, dv["cap_b"], p.f1)

        # leaf key = bucket_slot * l1 + leader1 ; group to [n_leaf, c_max]
        binst = nb_loc * dv["cap_b"]
        leaf_key = (jnp.arange(nb_loc, dtype=jnp.int32)[:, None, None] * p.l1
                    + leader1).reshape(-1)
        inst_valid = jnp.repeat(g_valid.reshape(-1), p.f1)
        rep1 = lambda a: jnp.repeat(
            a.reshape((binst,) + a.shape[2:]), p.f1, axis=0)
        pay3 = [rep1(b_vecf), rep1(b_sk), rep1(b_gid)]
        (lf_vec, lf_sk, lf_gid), lf_valid = group_by_capacity(
            leaf_key, inst_valid, dv["n_leaf"], p.c_max, pay3, shuffle=True)

        # ---- 4. leaf all-pairs GEMM + bidirected k-NN edges ---------------
        def leaf_chunk_edges(vec, skc, gidc, val):
            nd_mat = _leaf_pair_dists_neg(vec, p)          # [ch, C, C] (-d2)
            eye = jnp.eye(p.c_max, dtype=bool)
            bad = (~val[:, None, :]) | (~val[:, :, None]) | eye[None]
            # duplicate gids (same point via two buckets) -> mask
            dup = gidc[:, :, None] == gidc[:, None, :]
            neg_inf = jnp.asarray(-jnp.inf, nd_mat.dtype)
            nd_mat = jnp.where(bad | (dup & ~eye[None]), neg_inf, nd_mat)
            nd, ni = jax.lax.top_k(nd_mat, p.k)            # [ch, C, k]
            nd = -nd.astype(jnp.float32)
            src = jnp.broadcast_to(gidc[:, :, None], ni.shape)
            # per-leaf gathers (vmap keeps these O(C*k), no CxC broadcast)
            dst = jax.vmap(lambda g, i: g[i])(gidc, ni)        # [ch, C, k]
            sks = jnp.broadcast_to(skc[:, :, None, :],
                                   ni.shape + (p.m_bits,))
            skd = jax.vmap(lambda s, i: s[i])(skc, ni)         # [ch, C, k, m]
            ok = jnp.isfinite(nd) & (dst != INVALID_ID) & (src != INVALID_ID)
            # out-edge src->dst hashed h_src(dst); in-edge dst->src h_dst(src)
            h_out = _sketch.hash_from_sketches(skd, sks)
            h_in = _sketch.hash_from_sketches(sks, skd)
            e_src = jnp.stack([src, dst], -1)
            e_dst = jnp.stack([dst, src], -1)
            e_h = jnp.stack([h_out, h_in], -1)
            e_d = jnp.stack([nd, nd], -1)
            e_ok = jnp.stack([ok, ok], -1)
            return (jnp.where(e_ok, e_src, INVALID_ID).reshape(-1),
                    jnp.where(e_ok, e_dst, INVALID_ID).reshape(-1),
                    jnp.where(e_ok, e_h, 0).reshape(-1),
                    jnp.where(e_ok, e_d, INF).reshape(-1))

        nl_chunks = dv["n_leaf"] // p.leaf_chunk
        resh = lambda a: a.reshape((nl_chunks, p.leaf_chunk) + a.shape[1:])
        e_src, e_dst, e_h, e_d = jax.lax.map(
            lambda t: leaf_chunk_edges(*t),
            (resh(lf_vec.astype(jnp.float32)), resh(lf_sk), resh(lf_gid),
             resh(lf_valid)),
        )
        e_src, e_dst = e_src.reshape(-1), e_dst.reshape(-1)
        e_h, e_d = e_h.reshape(-1), e_d.reshape(-1)

        # ---- 5. route edges home ------------------------------------------
        e_owner = jnp.where(e_src >= 0, e_src // n_loc, S)
        (s_edges, s_ok) = group_by_capacity(
            e_owner, e_src >= 0, S, dv["cap_edge"],
            [e_src, e_dst, e_h, e_d])
        r_edges = [a2a(x) for x in s_edges]
        r_ok = a2a(s_ok).reshape(-1)
        m_src, m_dst, m_h, m_d = [
            x.reshape((S * dv["cap_edge"],) + x.shape[2:]) for x in r_edges]

        # ---- 6. HashPrune: fold flat edges straight into the reservoir ----
        # same fused fold as the streaming host build (mergeability lemma);
        # "segmented" sorts only this superstep's edge chunk and merges the
        # persistent reservoir per row, "flat" re-sorts reservoir-as-edges
        # together with the chunk
        lsrc = jnp.where(r_ok, m_src - me * n_loc, n_loc)
        fold = merge_flat_edges if p.merge == "flat" else merge_segmented_edges
        merged = fold(
            res_ids, res_hash, res_dist,
            lsrc, jnp.where(r_ok, m_dst, INVALID_ID), m_h,
            jnp.where(r_ok, m_d, INF))
        stats = jax.lax.psum(jnp.stack([
            jnp.sum(r_ok.astype(jnp.int32)),       # edges received
            jnp.sum(recv_valid.astype(jnp.int32)),  # replicas received
            drop_dispatch.astype(jnp.int32),        # dispatch drops
        ]), axes)
        return merged.ids, merged.hashes, merged.dists, stats

    sharded = P(axes)
    rep = P()
    step = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(sharded, rep, sharded, sharded, sharded),
        out_specs=(sharded, sharded, sharded, rep), check_vma=False,
    )

    def tile_step(points, hyperplanes, res: Reservoir):
        ids, hs, ds, stats = step(points, hyperplanes,
                                  res.ids, res.hashes, res.dists)
        return Reservoir(ids, hs, ds), stats

    # the raw shard_map program (flat args, no Reservoir wrapper) — what
    # the SPMD auditor (analysis/spmd_audit.py) traces and lowers
    tile_step.shard_step = step
    return tile_step


# ---------------------------------------------------------------------------
# Final prune superstep (request/response vector exchange + RobustPrune)
# ---------------------------------------------------------------------------

def make_final_prune_step(mesh: Mesh, p: DistBuildParams):
    axes = mesh_axes(mesh)
    S = int(np.prod([mesh.shape[a] for a in axes]))
    dv = p.derived(S)
    n_loc = dv["n_loc"]

    def shard_body(points, res_ids, res_dists):
        points = points.astype(jnp.float32)
        me = jax.lax.axis_index(axes)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axes,
                                split_axis=0, concat_axis=0, tiled=True)
        flat_ids = res_ids.reshape(-1)                     # [n_loc*l_max]
        valid = flat_ids != INVALID_ID
        owner = jnp.where(valid, flat_ids // n_loc, S)
        slot = jnp.arange(n_loc * p.l_max, dtype=jnp.int32)
        (s_req, s_ok) = group_by_capacity(
            owner, valid, S, dv["cap_req"], [flat_ids, slot])
        s_cand, s_slot = s_req                             # s_slot stays local
        r_cand = a2a(s_cand)                               # [S, capR]
        r_ok = a2a(s_ok)
        lidx = jnp.clip(r_cand - me * n_loc, 0, n_loc - 1)
        r_vecs = points[lidx]                              # [S, capR, d]
        r_vecs = jnp.where(r_ok[..., None], r_vecs, 0.0)
        # response a2a: slice s of the result is what owner s produced for
        # MY requests, i.e. aligned with my send buffer s_cand[s] — so my
        # own (local) s_slot / s_ok describe its layout.
        b_vecs = a2a(r_vecs)
        gat = jnp.zeros((n_loc * p.l_max, p.dim), jnp.float32)
        gat = gat.at[jnp.where(s_ok, s_slot, n_loc * p.l_max).reshape(-1)
                     ].set(b_vecs.reshape(-1, p.dim), mode="drop")
        cand_vecs = gat.reshape(n_loc, p.l_max, p.dim)

        def prune_chunk(t):
            # d_cc from the routed vectors; the keep/compact/truncate logic
            # is the shared block the host build's final_prune also uses
            ids, dists, vecs = t
            ip = jnp.einsum("bld,bmd->blm", vecs, vecs,
                            precision=GEMM_PRECISION)
            n2 = jnp.sum(vecs * vecs, axis=-1)
            d_cc = jnp.maximum(
                n2[:, :, None] + n2[:, None, :] - 2.0 * ip, 0.0)
            return prune_reservoir_block(ids, dists, d_cc,
                                         alpha=p.alpha, max_deg=p.max_deg)

        nch = n_loc // p.prune_chunk
        resh = lambda a: a.reshape((nch, p.prune_chunk) + a.shape[1:])
        gid, gd = jax.lax.map(
            prune_chunk, (resh(res_ids), resh(res_dists), resh(cand_vecs)))
        return (gid.reshape(n_loc, p.max_deg),
                gd.reshape(n_loc, p.max_deg))

    sharded = P(axes)
    return jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(sharded, sharded, sharded),
        out_specs=(sharded, sharded), check_vma=False,
    )


# ---------------------------------------------------------------------------
# Drivers: dry-run lowering + a real (small-scale) runnable build
# ---------------------------------------------------------------------------

def production_params(dim: int, variant: str = "baseline") -> DistBuildParams:
    if variant == "quantized":
        return DistBuildParams(dim=dim, route_dtype="int8")
    if variant == "opt":          # the full beyond-paper stack
        return DistBuildParams(dim=dim, route_dtype="int8",
                               leaf_dtype="bf16")
    if variant == "bf16leaf":
        return DistBuildParams(dim=dim, leaf_dtype="bf16")
    return DistBuildParams(dim=dim)


def lower_build_step(mesh: Mesh, *, n_points: int, dim: int,
                     variant: str = "baseline"):
    """AOT-lower one tile superstep (+ the collective schedule) on ``mesh``.

    ``n_points`` is the full dataset size (2^30 at billion scale); the
    compiled unit processes one n_tile tile — the build runs
    ceil(n_points / n_tile) such steps, all identical.
    """
    if variant == "final_prune":
        return lower_final_prune_step(mesh, dim=dim)
    p = production_params(dim, variant)
    axes = mesh_axes(mesh)
    sh = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    step = make_tile_step(mesh, p)
    pts = jax.ShapeDtypeStruct((p.n_tile, p.dim), jnp.float32, sharding=sh)
    hp = jax.ShapeDtypeStruct((p.m_bits, p.dim), jnp.float32, sharding=rep)
    res = Reservoir(
        ids=jax.ShapeDtypeStruct((p.n_tile, p.l_max), jnp.int32, sharding=sh),
        hashes=jax.ShapeDtypeStruct((p.n_tile, p.l_max), jnp.int32,
                                    sharding=sh),
        dists=jax.ShapeDtypeStruct((p.n_tile, p.l_max), jnp.float32,
                                   sharding=sh),
    )
    return jax.jit(step, donate_argnums=(2,)).lower(pts, hp, res)


def lower_final_prune_step(mesh: Mesh, *, dim: int):
    p = production_params(dim)
    axes = mesh_axes(mesh)
    sh = NamedSharding(mesh, P(axes))
    step = make_final_prune_step(mesh, p)
    pts = jax.ShapeDtypeStruct((p.n_tile, p.dim), jnp.float32, sharding=sh)
    ids = jax.ShapeDtypeStruct((p.n_tile, p.l_max), jnp.int32, sharding=sh)
    ds = jax.ShapeDtypeStruct((p.n_tile, p.l_max), jnp.float32, sharding=sh)
    return jax.jit(step).lower(pts, ids, ds)


def useful_flops(n_points: int, dim: int,
                 p: DistBuildParams | None = None) -> float:
    """Algorithmically-required MACs*2 for ONE tile step (matches the
    compiled unit): level-0 GEMM + level-1 GEMM + leaf all-pairs + sketch."""
    p = p or production_params(dim)
    n = p.n_tile
    per_point = (p.l0 + p.f0 * p.l1 + p.f0 * p.f1 * p.c_max + p.m_bits)
    return 2.0 * n * per_point * p.dim


def build_distributed(x: np.ndarray, mesh: Mesh,
                      p: DistBuildParams, *, seed: int = 0,
                      final_prune: bool = True):
    """Runnable distributed build (used by tests at small scale on CPU).

    Streams x tile-by-tile through the tile step (HashPrune mergeability
    licenses this), then runs the final-prune superstep.  Returns
    (graph [n, max_deg], dists [n, max_deg]).
    """
    n, d = x.shape
    assert d == p.dim
    pad_n = _round_up(n, p.n_tile)
    if pad_n != n:
        filler = x[np.random.default_rng(seed).integers(0, n, pad_n - n)]
        x = np.concatenate([x, filler + 1e3], 0)  # far-away pad points
    key = jax.random.PRNGKey(seed)
    hp = _sketch.make_hyperplanes(key, p.m_bits, p.dim)
    tile_step = make_tile_step(mesh, p)
    res = reservoir_init(p.n_tile, p.l_max)
    graph_parts, dist_parts = [], []
    fp_step = make_final_prune_step(mesh, p)
    for t0 in range(0, pad_n, p.n_tile):
        tile = jnp.asarray(x[t0: t0 + p.n_tile])
        res_t, _ = tile_step(tile, hp, reservoir_init(p.n_tile, p.l_max))
        # convert tile-local ids to global ids
        res_t = Reservoir(
            ids=jnp.where(res_t.ids >= 0, res_t.ids + t0, res_t.ids),
            hashes=res_t.hashes, dists=res_t.dists)
        if final_prune:
            # final prune needs tile-local ids for vector routing
            lids = jnp.where(res_t.ids >= 0, res_t.ids - t0, res_t.ids)
            gid, gd = fp_step(tile, lids, res_t.dists)
            gid = jnp.where(gid >= 0, gid + t0, gid)
        else:
            gid, gd = res_t.ids[:, : p.max_deg], res_t.dists[:, : p.max_deg]
        graph_parts.append(np.asarray(gid))
        dist_parts.append(np.asarray(gd))
    graph = np.concatenate(graph_parts)[:n]
    dists = np.concatenate(dist_parts)[:n]
    # drop edges pointing at pad points
    bad = graph >= n
    graph = np.where(bad, -1, graph)
    dists = np.where(bad, np.inf, dists)
    return graph, dists
