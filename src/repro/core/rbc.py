"""Overlapping partitioning for PiPNN (Sec. 4.1, Algorithm 5, Appendix A.1).

The production partitioner is Randomized Ball Carving (RBC) with *multi-level
fanout*: in each subproblem sample ``l = min(P_samp * |P|, leader_cap)``
leaders, assign every point to its ``fanout(depth)`` nearest leaders, recurse
on subproblems larger than ``C_max``; merge subproblems smaller than
``C_min``.  Fanout>1 at the top level(s) replaces whole-procedure replication
(Appendix A.2's cost analysis) — the paper observes recursion depth 2–3
suffices in practice because arity is ~1000.

Stage-1 execution strategies, selected by ``RBCParams.execution``:

  * ``"host"`` — the numpy oracle: the original host-side recursion, kept
    as the reference the device paths are bit-compared against.
  * ``"device"`` — host-orchestrated device carving: the host keeps ONLY
    the variable-size worklist (and the leader-sampling RNG stream); all
    per-subproblem math — the leader GEMM, top-f selection, and the
    bucket grouping (stable sort + searchsorted) — runs in fixed-shape
    jitted steps (``core/leader_assign.py``) over power-of-two padded
    row/leader blocks with VMEM-sized sub-batches.  Leader sampling draws
    from the same host ``np.random.Generator`` stream as the oracle, and
    the device assignment mirrors the oracle's arithmetic (same GEMM
    expansion, same stable tie-break), so the produced leaves are
    bit-identical to ``execution="host"`` for a fixed seed whenever the
    backend GEMM matches numpy's bit for bit — exact on this container's
    CPU backend (asserted by tests); on GPU/TPU accumulation order can
    differ and assignments may diverge at near-exact distance ties.
  * ``"static"`` — ``ball_carve_device``: a fully-static two-level carve
    (the ``launch/build_index.py`` tile-step shape, generalized to the
    fanout schedule) compiled as ONE jitted program with capacity-routed
    grouping; zero host compute beyond sampling the level-0 leaders.
    Skew overflow beyond the static capacities is dropped, but each point
    also routes to ``bucket_spill`` next-nearest leaders whose replicas
    only claim capacity primaries left unused — the static substitute for
    the recursion's adaptivity, which keeps index quality at parity with
    the recursive carve.  Points that lose every replica (duplicate-heavy
    clusters) are re-added in appended salvage leaves, so full coverage
    is guaranteed here too.
  * ``"auto"`` (default) — ``"device"`` on an accelerator backend,
    ``"host"`` on CPU (where the jit round-trips don't pay for
    themselves at test scale).

Also implemented (for the Appendix A.1 ablation benchmarks):
  * binary partitioning (HCNNG style) — 2 random leaders, no fanout analog;
  * hierarchical k-means — leaders chosen by Lloyd iterations instead of
    uniformly at random;
  * sorting-LSH — concatenated hyperplane hashes, lexicographic sort,
    consecutive groups of <= C_max (replication, not fanout).

Degenerate-data hardening (duplicate-heavy inputs): the recursive carvers
force-split any oversized bucket that made no progress (bucket == parent)
into permutation halves, ``binary_partition`` splits degenerate 2-leader
ties the same way, and sorting-LSH packs its hash bits into uint64 words
(the old float64 key silently collided past 53 bits).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Literal, Sequence

import numpy as np

from repro import tracing
from repro.core import metrics as _metrics


@dataclasses.dataclass(frozen=True)
class RBCParams:
    c_max: int = 1024          # max leaf size (paper: 1024-2048)
    c_min: int = 64            # min leaf size before merging
    p_samp: float = 0.01       # leader fraction per subproblem
    leader_cap: int = 1000     # hard cap on leaders per subproblem (paper: 1000)
    fanout: Sequence[int] = (10, 3)  # fanout(depth); 1 past the schedule
    replicas: int = 1          # independent RBC runs (quality knob, Sec. 5.2)
    metric: str = "l2"
    seed: int = 0
    execution: str = "auto"    # "auto" | "host" | "device" | "static"
    assign_rows: int = 4096    # device path: GEMM sub-batch rows (VMEM budget)
    bucket_slack: float = 1.5  # static path: level-0 bucket capacity slack
    bucket_spill: int = 2      # static path: extra next-nearest leaders each
    #                            point routes to, so replicas squeezed out of
    #                            a capacity-full (skewed) bucket survive in
    #                            the point's next-best ball — the static
    #                            substitute for the recursion's adaptivity
    leaf_fill: float = 0.55    # static path: target mean leaf fill (sizes the
    #                            level-1 leader count so skewed leaves stay
    #                            under the hard c_max cap, as in build_index)

    def fanout_at(self, depth: int) -> int:
        return self.fanout[depth] if depth < len(self.fanout) else 1


def resolve_execution(params: RBCParams) -> str:
    """Resolve ``execution="auto"`` against the active jax backend."""
    if params.execution != "auto":
        return params.execution
    import jax

    return "device" if jax.default_backend() in ("tpu", "gpu") else "host"


def _pairwise_np(a: np.ndarray, b: np.ndarray, metric: str) -> np.ndarray:
    """Host-side GEMM-expansion distance matrix (numpy mirror of metrics.pairwise)."""
    ip = a @ b.T
    if metric == "mips":
        return -ip
    if metric == "cosine":
        an = np.linalg.norm(a, axis=-1, keepdims=True)
        bn = np.linalg.norm(b, axis=-1, keepdims=True)
        return 1.0 - ip / np.maximum(an * bn.T, 1e-30)
    a2 = np.sum(a * a, axis=-1)[:, None]
    b2 = np.sum(b * b, axis=-1)[None, :]
    return np.maximum(a2 + b2 - 2.0 * ip, 0.0)


def _nearest_leaders(
    x: np.ndarray, leaders: np.ndarray, k: int, metric: str
) -> np.ndarray:
    """Indices [n, k] of the k nearest leaders for each row of x, ordered by
    ascending distance with ties broken by ascending leader index — the
    same total order ``lax.top_k`` produces, so the device assignment step
    can reproduce these decisions bit for bit."""
    d = _pairwise_np(x, leaders, metric)
    k = min(k, leaders.shape[0])
    return np.argsort(d, axis=1, kind="stable")[:, :k]


def _merge_small(
    buckets: list[np.ndarray], c_min: int, c_max: int, rng: np.random.Generator
) -> list[np.ndarray]:
    """Randomly merge buckets smaller than c_min, never exceeding c_max."""
    small = [b for b in buckets if len(b) < c_min]
    keep = [b for b in buckets if len(b) >= c_min]
    if not small:
        return keep
    order = rng.permutation(len(small))
    cur: list[np.ndarray] = []
    cur_len = 0
    for j in order:
        b = small[j]
        if cur_len + len(b) > c_max and cur:
            # dedupe: fanout may place a point in several merged buckets
            keep.append(np.unique(np.concatenate(cur)))
            cur, cur_len = [], 0
        cur.append(b)
        cur_len += len(b)
    if cur:
        keep.append(np.unique(np.concatenate(cur)))
    return keep


# ---------------------------------------------------------------------------
# Stage-1 assignment backends (host oracle / jitted device step)
# ---------------------------------------------------------------------------
#
# Both backends implement the same contract for one subproblem:
#   (x, idx, leader_pos, f, metric, ctx) -> (order, starts)
# where ``order`` are positions into the row-major [m, f] assignment table
# stably sorted by assigned-leader id, and ``starts`` [n_leaders + 1] are
# the per-leader group boundaries (searchsorted).  Bucket l is then
# ``idx[order[starts[l]:starts[l+1]] // f]``.  Stable sorting makes the
# permutation unique given the keys, so host and device grouping agree
# whenever the assignments do.

def _assign_host(x, idx, leader_pos, f, metric, ctx):
    leaders = x[idx[leader_pos]]
    assign = _nearest_leaders(x[idx], leaders, f, metric)      # [m, f]
    flat = assign.reshape(-1)
    order = np.argsort(flat, kind="stable")
    starts = np.searchsorted(flat[order], np.arange(len(leader_pos) + 1))
    return order, starts


def _next_pow2(v: int) -> int:
    return 1 << max(0, int(v - 1).bit_length())


@functools.lru_cache(maxsize=32)
def _make_carve_step(f: int, metric: str, sub: int):
    """Compile the fixed-shape per-subproblem carve step.

    carve_step(xj, idx_pad, lead_pad, m, n_lead) -> (order, starts) where xj is
    the device-resident dataset, idx_pad [R] / lead_pad [L] are padded
    point/leader index blocks (R, L powers of two — shape specialization
    stays logarithmic in n), and m / n_lead are the true counts as traced
    scalars.  The leader GEMM runs over ``sub``-row sub-batches via
    ``lax.map`` so the [sub, L] distance tile is the only large
    intermediate; grouping is a stable sort + searchsorted on device.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.leader_assign import leader_assign

    def carve_step(xj, idx_pad, lead_pad, m, n_lead):
        r = idx_pad.shape[0]
        l = lead_pad.shape[0]
        leaders = xj[lead_pad]                                  # [L, d]
        lead_ok = jnp.arange(l, dtype=jnp.int32) < n_lead

        def block(ids_sub):
            return leader_assign(xj[ids_sub], leaders, f, metric=metric,
                                 leader_valid=lead_ok)

        a = jax.lax.map(block, idx_pad.reshape(r // sub, sub))  # [R/sub, sub, f]
        a = a.reshape(r, f)
        row_ok = jnp.arange(r, dtype=jnp.int32) < m
        # padded rows key to the sentinel l: they stably sort after every
        # real leader group, so the valid prefix of ``order`` is exactly
        # the host oracle's permutation of the [m, f] table
        key = jnp.where(row_ok[:, None], a, jnp.int32(l)).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        starts = jnp.searchsorted(
            key[order], jnp.arange(l + 1, dtype=jnp.int32)).astype(jnp.int32)
        return order, starts

    return jax.jit(carve_step)


def _assign_device(x, idx, leader_pos, f, metric, ctx):
    import jax.numpy as jnp

    xj, sub_cfg = ctx
    m, nl = len(idx), len(leader_pos)
    r_pad = _next_pow2(max(m, 8))
    sub = min(_next_pow2(sub_cfg), r_pad)
    l_pad = _next_pow2(max(nl, 2))
    idx_pad = np.zeros(r_pad, np.int32)
    idx_pad[:m] = idx
    lead_pad = np.zeros(l_pad, np.int32)
    lead_pad[:nl] = idx[leader_pos]
    step = _make_carve_step(f, metric, sub)
    order, starts = step(xj, jnp.asarray(idx_pad), jnp.asarray(lead_pad),
                         jnp.asarray(np.int32(m)), jnp.asarray(np.int32(nl)))
    return np.asarray(order), np.asarray(starts)[: nl + 1]


def _carve_worklist(
    x: np.ndarray,
    params: RBCParams,
    seed: int | None,
    assign_fn: Callable,
    ctx,
) -> list[np.ndarray]:
    """Algorithm 5's recursion as an explicit worklist, shared by the host
    and device assignment backends (identical RNG stream consumption, so
    both produce identical leaves when the assignments agree).

    Spans: ``rbc.worklist`` around the whole and ``rbc.assign`` around
    each assignment, its copy back to the host included; the worklist's
    own host time is the first less the second."""
    rng = np.random.default_rng(params.seed if seed is None else seed)
    n = x.shape[0]
    leaves: list[np.ndarray] = []
    # worklist of (point-index-array, depth)
    stack: list[tuple[np.ndarray, int]] = [(np.arange(n, dtype=np.int64), 0)]
    with tracing.span("rbc.worklist"):
        while stack:
            idx, depth = stack.pop()
            if len(idx) <= params.c_max:
                leaves.append(idx)
                continue
            n_leaders = int(
                np.clip(round(params.p_samp * len(idx)), 2, params.leader_cap)
            )
            leader_pos = rng.choice(len(idx), size=n_leaders, replace=False)
            f = min(params.fanout_at(depth), n_leaders)
            with tracing.span("rbc.assign"):
                order, starts = assign_fn(x, idx, leader_pos, f,
                                          params.metric, ctx)
            buckets: list[np.ndarray] = []
            for s, e in zip(starts[:-1], starts[1:]):
                if e > s:
                    buckets.append(idx[order[s:e] // f])
            buckets = _merge_small(buckets, params.c_min, params.c_max, rng)
            for b in buckets:
                if len(b) <= params.c_max:
                    leaves.append(b)
                elif len(b) == len(idx):
                    # no progress (duplicate-heavy data: every point
                    # assigned to one leader) — the bucket equals the
                    # parent and would recurse forever; force-split by
                    # permutation halves
                    perm = rng.permutation(len(b))
                    half = len(b) // 2
                    stack.append((b[perm[:half]], depth + 1))
                    stack.append((b[perm[half:]], depth + 1))
                else:
                    stack.append((b, depth + 1))
    return leaves


def ball_carve(
    x: np.ndarray,
    params: RBCParams,
    *,
    seed: int | None = None,
    execution: str | None = None,
) -> list[np.ndarray]:
    """Algorithm 5. Returns leaves as arrays of point indices (overlapping).

    ``execution`` overrides ``params.execution``; see the module docstring
    for the strategies.  ``"host"`` and ``"device"`` are bit-identical for
    a fixed seed (modulo backend GEMM parity with numpy — exact on CPU);
    ``"static"`` is the fully-static two-level variant.
    """
    mode = execution if execution is not None else resolve_execution(params)
    if mode == "static":
        padded = ball_carve_device(x, params, seed=seed)
        return [row[row >= 0].astype(np.int64) for row in padded]
    if mode == "device":
        import jax.numpy as jnp

        ctx = (jnp.asarray(x), params.assign_rows)
        return _carve_worklist(x, params, seed, _assign_device, ctx)
    return _carve_worklist(x, params, seed, _assign_host, None)


def ball_carve_replicated(x: np.ndarray, params: RBCParams) -> list[np.ndarray]:
    """``params.replicas`` independent RBC runs; union of leaves (Sec. 5.2)."""
    leaves: list[np.ndarray] = []
    for r in range(params.replicas):
        leaves.extend(ball_carve(x, params, seed=params.seed + 7919 * r))
    return leaves


# ---------------------------------------------------------------------------
# Fully-static two-level device carve (the build_index.py tile-step shape)
# ---------------------------------------------------------------------------

def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _static_shapes(n: int, params: RBCParams) -> dict[str, int]:
    """Static problem sizes for ``ball_carve_device`` (mirrors
    ``DistBuildParams.derived``, generalized to the fanout schedule)."""
    l0 = int(np.clip(round(params.p_samp * n), 2, min(params.leader_cap, n)))
    if _round_up(l0, 8) <= n:     # round to a bucket_chunk-friendly count
        l0 = _round_up(l0, 8)
    f0 = min(params.fanout_at(0), l0)
    # each point also routes to bucket_spill next-nearest leaders; spill
    # replicas only claim capacity primaries left unused, so a replica
    # squeezed out of a skewed over-capacity ball survives in the point's
    # next-best ball instead of being dropped outright
    f0r = min(f0 + max(params.bucket_spill, 0), l0)
    cap_b = _round_up(int(n * f0 / l0 * params.bucket_slack) + 1, 8)
    f1 = params.fanout_at(1)
    # level-1 leader count sized from capacity: per-bucket leaf capacity
    # l1 * c_max must hold cap_b * f1 placements at ~leaf_fill mean fill
    l1 = -(-int(cap_b * f1) // max(int(params.c_max * params.leaf_fill), 1))
    l1 = int(np.clip(l1, 2, min(params.leader_cap, cap_b)))
    f1 = min(f1, l1)
    return dict(l0=l0, f0=f0, f0r=f0r, cap_b=cap_b, l1=l1, f1=f1)


@functools.lru_cache(maxsize=16)
def _make_static_carve(n_pad: int, l0: int, f0: int, f0r: int, cap_b: int,
                       l1: int, f1: int, c_max: int, metric: str, sub: int,
                       bucket_chunk: int, cap_chunk: int):
    """Compile the one-shot two-level carve: level-0 leader GEMM + top-f0r,
    capacity-routed bucket grouping (primary replicas claim capacity
    first, spill replicas fill what is left), strided level-1 leaders,
    level-1 GEMM + top-f1 (per bucket chunk), capacity-routed leaf
    grouping.  Returns leaf_ids [l0 * l1, c_max] int32, -1 padded.

    BOTH assignment levels stream their point gathers: level 0 in ``sub``
    rows and level 1 in ``cap_chunk``-point sub-blocks of each bucket
    (the ``build_index`` tile-step ``assign_chunk`` pattern), so the
    largest points intermediate is [bucket_chunk, cap_chunk, d] — NOT the
    full [bucket_chunk, cap_b, d] bucket gather, whose cap_b ~ n*f0/l0
    rows grow with the dataset and would dominate peak carve memory at
    billion scale (the ROADMAP carve-gather item; proven chunk-bounded by
    the PIPM001 memory audit)."""
    import jax
    import jax.numpy as jnp

    from repro.core.leader_assign import leader_assign
    from repro.distributed.routing import group_by_capacity

    n_leaf = l0 * l1

    def wshuf(*arrs):
        # fixed Weyl permutation (the group_by_capacity shuffle) applied
        # per segment, so overflow drops are unbiased WITHIN a segment
        # while primaries still arrive before spills
        e = arrs[0].shape[0]
        perm = jnp.argsort(
            jnp.arange(e, dtype=jnp.uint32) * jnp.uint32(2654435761))
        return [a[perm] for a in arrs]

    def static_carve_step(xj, lead0_idx, m):
        leaders0 = xj[lead0_idx]                               # [l0, d]
        pid = jnp.arange(n_pad, dtype=jnp.int32)

        def blk(ids_sub):
            return leader_assign(xj[ids_sub], leaders0, f0r, metric=metric)

        a0 = jax.lax.map(blk, pid.reshape(n_pad // sub, sub))
        a0 = a0.reshape(n_pad, f0r)                            # [n, f0r]
        valid = pid < m
        seg = []
        for lo, hi in ((0, f0), (f0, f0r)):                    # primaries, spills
            if hi == lo:
                continue
            seg.append(wshuf(a0[:, lo:hi].reshape(-1),
                             jnp.repeat(valid, hi - lo),
                             jnp.repeat(pid, hi - lo)))
        keys, ok, pids = (jnp.concatenate(parts)
                          for parts in zip(*seg))
        (bpid,), bval = group_by_capacity(
            keys, ok, l0, cap_b, [pids], shuffle=False)        # [l0, cap_b]

        # level-1 leaders: strided picks from each bucket's grouped slots
        stride = max(cap_b // l1, 1)
        lead1_idx = bpid[:, ::stride][:, :l1]                  # [l0, l1]
        lead1_ok = bval[:, ::stride][:, :l1]

        n_cc = cap_b // cap_chunk

        def bucket_blk(t):
            # gather this chunk's leaders once ([bucket_chunk, l1, d]),
            # then stream the cap_b point axis in cap_chunk sub-blocks so
            # the only large points intermediate is [bucket_chunk,
            # cap_chunk, d] — never the full bucket.  leader_assign is
            # row-independent over points, so the split is bit-identical.
            ids, iok, lids, lok = t
            leaders = xj[jnp.maximum(lids, 0)]

            def cc_blk(u):
                cids, cok = u
                return leader_assign(
                    xj[jnp.maximum(cids, 0)], leaders, f1,
                    metric=metric, point_valid=cok, leader_valid=lok)

            cc = lambda a: jnp.swapaxes(
                a.reshape(a.shape[0], n_cc, cap_chunk), 0, 1)
            a = jax.lax.map(cc_blk, (cc(ids), cc(iok)))
            return jnp.swapaxes(a, 0, 1).reshape(ids.shape[0], cap_b, f1)

        resh = lambda a: a.reshape((l0 // bucket_chunk, bucket_chunk)
                                   + a.shape[1:])
        a1 = jax.lax.map(
            bucket_blk, (resh(bpid), resh(bval), resh(lead1_idx),
                         resh(lead1_ok)))
        a1 = a1.reshape(l0, cap_b, f1)
        # sparse buckets can hold fewer valid level-1 leaders than f1, in
        # which case top-f1 is forced to emit an INF-masked (invalid)
        # leader — drop those placements instead of keying junk leaves
        a1_ok = jnp.take_along_axis(
            lead1_ok, a1.reshape(l0, cap_b * f1), axis=1).reshape(a1.shape)

        leaf_key = (jnp.arange(l0, dtype=jnp.int32)[:, None, None] * l1
                    + a1).reshape(-1)
        inst_ok = jnp.repeat(bval.reshape(-1), f1) & a1_ok.reshape(-1)
        (leaf_ids,), leaf_ok = group_by_capacity(
            leaf_key, inst_ok, n_leaf, c_max,
            [jnp.repeat(bpid.reshape(-1), f1)], shuffle=True)
        return jnp.where(leaf_ok, leaf_ids, -1)                # [n_leaf, c_max]

    return jax.jit(static_carve_step)


def carve_workspace_bytes(n_pad: int, d: int, l0: int, f0r: int, cap_b: int,
                          l1: int, f1: int, bucket_chunk: int,
                          cap_chunk: int) -> int:
    """Modeled XLA temp bytes of one ``_make_static_carve`` step: the
    [n_pad, f0r] level-0 assignment plus its capacity-routing sort
    buffers (key + validity + payload per replica instance), the
    STREAMED level-1 gather ([bucket_chunk, cap_chunk, d] points +
    [bucket_chunk, l1, d] leaders — never the full [bucket_chunk, cap_b,
    d] bucket), and the leaf placements with their routing sort.
    Validated against the compiled ledger by the memory auditor
    (PIPM004, ~2x above the measured CPU-XLA temp) and priced at the
    deployment envelope by PIPM003 — which is where a regression to the
    bucket-wide gather shows up: at envelope scale that gather alone
    adds a bucket_chunk * cap_b * d term this model does not grant."""
    inst0 = n_pad * f0r
    level0 = inst0 * 4 + 3 * inst0 * 9
    gather1 = bucket_chunk * (cap_chunk * d + l1 * d) * 4
    placements = l0 * cap_b * f1
    level1 = placements * 4 + 3 * placements * 9
    return level0 + gather1 + level1


def carve_chunks(n: int, params: RBCParams) -> dict:
    """The static chunking ``ball_carve_device`` resolves for ``n``
    points: level-0 row sub-batch ``sub``, level-1 bucket group
    ``bucket_chunk`` and point sub-block ``cap_chunk`` (largest divisor
    of ``cap_b`` keeping ``bucket_chunk * cap_chunk`` gathered rows near
    ``params.assign_rows``).  Shared with the memory auditor so the
    audited program is exactly the production one."""
    sh = _static_shapes(n, params)
    sub = min(_next_pow2(params.assign_rows), _next_pow2(max(n, 8)))
    bucket_chunk = next(c for c in (8, 4, 2, 1) if sh["l0"] % c == 0)
    cap_target = min(sh["cap_b"],
                     max(8, params.assign_rows // max(bucket_chunk, 1)))
    cap_chunk = next(c for c in range(cap_target, 0, -1)
                     if sh["cap_b"] % c == 0)
    return dict(sh, sub=sub, n_pad=_round_up(n, sub),
                bucket_chunk=bucket_chunk, cap_chunk=cap_chunk)


def ball_carve_device(
    x: np.ndarray, params: RBCParams, *, seed: int | None = None
) -> np.ndarray:
    """Fully-static two-level RBC on device: ONE jitted program produces the
    padded [L, c_max] leaf matrix directly (the TPU-facing representation
    ``leaves_to_padded`` would build) — no host recursion, no per-leaf
    host lists.  Generalizes the ``launch/build_index.py`` tile-step shape
    to ``params.fanout``.

    Coverage is guaranteed: capacity routing drops overflow replicas under
    skew (spill routing keeps that rare on spread-out data), and any point
    that loses ALL its replicas — duplicate-heavy clusters can overflow
    every ball they hash to — is placed into salvage leaves appended
    host-side (dropped points grouped c_max at a time; for a dense
    cluster these ARE its nearest neighbors).  Empty leaves are filtered
    host-side.
    """
    import jax.numpy as jnp

    n, _ = x.shape
    if n <= params.c_max:
        return leaves_to_padded([np.arange(n, dtype=np.int64)], params.c_max)
    sh = carve_chunks(n, params)
    rng = np.random.default_rng(params.seed if seed is None else seed)
    lead0 = rng.choice(n, size=sh["l0"], replace=False).astype(np.int32)
    n_pad = sh["n_pad"]
    xpad = x if n_pad == n else np.concatenate(
        [x, np.zeros((n_pad - n, x.shape[1]), x.dtype)])
    step = _make_static_carve(
        n_pad, sh["l0"], sh["f0"], sh["f0r"], sh["cap_b"], sh["l1"],
        sh["f1"], params.c_max, params.metric, sh["sub"],
        sh["bucket_chunk"], sh["cap_chunk"])
    leaf_ids = np.asarray(step(jnp.asarray(xpad), jnp.asarray(lead0),
                               jnp.asarray(np.int32(n))))
    leaf_ids = leaf_ids[(leaf_ids >= 0).any(axis=1)]
    # salvage pass: every point must land in at least one leaf
    seen = np.zeros(n, dtype=bool)
    seen[leaf_ids[leaf_ids >= 0]] = True
    if not seen.all():
        lost = np.flatnonzero(~seen)
        salvage = [lost[s: s + params.c_max]
                   for s in range(0, len(lost), params.c_max)]
        leaf_ids = np.concatenate(
            [leaf_ids, leaves_to_padded(salvage, params.c_max)])
    return leaf_ids


def padded_coverage(padded: np.ndarray, n: int) -> int:
    """Number of the ``n`` points that appear in at least one padded leaf."""
    seen = np.zeros(n, dtype=bool)
    ids = padded[padded >= 0]
    seen[ids] = True
    return int(seen.sum())


def partition_padded(
    x: np.ndarray, params: RBCParams,
    method: Literal["rbc", "binary", "kmeans", "sorting_lsh"] = "rbc",
) -> np.ndarray:
    """Stage-1 entry point returning the dense [L, c_max] padded leaf
    matrix.  For ``method="rbc"`` with the static execution strategy the
    matrix comes straight off the device (replicas concatenated); all
    other configurations go through the list-of-leaves path."""
    if method == "rbc" and resolve_execution(params) == "static":
        mats = [ball_carve_device(x, params, seed=params.seed + 7919 * r)
                for r in range(max(params.replicas, 1))]
        return mats[0] if len(mats) == 1 else np.concatenate(mats, axis=0)
    return leaves_to_padded(partition(x, params, method), params.c_max)


# ---------------------------------------------------------------------------
# Ablation partitioners (Appendix A.1)
# ---------------------------------------------------------------------------

def binary_partition(
    x: np.ndarray,
    *,
    c_max: int = 1024,
    replicas: int = 1,
    metric: str = "l2",
    seed: int = 0,
) -> list[np.ndarray]:
    """HCNNG's recursive 2-leader partitioning (A.1.1). Disjoint per replica."""
    leaves: list[np.ndarray] = []
    for r in range(replicas):
        rng = np.random.default_rng(seed + 104729 * r)
        stack = [np.arange(x.shape[0], dtype=np.int64)]
        while stack:
            idx = stack.pop()
            if len(idx) <= c_max:
                leaves.append(idx)
                continue
            two = rng.choice(len(idx), size=2, replace=False)
            d = _pairwise_np(x[idx], x[idx[two]], metric)
            left = d[:, 0] <= d[:, 1]
            if left.all() or (~left).all():
                # degenerate split (duplicate points): permutation halves —
                # guaranteed progress, unlike the old coin-flip mask which
                # could re-push the full subproblem
                perm = rng.permutation(len(idx))
                half = len(idx) // 2
                stack.append(idx[perm[:half]])
                stack.append(idx[perm[half:]])
                continue
            stack.append(idx[left])
            stack.append(idx[~left])
    return leaves


def _lloyd(x: np.ndarray, k: int, iters: int, rng, metric: str) -> np.ndarray:
    centers = x[rng.choice(x.shape[0], size=k, replace=False)].copy()
    for _ in range(iters):
        a = np.argmin(_pairwise_np(x, centers, metric), axis=1)
        for j in range(k):
            m = a == j
            if m.any():
                centers[j] = x[m].mean(axis=0)
    return centers


def kmeans_carve(
    x: np.ndarray, params: RBCParams, *, lloyd_iters: int = 3, seed: int | None = None
) -> list[np.ndarray]:
    """Hierarchical k-means (A.1.2): RBC but leaders are Lloyd centroids."""
    rng = np.random.default_rng(params.seed if seed is None else seed)
    leaves: list[np.ndarray] = []
    stack: list[tuple[np.ndarray, int]] = [(np.arange(x.shape[0], dtype=np.int64), 0)]
    while stack:
        idx, depth = stack.pop()
        if len(idx) <= params.c_max:
            leaves.append(idx)
            continue
        n_leaders = int(np.clip(round(params.p_samp * len(idx)), 2, params.leader_cap))
        centers = _lloyd(x[idx], n_leaders, lloyd_iters, rng, params.metric)
        f = min(params.fanout_at(depth), n_leaders)
        assign = _nearest_leaders(x[idx], centers, f, params.metric)
        flat = assign.reshape(-1)
        src = np.repeat(idx, f)
        order = np.argsort(flat, kind="stable")
        flat_sorted, src_sorted = flat[order], src[order]
        buckets = []
        starts = np.searchsorted(flat_sorted, np.arange(n_leaders))
        ends = np.searchsorted(flat_sorted, np.arange(n_leaders) + 1)
        for s, e in zip(starts, ends):
            if e > s:
                buckets.append(src_sorted[s:e])
        buckets = _merge_small(buckets, params.c_min, params.c_max, rng)
        for b in buckets:
            if len(b) <= params.c_max:
                leaves.append(b)
            elif len(b) == len(idx):
                # duplicate-heavy data: no-progress bucket, same forced
                # permutation-halves split as ball_carve
                perm = rng.permutation(len(b))
                half = len(b) // 2
                stack.append((b[perm[:half]], depth + 1))
                stack.append((b[perm[half:]], depth + 1))
            else:
                stack.append((b, depth + 1))
    return leaves


def bit_lex_order(bits: np.ndarray) -> np.ndarray:
    """Stable lexicographic argsort of boolean rows (column 0 most
    significant).  Bits pack into big-endian uint64 words compared via
    ``np.lexsort``, so ANY number of bits keeps full precision — the old
    float64 accumulator (``key = key*2 + bit``) silently collided for
    n_bits > 53 (float64 mantissa), destroying the sort order."""
    n, n_bits = bits.shape
    words = []
    for w0 in range(0, n_bits, 64):
        chunk = bits[:, w0:w0 + 64]
        word = np.zeros(n, dtype=np.uint64)
        for i in range(chunk.shape[1]):
            word = (word << np.uint64(1)) | chunk[:, i].astype(np.uint64)
        words.append(word)
    # lexsort's LAST key is primary -> reverse so word 0 dominates
    return np.lexsort(tuple(reversed(words)))


def sorting_lsh_partition(
    x: np.ndarray,
    *,
    c_max: int = 1024,
    n_bits: int = 24,
    replicas: int = 1,
    seed: int = 0,
) -> list[np.ndarray]:
    """Sorting-LSH (A.1.3): lexicographic sort on concatenated hyperplane
    bits, consecutive groups of <= c_max.  Overlap via replication only."""
    leaves: list[np.ndarray] = []
    n, d = x.shape
    for r in range(replicas):
        rng = np.random.default_rng(seed + 15485863 * r)
        h = rng.standard_normal((n_bits, d)).astype(x.dtype)
        bits = (x @ h.T) >= 0.0  # [n, n_bits]
        order = bit_lex_order(bits)
        for s in range(0, n, c_max):
            leaves.append(order[s : s + c_max].astype(np.int64))
    return leaves


PARTITIONERS: dict[str, Callable] = {
    "rbc": lambda x, p: ball_carve_replicated(x, p),
    "binary": lambda x, p: binary_partition(
        x, c_max=p.c_max, replicas=max(p.replicas, 1), metric=p.metric, seed=p.seed
    ),
    "kmeans": lambda x, p: kmeans_carve(x, p),
    "sorting_lsh": lambda x, p: sorting_lsh_partition(
        x, c_max=p.c_max, replicas=max(p.replicas, 1), seed=p.seed
    ),
}


def partition(
    x: np.ndarray, params: RBCParams, method: Literal["rbc", "binary", "kmeans", "sorting_lsh"] = "rbc"
) -> list[np.ndarray]:
    return PARTITIONERS[method](x, params)


def leaves_to_padded(
    leaves: list[np.ndarray], c_max: int
) -> np.ndarray:
    """Stack leaves into a dense [L, c_max] int32 matrix, -1 padded.

    This is the TPU-facing representation: every leaf becomes one row of a
    regular batch so all-leaf distance matrices are a single batched GEMM.
    """
    out = np.full((len(leaves), c_max), -1, dtype=np.int32)
    for i, b in enumerate(leaves):
        if len(b) > c_max:
            raise ValueError(f"leaf {i} larger than c_max ({len(b)} > {c_max})")
        out[i, : len(b)] = b
    return out
