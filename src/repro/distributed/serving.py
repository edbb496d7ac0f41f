"""Sharded SPMD serving: one partition-aligned index shard per device.

The single-device ``core.serving.ServingIndex`` tops out at whatever one
device can hold; the paper's billion-scale regime (and GGNN / the
multi-GPU indexing line in PAPERS.md) shard the index across devices and
merge per-shard results.  ``ShardedServingIndex`` is that serving shape,
built from the primitives the repo already has:

  * **Partition-aligned shards with a 1-hop halo.**  Every point joins
    the shard of its nearest shard leader (``core.leader_assign`` — the
    same Stage-1 RBC assignment primitive the build uses), so ownership
    is a DISJOINT partition and locality-preserving: most graph edges
    stay intra-shard.  Each shard then also carries GHOST rows — the
    out-of-shard endpoints of its members' edges — so NO graph edge is
    dropped (the GGNN-style halo): member rows keep their full neighbor
    lists under LOCAL renumbering, ghost rows keep whichever of their
    own edges happen to land in-shard.  Each shard has its own entry
    point (the owned member nearest the global entry) and a ``gids`` map
    back to global ids; shards pad to the largest row count so the
    stacked ``[S, m, ...]`` arrays are fixed-shape.
  * **Per-shard search under ``shard_map``.**  Each device runs the
    UNCHANGED multi-expansion beam search (``_beam_search_multi``) over
    its shard — same kernels (VMEM-resident or HBM-streaming per the
    shard's size, see ``beam_search.resolve_kernel_path``), same early
    exit — then maps beam ids local -> global through ``gids``.
  * **Query routing.**  ``router="all"`` (default) replicates every query
    to every shard — the recall-parity configuration: the merged result
    can only see MORE of the graph than a single-device search.
    ``router="leaders"`` probes only each query's ``n_probes`` nearest
    shard leaders (``leader_assign`` again, now as the query router) and
    masks the other shards' results out of the merge — the
    throughput-over-recall trade.
  * **Cross-shard top-k merge.**  A global id reaching two shards' beams
    (a halo replica) carries BIT-IDENTICAL distances on both — same row
    values, same query, same padded reduction extent — which is exactly
    the dedup contract of the engine's rank-based bounded merge
    (``beam_search.merge_block``): ``cross_shard_topk`` folds the ``S``
    beams into one sorted [Q, k] block with no sort anywhere.

``ServingIndex.from_index(..., mesh=...)``, ``pipnn.search(mesh=...)``
and ``launch.serve.Retriever(mesh=...)`` all route here.  On this
container the mesh is simulated CPU devices
(``--xla_force_host_platform_device_count``); the shard_map program is
identical on a real TPU pod slice.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import metrics as _metrics

ROUTERS = ("all", "leaders")


class AllShardsDown(RuntimeError):
    """Every shard is marked unhealthy — no result could be served.

    The serving loop treats this as fail-stop (nothing left to degrade
    to) rather than returning an all ``-1`` result that looks like an
    empty index."""


def _dist_to_point(x: np.ndarray, p: np.ndarray, metric: str) -> np.ndarray:
    """Host-side dissimilarity of every row of ``x`` to the single point
    ``p`` (entry-point selection; mirrors ``beam_search._dist_np``)."""
    ip = x @ p
    if metric == "mips":
        return -ip
    if metric == "cosine":
        return 1.0 - ip / np.maximum(
            np.linalg.norm(x, axis=1) * np.linalg.norm(p), 1e-30)
    return np.sum(x * x, axis=1) + p @ p - 2.0 * ip


@functools.partial(jax.jit, static_argnames=("k",))
def cross_shard_topk(ids_s: jax.Array, ds_s: jax.Array, *, k: int
                     ) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard result blocks into the global top-k.

    ``ids_s`` [S, Q, B] global ids (-1 = pad / masked), ``ds_s`` [S, Q, B]
    f32 (+inf at pads) -> (ids [Q, k], dists [Q, k]) sorted ascending by
    (dist, id) — ties break toward the smaller global id, padded with
    (-1, +inf) when fewer than ``k`` valid entries exist in the union.

    Built from the engine's own sort-free rank-based bounded merge
    (``beam_search.merge_block``): shard ownership partitions the
    dataset, and a halo replica reaching two shards' beams carries
    bit-identical distances on both (same row values, same query, same
    padded reduction extent) — exactly the merge's dedup contract, so
    folding one block at a time into a k-bounded beam is exact.  ``k``
    may exceed the per-shard beam width B — the union supplies up to
    ``S * B`` entries.

    The fold is a ``lax.scan`` over the shard axis (same left-to-right
    block order as the old Python loop, so bit-identical results): the
    traced program is one merge body regardless of S, which is what the
    mesh-shape stability rule (PIPS005) requires — a Python loop here
    would bake the shard count into the jaxpr and recompile per mesh
    size.
    """
    from repro.core.beam_search import merge_block

    _, nq, _ = ids_s.shape
    init = (jnp.full((nq, k), -1, jnp.int32),
            jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.zeros((nq, k), dtype=bool))

    def fold(carry, block):
        bids, bds = block
        return merge_block(*carry, bids.astype(jnp.int32), bds), None

    (ids, ds, _), _ = jax.lax.scan(fold, init, (ids_s, ds_s))
    return ids, ds


def cross_shard_topk_workspace_bytes(n_shards: int, nq: int, b: int,
                                     k: int) -> int:
    """Modeled XLA temp bytes of one ``cross_shard_topk`` merge: the
    [nq, k] carry triplet (ids + dists + visited) double-buffered through
    the scan plus one [nq, b] block's rank-merge scratch.  Independent of
    ``n_shards`` beyond the stacked INPUT blocks (arguments, not temp) —
    the scan body is one merge regardless of S.  Validated by the memory
    auditor (PIPM004); prices the S=256 envelope (PIPM003)."""
    carry = 2 * nq * k * 12
    block = nq * (b + k) * 32
    return carry + block


def sharded_search_workspace_bytes(nq: int, m: int, d: int, r: int,
                                   beam: int, expansions: int,
                                   n_shards: int) -> int:
    """Modeled per-device XLA temp bytes of one sharded search dispatch:
    the unchanged per-shard engine workspace over the [m, ...] local
    shard (``core.serving.engine_workspace_bytes``) plus the all-gathered
    [S, nq, beam] result blocks feeding the cross-shard merge.  Validated
    by the memory auditor when a multi-device mesh exists (PIPM004);
    prices the BigANN-1B S=256 envelope together with the packing model
    (``spmd_audit.price_shard_packing``) in PIPM003."""
    from repro.core.serving import engine_workspace_bytes

    engine = engine_workspace_bytes(nq, m, d, r, beam, expansions)
    gathered = 2 * n_shards * nq * beam * 8
    return engine + gathered


@dataclasses.dataclass
class ShardedServingIndex:
    """A PiPNN index packed as one partition-aligned shard per device.

    All shard arrays are stacked on a leading shard axis ``[S, ...]`` and
    consumed through ``shard_map`` over the single-axis ``mesh``; ``-1``
    pads everywhere (gids, local graph ids).
    """

    gids: jax.Array           # [S, m] int32 global ids, -1 pad
    graph: jax.Array          # [S, m, R] int32 LOCAL neighbor ids, -1 pad
    points: jax.Array         # [S, m, d] (f32 / downcast / int8)
    norms: jax.Array          # [S, m] f32 point norms (pre-downcast)
    starts: jax.Array         # [S] int32 per-shard local entry point
    leaders: jax.Array        # [S, d] f32 shard leader vectors (router)
    mesh: Mesh
    metric: str = "l2"
    scales: jax.Array | None = None   # [S, m] f32 dequant scales (int8)
    router: str = "all"
    n_probes: int = 2
    vmem_budget: int | None = None
    n_points: int = 0         # dataset size (each point OWNED by 1 shard)
    owned: np.ndarray | None = None   # [S] owned (member) row counts
    health: np.ndarray | None = None  # [S] bool shard health mask (None=all)
    _search_cache: dict = dataclasses.field(default_factory=dict,
                                            repr=False, compare=False)
    _dummy_scales: Any = dataclasses.field(default=None, repr=False,
                                           compare=False)
    _health_dev: Any = dataclasses.field(default=None, repr=False,
                                         compare=False)

    # Declared per-chunk host<->device transfer budget of ``search``:
    # queries in, merged ids out — everything between the shard search and
    # the cross-shard merge stays on device.  ``with_stats=True`` adds
    # three d2h crossings (hops, dist_comps, converged), and the first
    # search after a health-mask change adds one h2d (the re-committed
    # mask operand, cached until the next change).  The SPMD auditor
    # (PIPS004) replays a steady-state search under
    # ``core.transfers.ledger`` and gates against this.
    TRANSFER_BUDGET = {"h2d": 1, "d2h": 1}

    # ------------------------------------------------------------- sizing --
    @property
    def n_shards(self) -> int:
        return self.gids.shape[0]

    @property
    def shard_capacity(self) -> int:
        return self.gids.shape[1]

    @property
    def n(self) -> int:
        """Dataset size.  Live rows across shards exceed this by the halo
        replicas — count ``gids >= 0`` for the physical total."""
        return self.n_points

    @property
    def axis(self) -> str:
        return self.mesh.axis_names[0]

    def _shard_avals(self):
        """ShapeDtypeStructs of ONE shard's points/scales slice — all the
        kernel-path pricing reads.  ``self.points[0]`` would work too, but
        an eager getitem on a mesh-sharded array dispatches a gather (with
        an implicit scalar h2d for the index) on every search call."""
        pts = jax.ShapeDtypeStruct(self.points.shape[1:], self.points.dtype)
        scl = (None if self.scales is None else
               jax.ShapeDtypeStruct(self.scales.shape[1:],
                                    self.scales.dtype))
        return pts, scl

    @property
    def kernel_path(self) -> str:
        """The distance-kernel path each shard auto-selects, judged on the
        PER-SHARD [m, d] points block — the whole reason to shard is that
        the budget applies per device, not to the global index."""
        from repro.core import beam_search as _bs

        return _bs.resolve_kernel_path(*self._shard_avals(),
                                       vmem_budget=self.vmem_budget)

    def device_bytes(self, per_shard: bool = False,
                     breakdown: bool = False):
        """Device-resident footprint: the full stacked packing, or (with
        ``per_shard=True``) ONE shard's slice — what a single device
        actually holds under the mesh.  ``breakdown=True`` additionally
        splits the row-indexed bytes into member / ghost / pad shares
        (``halo_stats``) — the replication cost of the halo packing."""
        parts = (self.gids, self.graph, self.points, self.norms,
                 self.starts, self.leaders) + (
            () if self.scales is None else (self.scales,))
        total = sum(int(a.size) * a.dtype.itemsize for a in parts)
        total = total // self.n_shards if per_shard else total
        if not breakdown:
            return total
        hs = self.halo_stats()
        scale = 1.0 / self.n_shards if per_shard else 1.0
        return {
            "total": total,
            "member_bytes": int(hs["member_bytes"].sum() * scale),
            "ghost_bytes": int(hs["ghost_bytes"].sum() * scale),
            "pad_bytes": int(hs["pad_bytes"].sum() * scale),
            "halo_fraction": hs["halo_fraction"],
        }

    def halo_stats(self) -> dict[str, Any]:
        """Member / ghost / pad row accounting per shard — the replication
        cost of the GGNN-style 1-hop halo, and the measured data the SPMD
        auditor's footprint model (PIPS003) prices against.

        Returns per-shard int arrays ``members`` / ``ghosts`` / ``pads``
        (rows: owned partition members, halo replicas, -1 padding up to
        the stacked capacity ``m``), the matching ``*_bytes`` (at
        ``row_bytes`` — the per-row cost across gids+graph+points+norms
        [+scales]), and the scalar ``halo_fraction``: ghost rows' share
        of all LIVE rows across the packing — 0.0 means no replication,
        0.5 would mean every owned row is matched by a ghost copy."""
        if self.owned is None:
            raise ValueError(
                "halo_stats needs the owned-row counts recorded by "
                "from_graph; this packing was constructed without them")
        gids = np.asarray(self.gids)
        m = self.shard_capacity
        members = np.asarray(self.owned, np.int64)
        live = (gids >= 0).sum(axis=1).astype(np.int64)
        ghosts = live - members
        pads = m - live
        r, d = self.graph.shape[2], self.points.shape[2]
        row_bytes = (self.gids.dtype.itemsize
                     + r * self.graph.dtype.itemsize
                     + d * self.points.dtype.itemsize
                     + self.norms.dtype.itemsize
                     + (0 if self.scales is None
                        else self.scales.dtype.itemsize))
        total_live = max(int(live.sum()), 1)
        return {
            "members": members,
            "ghosts": ghosts,
            "pads": pads,
            "row_bytes": int(row_bytes),
            "member_bytes": members * row_bytes,
            "ghost_bytes": ghosts * row_bytes,
            "pad_bytes": pads * row_bytes,
            "halo_fraction": float(ghosts.sum() / total_live),
        }

    # ------------------------------------------------------------ packing --
    @classmethod
    def from_graph(
        cls,
        graph: np.ndarray,
        x: np.ndarray,
        start: int,
        *,
        mesh: Mesh,
        metric: str = "l2",
        dtype=None,
        vmem_budget: int | None = None,
        router: str = "all",
        n_probes: int = 2,
        seed: int = 0,
        halo: bool = True,
    ) -> "ShardedServingIndex":
        """Shard an adjacency matrix + dataset across ``mesh``'s devices.

        ``mesh`` must have a single axis; one shard per device.  Leaders
        are a deterministic sample of ``S`` dataset points (``seed``);
        every point joins its top-1 nearest leader (``leader_assign`` —
        ties toward the smaller leader index).  With ``halo`` (default)
        each shard also carries its members' out-of-shard neighbors as
        ghost rows so no graph edge is dropped; ``halo=False`` keeps the
        bare induced subgraph (smaller, lower recall).  Each shard's
        entry point is its OWNED member nearest the global entry
        ``x[start]``; it gets hub edges (``pipnn.link_entry_hubs``) and
        every row of a shard is made reachable from it
        (``pipnn.connect_from_start`` on the shard's local graph): the
        edges that link the global graph's components hang off the global
        entry point, which a shard without it does not hold.  ``dtype`` follows the single-device packing:
        ``None``/f32, a downcast dtype (e.g. bf16), or ``"int8"`` for
        the scalar-quantized copy (quantization is per-point/row-local,
        so sharding cannot change the bits — a ghost row quantizes
        identically in every shard that holds it).
        """
        from repro.core.leader_assign import leader_assign
        from repro.core.pipnn import connect_from_start, link_entry_hubs
        from repro.core.serving import _is_int8

        if len(mesh.axis_names) != 1:
            raise ValueError(f"serving mesh must have exactly one axis, "
                             f"got {mesh.axis_names}")
        if router not in ROUTERS:
            raise ValueError(f"router must be one of {ROUTERS}, "
                             f"got {router!r}")
        if router == "leaders" and int(n_probes) <= 0:
            # an empty probe set would mask EVERY shard out of the merge
            # and return all -1 ids — fail loudly at packing time instead
            raise ValueError(f"router='leaders' needs n_probes >= 1, "
                             f"got {n_probes}")
        s = int(np.prod(mesh.devices.shape))
        x = np.ascontiguousarray(x, dtype=np.float32)
        graph = np.ascontiguousarray(graph, dtype=np.int32)
        n, d = x.shape
        r = graph.shape[1]
        if n < s:
            raise ValueError(f"cannot shard {n} points over {s} devices")
        rng = np.random.default_rng(seed)
        leader_ids = np.sort(rng.choice(n, size=s, replace=False))
        leaders = x[leader_ids]
        assign = np.asarray(leader_assign(
            jnp.asarray(x), jnp.asarray(leaders), 1, metric=metric))[:, 0]
        # per-shard row lists: owned members (ascending global id) first,
        # then the 1-hop halo — every out-of-shard endpoint of a member's
        # edge rides along as a ghost row, so no edge is dropped
        rows, owned = [], np.zeros(s, np.int64)
        for i in range(s):
            mem = np.where(assign == i)[0]
            owned[i] = len(mem)
            if halo and len(mem):
                flat = graph[mem]
                flat = flat[flat >= 0]
                ghosts = np.unique(flat[assign[flat] != i])
            else:
                ghosts = np.empty(0, np.int64)
            rows.append(np.concatenate([mem, ghosts]))
        m = max(1, max(len(ridx) for ridx in rows))
        gids = np.full((s, m), -1, np.int32)
        graph_s = np.full((s, m, r), -1, np.int32)
        norms_s = np.zeros((s, m), np.float32)
        # norms in f32 BEFORE any downcast/quantization (the exact-norm
        # trick carries over shard by shard)
        norms = np.asarray(_metrics.point_norms(jnp.asarray(x), metric))
        int8 = _is_int8(dtype)
        if int8:
            from repro.kernels.ref import quantize_symmetric

            x8, scl = quantize_symmetric(jnp.asarray(x))
            xp, scl = np.asarray(x8), np.asarray(scl)
            pts_s = np.zeros((s, m, d), np.int8)
            # pad scales with 1.0, not 0.0: pad rows are all-zero int8
            # vectors, and a zero scale would be the only 0.0 the kernels'
            # rescale path ever sees
            scales_np = np.ones((s, m), np.float32)
        else:
            xp = x
            pts_s = np.zeros((s, m, d), np.float32)
        # per-shard entry: the OWNED member nearest the global entry point
        # (owned rows come first, so the argmin's position IS its local id)
        dstart = _dist_to_point(x, x[start], metric)
        starts_local = np.zeros(s, np.int32)
        for i in range(s):
            mem = rows[i][: owned[i]]
            if len(mem):
                starts_local[i] = np.argmin(dstart[mem])
        lookup = np.full(n, -1, np.int64)
        for i, ridx in enumerate(rows):
            c = len(ridx)
            gids[i, :c] = ridx
            lookup[:] = -1
            lookup[ridx] = np.arange(c)
            ga = graph[ridx]
            # member rows: every edge endpoint is in-shard by halo
            # construction; ghost rows keep whichever of their own edges
            # happen to land in-shard
            local = np.where(ga >= 0, lookup[np.maximum(ga, 0)], -1)
            if c:
                local, _ = link_entry_hubs(local.astype(np.int32), None,
                                           x[ridx], int(starts_local[i]),
                                           metric)
                local, _, _ = connect_from_start(
                    local, None, x[ridx], int(starts_local[i]), metric)
            graph_s[i, :c] = local
            norms_s[i, :c] = norms[ridx]
            pts_s[i, :c] = xp[ridx]
            if int8:
                scales_np[i, :c] = scl[ridx]
        pts_j = jnp.asarray(pts_s)
        if dtype is not None and not int8:
            pts_j = pts_j.astype(dtype)
        # commit every stacked array to its mesh placement NOW: shard-axis
        # arrays split over the devices, router leaders replicated.  A
        # plain jnp.asarray would land everything on device 0 and the jit
        # dispatch of the shard_map program would reshard the ENTIRE
        # packing device->devices on every single search call (an implicit
        # transfer jax performs silently — PIPS004's reason to exist).
        from jax.sharding import NamedSharding

        shard = NamedSharding(mesh, P(mesh.axis_names[0]))
        rep = NamedSharding(mesh, P())
        return cls(
            gids=jax.device_put(gids, shard),
            graph=jax.device_put(graph_s, shard),
            points=jax.device_put(pts_j, shard),
            norms=jax.device_put(norms_s, shard),
            starts=jax.device_put(starts_local, shard),
            leaders=jax.device_put(np.ascontiguousarray(leaders), rep),
            mesh=mesh, metric=metric,
            scales=(jax.device_put(scales_np, shard) if int8 else None),
            router=router, n_probes=int(n_probes), vmem_budget=vmem_budget,
            n_points=n, owned=owned.astype(np.int64),
        )

    @classmethod
    def from_index(cls, index, x: np.ndarray, *, mesh: Mesh, dtype=None,
                   **kw) -> "ShardedServingIndex":
        return cls.from_graph(index.graph, x, index.start, mesh=mesh,
                              metric=index.params.metric, dtype=dtype, **kw)

    # ------------------------------------------------------------- search --
    def _sharded_search_fn(self, *, beam, iters, expansions, early_exit,
                           kernel_path, interpret):
        """Compile (and cache) the shard_map'd per-shard search: every
        device runs the unchanged multi-expansion engine over its own
        shard and maps beam ids local -> global through its gids slice."""
        key = (beam, iters, expansions, early_exit, kernel_path, interpret,
               self.scales is not None)
        fn = self._search_cache.get(key)
        if fn is not None:
            return fn
        from repro.core.beam_search import _beam_search_multi

        int8 = self.scales is not None

        def body(gids, graph, points, norms, starts, scales, queries):
            ids, ds, hops, comps, conv = _beam_search_multi(
                graph[0], points[0], norms[0], queries, starts[0],
                scales[0] if int8 else None,
                beam=beam, iters=iters, metric=self.metric,
                expansions=expansions, early_exit=early_exit,
                kernel_path=kernel_path, interpret=interpret)
            g = gids[0]
            gid = jnp.where(ids >= 0, g[jnp.maximum(ids, 0)], -1)
            # a pad entry point (empty shard) carries gid -1: push its
            # distance to +inf so the cross-shard merge drops it
            ds = jnp.where(gid >= 0, ds, jnp.inf)
            return gid[None], ds[None], hops[None], comps[None], conv[None]

        p, rep = P(self.axis), P()
        sm = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(p, p, p, p, p, p, rep),
            out_specs=(p, p, p, p, p), check_vma=False)
        fn = jax.jit(sm)
        self._search_cache[key] = fn
        return fn

    # ------------------------------------------------------------- health --
    def _health_np(self) -> np.ndarray:
        """Host-side [S] bool shard health mask (lazily all-healthy)."""
        if self.health is None:
            self.health = np.ones(self.n_shards, dtype=bool)
        return self.health

    @property
    def healthy_shards(self) -> int:
        return int(self._health_np().sum())

    @property
    def down_shards(self) -> tuple[int, ...]:
        """Indices of tombstoned shards (empty when fully healthy)."""
        return tuple(int(i) for i in np.nonzero(~self._health_np())[0])

    def mark_shard_down(self, shard: int) -> None:
        """Tombstone a shard: its beams are masked out of every merge
        (router="all") / its leader is never probed (router="leaders")
        until :meth:`probe_shard` re-admits it.  The device mask operand
        is rebuilt ONCE here, not per search call."""
        h = self._health_np()
        h[int(shard)] = False
        self._health_dev = None

    def mark_shard_up(self, shard: int) -> None:
        h = self._health_np()
        h[int(shard)] = True
        self._health_dev = None

    def probe_shard(self, shard: int, probe=None) -> bool:
        """Attempt to re-admit a tombstoned shard.

        The shard is optimistically marked up, then ``probe(shard)`` must
        return truthy without raising; on failure the tombstone is
        restored.  The default probe serves the shard's own leader vector
        through ``search`` and checks a valid id comes back — under fault
        injection (``repro.testing.faults``) that call raises while the
        shard's outage is still scheduled, so probing naturally fails
        until the fault clears.  Returns True iff the shard is healthy
        after the call (idempotent on already-healthy shards)."""
        i = int(shard)
        if self._health_np()[i]:
            return True
        if probe is None:
            probe = self._default_probe
        self.mark_shard_up(i)
        try:
            ok = bool(probe(i))
        except Exception:
            ok = False
        if not ok:
            self.mark_shard_down(i)
        return ok

    def _default_probe(self, shard: int) -> bool:
        q = np.asarray(self.leaders)[int(shard)][None, :]
        ids = self.search(np.ascontiguousarray(q, np.float32), k=1, beam=4)
        return bool(ids[0, 0] >= 0)

    def _health_operand(self) -> jax.Array:
        """Replicated device copy of the health mask, rebuilt only when
        the mask changes (``mark_shard_down`` / ``mark_shard_up``) — built
        per call it would be a fresh h2d transfer on every search, blowing
        the PIPS004 budget."""
        if self._health_dev is None:
            from jax.sharding import NamedSharding

            from repro.core.transfers import to_device

            self._health_dev = to_device(
                np.ascontiguousarray(self._health_np()),
                NamedSharding(self.mesh, P()))
        return self._health_dev

    def _active_mask(self, queries: jax.Array) -> jax.Array | None:
        """Bool mask ([S, Q] or a broadcastable [S, 1]) of which shards'
        beams enter the merge for which query: the router's probe set
        AND'd with the shard health mask.  ``None`` — the steady state:
        router="all" with every shard healthy — skips masking entirely,
        so healthy serving stays bit-identical to (and as transfer-lean
        as) the pre-health code path."""
        health = self._health_np()
        if not health.any():
            raise AllShardsDown(
                f"all {self.n_shards} shards are marked down")
        healthy = bool(health.all())
        hdev = None if healthy else self._health_operand()
        if self.router == "all":
            return None if healthy else hdev[:, None]
        if int(self.n_probes) <= 0:
            # guard direct construction too: from_graph already rejects
            # this, but an empty probe set silently masking every shard
            # (all -1 results) must never reach the merge
            raise ValueError(f"router='leaders' needs n_probes >= 1, "
                             f"got {self.n_probes}")
        from repro.core.leader_assign import leader_assign

        # a dead shard's leader is masked out of the probe distance
        # matrix, so each query re-probes its next-best HEALTHY leaders
        # instead of silently losing a probe slot
        probes = min(int(self.n_probes), int(health.sum()))
        probe = leader_assign(queries, self.leaders, probes,
                              metric=self.metric,
                              leader_valid=hdev)           # [Q, probes]
        sids = jnp.arange(self.n_shards, dtype=probe.dtype)
        mask = jnp.any(probe[None, :, :] == sids[:, None, None], axis=2)
        return mask if healthy else mask & hdev[:, None]

    def _scales_operand(self) -> jax.Array:
        """The scales argument of the shard_map program: the real [S, m]
        scales (int8 packing) or a cached mesh-committed [S, 1] dummy the
        f32 body ignores — rebuilt per call it would be a fresh implicit
        h2d transfer on every search."""
        if self.scales is not None:
            return self.scales
        if self._dummy_scales is None:
            from jax.sharding import NamedSharding

            self._dummy_scales = jax.device_put(
                np.zeros((self.n_shards, 1), np.float32),
                NamedSharding(self.mesh, P(self.axis)))
        return self._dummy_scales

    def search(
        self,
        queries: np.ndarray,
        *,
        k: int = 10,
        beam: int = 32,
        expansions: int = 4,
        iters: int | None = None,
        early_exit: bool = True,
        kernel_path: str | None = None,
        interpret: bool | None = None,
        query_chunk: int | None = None,
        with_stats: bool = False,
    ):
        """Serve a query batch over the mesh; [Q, k] global ids (int64,
        -1-padded).  Semantics mirror ``ServingIndex.search``: per shard
        the multi-expansion beam search runs unchanged (``beam`` is the
        PER-SHARD beam width), then the ``router`` decides which shards'
        beams enter the cross-shard top-k merge.  ``query_chunk`` bounds
        the per-dispatch batch exactly like the single-device path: small
        batches pad UP to the chunk so every dispatch reuses one compiled
        shard_map program instead of compiling per distinct nq.
        ``with_stats=True`` adds per-query telemetry summed over the
        shards that served the query, plus the resolved kernel path,
        routing settings and the packing's halo fraction.

        The boundary is hardened exactly like the single-device path:
        ``k``/``beam`` must be >= 1 and NaN/Inf query rows raise a
        structured ``InvalidQueryError`` (``core.validation``).  Shards
        tombstoned by :meth:`mark_shard_down` are masked out of the merge
        (router="all") or re-probed around (router="leaders"); when all
        shards are down the call raises :class:`AllShardsDown`.

        Host traffic per chunk is exactly the declared
        ``TRANSFER_BUDGET``: queries in (``core.transfers.to_device``,
        committed replicated to the mesh), merged ids out
        (``to_host``) — the per-shard beams and the cross-shard merge
        never leave the devices.  ``with_stats`` adds the three telemetry
        d2h crossings (hops, dist_comps, converged).
        """
        from jax.sharding import NamedSharding

        from repro.core import beam_search as _bs
        from repro.core.transfers import to_device, to_host
        from repro.core.validation import (validate_queries,
                                           validate_search_params)

        if query_chunk is not None and int(query_chunk) <= 0:
            raise ValueError(f"query_chunk must be >= 1, got {query_chunk}")
        validate_search_params(k=k, beam=beam)
        q = validate_queries(queries, dim=int(self.points.shape[-1]))
        nq = q.shape[0]
        iters_cap = int(iters if iters is not None
                        else _bs.default_iters(beam))
        path = _bs.resolve_kernel_path(
            *self._shard_avals(),
            kernel_path=kernel_path, vmem_budget=self.vmem_budget)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if nq == 0:
            out = np.full((0, k), -1, dtype=np.int64)
            if with_stats:
                return out, self._stats(np.empty((0,), np.int32),
                                        np.empty((0,), np.int32),
                                        np.empty((0,), bool),
                                        expansions, iters_cap, path)
            return out
        fn = self._sharded_search_fn(
            beam=beam, iters=iters_cap, expansions=int(expansions),
            early_exit=bool(early_exit), kernel_path=path,
            interpret=bool(interpret))
        scales = self._scales_operand()
        replicated = NamedSharding(self.mesh, P())
        chunk = int(query_chunk) if query_chunk else nq
        ids_parts, hops_parts, comps_parts, conv_parts = [], [], [], []
        for c0 in range(0, nq, chunk):
            qc = q[c0 : c0 + chunk]
            pad = chunk - qc.shape[0]
            if pad:
                qc = np.pad(qc, ((0, pad), (0, 0)))
            qj = to_device(qc, replicated)
            ids_s, ds_s, hops_s, comps_s, conv_s = fn(
                self.gids, self.graph, self.points, self.norms,
                self.starts, scales, qj)               # [S, Q, B] / [S, Q]
            active = self._active_mask(qj)
            if active is not None:
                ids_s = jnp.where(active[:, :, None], ids_s, -1)
                ds_s = jnp.where(active[:, :, None], ds_s, jnp.inf)
                hops_s = jnp.where(active, hops_s, 0)
                comps_s = jnp.where(active, comps_s, 0)
                # a shard that did not serve the query cannot be its
                # straggler: converged is the AND over ACTIVE shards only
                conv_s = jnp.where(active, conv_s, True)
            ids, _ = cross_shard_topk(ids_s, ds_s, k=k)
            take = chunk - pad
            ids_parts.append(to_host(ids)[:take])
            if with_stats:
                hops_parts.append(to_host(
                    jnp.sum(hops_s, axis=0, dtype=jnp.int32))[:take])
                comps_parts.append(to_host(
                    jnp.sum(comps_s, axis=0, dtype=jnp.int32))[:take])
                conv_parts.append(to_host(
                    jnp.all(conv_s, axis=0))[:take])
        out = _bs.pad_ids(np.concatenate(ids_parts, axis=0),
                          k).astype(np.int64)
        if with_stats:
            return out, self._stats(
                np.concatenate(hops_parts), np.concatenate(comps_parts),
                np.concatenate(conv_parts).astype(bool),
                expansions, iters_cap, path)
        return out

    def _stats(self, hops, comps, converged, expansions, iters_cap, path
               ) -> dict[str, Any]:
        stats = {
            "hops": hops,
            "dist_comps": comps,
            "converged": converged,
            "expansions": int(expansions),
            "iters_cap": int(iters_cap),
            "kernel_path": path,
            "n_shards": self.n_shards,
            "healthy_shards": self.healthy_shards,
            "router": self.router,
        }
        if self.router == "leaders":
            stats["n_probes"] = min(int(self.n_probes), self.healthy_shards)
        if self.owned is not None:
            stats["halo_fraction"] = self.halo_stats()["halo_fraction"]
        return stats
