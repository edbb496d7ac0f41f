"""Chip smoke: the main path, pipnn.build -> ServingIndex -> ServeLoop, once
on a TPU at SIFT1M scale, checked against brute force.

  python chip_smoke.py             # one chip: kernel phase, then end to end
  python chip_smoke.py --chips 4   # sharded serving over four chips only

Deployment: the shapes of ann-benchmarks ``sift-128-euclidean`` (1,000,000
base vectors, d=128, f32, L2, recall@10 over 1,000 queries), generated
from ``--seed`` by ``data.pipeline.make_vectors`` / ``make_queries``
(Gaussian mixture).  The index is built with ``PiPNNParams`` defaults
except RBC's level-0 leader count, set for this corpus's clusters
(``build_params``).

Phases (one chip):
  1. kernels — every Pallas kernel the TPU path selects, compiled for the
     chip at one real shape, against its ``kernels/ref.py`` oracle;
  2. end to end — build, pack ``ServingIndex`` in f32 and int8 (both must
     resolve to the HBM-streaming kernel by themselves), answer the
     queries through ``ServeLoop``, compare with a host brute-force top-10.
     Passes when f32 recall@10 >= 0.90 at some beam <= 128 and ServeLoop
     answered every request without an error.

With ``--chips 4`` only the sharded phase runs: the same corpus packed over
a 4-device mesh (``ShardedServingIndex``), answered with both routers, and
compared with the single-chip f32 index on device 0.

Every phase that fails raises, and the script exits non-zero.  It exits
non-zero, printing no result, when JAX finds no TPU.  The last line of
stdout is one JSON object: {"ok": true, "device": {...}}.  Times printed
are this script's own readings, not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

RECALL_BAR = 0.90
SHARDED_RECALL_TOL = 0.01
_T0 = time.perf_counter()


def _use_repo_src() -> None:
    """Import the package from this checkout's ``src``."""
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke.py: no package at {src}/repro; run it "
                         "from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def accelerator(n_chips: int) -> dict:
    """The device record of the last line; raises SystemExit when JAX finds
    no TPU or fewer than ``n_chips`` of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke.py: {n_chips} chips requested, JAX "
                         f"found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 1: kernels against their oracles
# ---------------------------------------------------------------------------

def _check(name: str, got, want, *, rtol: float = 0.0) -> float:
    """Max abs difference of ``got`` vs ``want``; raises on a mismatch.
    ``rtol`` (relative to the largest finite oracle magnitude) admits the
    f32 rounding of a different reduction order; 0 demands equality.
    Infinities must sit at the same places."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != oracle "
                             f"{want.shape}")
    fin = np.isfinite(want)
    if not np.array_equal(np.isfinite(got), fin) or not np.array_equal(
            got[~fin], want[~fin]):
        raise AssertionError(f"{name}: non-finite entries differ from the "
                             "oracle")
    diff = float(np.max(np.abs(got[fin].astype(np.float64)
                               - want[fin].astype(np.float64)),
                        initial=0.0))
    scale = float(np.max(np.abs(want[fin]), initial=1.0))
    log(f"kernel {name}: max abs diff {diff:.3e} (oracle scale "
        f"{scale:.3e})")
    if diff > rtol * scale:
        raise AssertionError(f"{name}: max abs diff {diff} exceeds "
                             f"{rtol} x {scale}")
    return diff


def kernel_phase(*, n_vmem: int = 16384, n_hbm: int = 1 << 20, d: int = 128,
                 nq: int = 64, c: int = 256, n_res: int = 65536,
                 l_max: int = 64, n_edges: int = 1 << 20, hash_bits: int = 12,
                 seed: int = 0, interpret: bool = False) -> dict:
    """Run each Pallas kernel of the TPU path once against its oracle.
    Returns {kernel: max abs diff}."""
    import jax
    import jax.numpy as jnp

    from repro.core.hashprune import hashprune_batch
    from repro.core.metrics import point_norms
    from repro.kernels import ref
    from repro.kernels.edge_hash import edge_hashes
    from repro.kernels.gather_distance import (fits_vmem, gather_distance,
                                               gather_distance_hbm,
                                               gather_distance_int8,
                                               gather_distance_int8_hbm)
    from repro.kernels.segmented_merge import merge_sorted_reservoirs

    rng = np.random.default_rng(seed)
    out = {}
    qs = jnp.asarray(rng.standard_normal((nq, d)), jnp.float32)
    qn = point_norms(qs, "l2")
    for where, n in (("vmem", n_vmem), ("hbm", n_hbm)):
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        ids = jnp.asarray(rng.integers(-1, n, (nq, c)), jnp.int32)
        norms = point_norms(x, "l2")
        x8, scales = ref.quantize_symmetric(x)
        if where == "vmem" and not interpret and not (
                fits_vmem(x) and fits_vmem(x8, scales)):
            raise AssertionError("the VMEM kernel shape must fit the VMEM "
                                 "budget")
        f32 = gather_distance if where == "vmem" else gather_distance_hbm
        i8 = gather_distance_int8 if where == "vmem" else \
            gather_distance_int8_hbm
        name = "gather_distance" if where == "vmem" else \
            "gather_distance_hbm"
        out[name] = _check(
            f"{name} [n={n}, d={d}, Q={nq}, C={c}]",
            f32(x, norms, qs, ids, interpret=interpret),
            ref.gather_distance_hbm_ref(x, norms, qs, ids), rtol=1e-5)
        name8 = name.replace("distance", "distance_int8")
        out[name8] = _check(
            f"{name8} [n={n}, d={d}, Q={nq}, C={c}]",
            i8(x8, scales, norms, qs, qn, ids, interpret=interpret),
            ref.gather_distance_int8_ref(x8, scales, norms, qs, qn, ids),
            rtol=1e-6)
        del x, x8

    # two valid reservoirs (rows sorted by (dist, id), one slot per hash
    # bucket, padding at the tail) from random candidate lists
    def reservoir(key):
        r = np.random.default_rng(key)
        cand = 2 * l_max
        ids = jnp.asarray(r.integers(0, 1 << 20, (n_res, cand)), jnp.int32)
        hs = jnp.asarray(r.integers(0, 1 << hash_bits, (n_res, cand)),
                         jnp.int32)
        ds = jnp.asarray(r.random((n_res, cand)), jnp.float32)
        return hashprune_batch(ids, hs, ds, l_max=l_max)

    a, b = reservoir(seed + 1), reservoir(seed + 2)
    got = merge_sorted_reservoirs(*a, *b, interpret=interpret)
    want = ref.merge_sorted_reservoirs_ref(*a, *b)
    for part, g, w in zip(("ids", "hashes", "dists"), got, want):
        out[f"merge_sorted_reservoirs.{part}"] = _check(
            f"merge_sorted_reservoirs.{part} [{n_res}, {l_max}]", g, w)

    s = jnp.asarray(rng.standard_normal((n_edges, hash_bits)), jnp.float32)
    t = jnp.asarray(rng.standard_normal((n_edges, hash_bits)), jnp.float32)
    out["edge_hashes"] = _check(f"edge_hashes [E={n_edges}, m={hash_bits}]",
                                edge_hashes(s, t, interpret=interpret),
                                ref.edge_hashes_ref(s, t))
    jax.block_until_ready(s)
    return out


# ---------------------------------------------------------------------------
# phase 2: build -> ServingIndex -> ServeLoop, against brute force
# ---------------------------------------------------------------------------

def make_corpus(n: int, n_queries: int, seed: int):
    from repro.data.pipeline import (VectorPipelineConfig, make_queries,
                                     make_vectors)

    cfg = VectorPipelineConfig(n=n, dim=128, seed=seed)
    return make_vectors(cfg), make_queries(cfg, n_queries)


def build_params(seed: int):
    """The smoke's build settings: ``PiPNNParams`` defaults except RBC's
    level-0 leader count, 1,250 (p_samp 0.00125, leader_cap 10000) where
    the defaults cap it at 1,000.

    Each of this corpus's 32 clusters is an isotropic 128-d Gaussian of
    ~31K points, and a 31,250-point single-cluster corpus built with the
    same per-cluster settings reproduced the 1M chip run's recall@10 to
    within 0.004 (CPU).  On that proxy at beam 128 these settings gave
    0.926; leaves of up to 8192 points (c_max 8192) gave 0.894, which is
    what the chip measured at 1M, and took 3.5x the build time
    (PERF.md section 4)."""
    from repro.core.pipnn import PiPNNParams
    from repro.core.rbc import RBCParams

    return PiPNNParams(rbc=RBCParams(p_samp=0.00125, leader_cap=10000),
                       seed=seed)


def build_index(x: np.ndarray, seed: int):
    from repro.core import pipnn

    t0 = time.perf_counter()
    index = pipnn.build(x, build_params(seed))
    wall = time.perf_counter() - t0
    log(f"build: n={x.shape[0]} d={x.shape[1]} wall {wall:.1f}s phases "
        + ", ".join(f"{k}={v:.1f}s" for k, v in index.timings.items())
        + f"; {index.stats['n_leaves']} leaves, average degree "
        f"{index.average_degree():.1f}")
    return index, wall


def brute_force(x: np.ndarray, queries: np.ndarray, k: int = 10):
    from repro.core.beam_search import brute_force_knn

    t0 = time.perf_counter()
    truth = brute_force_knn(x, queries, k, chunk=64)
    log(f"brute force top-{k} on the host: {time.perf_counter() - t0:.1f}s")
    return truth


def serve_loop_pass(sv, queries: np.ndarray, beam: int, k: int = 10):
    """Answer every query through one ``ServeLoop``; returns ([Q, k] ids,
    counters).  Raises unless every request came back without an error."""
    from repro.launch.serve_loop import OperatingPoint, ServeLoop

    loop = ServeLoop(sv, k=k, max_queue=len(queries),
                     ladder=(OperatingPoint(f"beam{beam}", beam=beam),))
    rids = [loop.submit(q) for q in queries]
    results = {r.rid: r for r in loop.run_until_drained()}
    errors = [r for r in results.values() if not r.ok]
    if len(results) != len(rids) or errors:
        raise AssertionError(
            f"ServeLoop answered {len(results)} of {len(rids)} requests, "
            f"{len(errors)} with errors "
            f"({sorted({r.error for r in errors})})")
    return np.stack([results[r].ids for r in rids]), dict(loop.counters)


def serve_phase(index, x: np.ndarray, queries: np.ndarray,
                truth: np.ndarray, *, beams=(64, 128),
                expect_path: str | None = "hbm") -> dict:
    """Pack the index in f32 and int8 and answer through ServeLoop at each
    beam.  Returns {dtype: {"kernel_path", "recall": {beam: r}}}."""
    from repro.core.beam_search import recall_at_k
    from repro.core.serving import ServingIndex

    out = {}
    for dtype in ("f32", "int8"):
        sv = ServingIndex.from_index(index, x,
                                     dtype=None if dtype == "f32" else "int8")
        log(f"ServingIndex {dtype}: kernel_path={sv.kernel_path} "
            f"({sv.device_bytes()} device bytes)")
        if expect_path is not None and sv.kernel_path != expect_path:
            raise AssertionError(f"{dtype} serving resolved kernel_path="
                                 f"{sv.kernel_path}, expected {expect_path}")
        rec = {}
        for beam in beams:
            t0 = time.perf_counter()
            ids, counters = serve_loop_pass(sv, queries, beam)
            rec[beam] = recall_at_k(ids, truth, 10)
            log(f"ServeLoop {dtype} beam={beam}: recall@10 {rec[beam]:.4f}, "
                f"{time.perf_counter() - t0:.1f}s incl. compile, "
                f"counters {counters}")
        out[dtype] = {"kernel_path": sv.kernel_path, "recall": rec}
        del sv
    best = max(out["f32"]["recall"].values())
    if best < RECALL_BAR:
        raise AssertionError(f"f32 recall@10 {best:.4f} < {RECALL_BAR} at "
                             f"every beam in {tuple(beams)}")
    return out


# ---------------------------------------------------------------------------
# --chips 4: sharded serving vs one chip
# ---------------------------------------------------------------------------

def sharded_phase(index, x: np.ndarray, queries: np.ndarray,
                  truth: np.ndarray, *, n_chips: int = 4,
                  beam: int = 128) -> dict:
    """Pack over an ``n_chips`` mesh, answer with both routers, compare
    recall@10 with the single-device f32 index."""
    import jax
    from jax.sharding import Mesh

    from repro.core.beam_search import recall_at_k
    from repro.core.serving import ServingIndex

    single = ServingIndex.from_index(index, x)
    base = recall_at_k(single.search(queries, k=10, beam=beam), truth, 10)
    log(f"single chip f32 beam={beam}: recall@10 {base:.4f} "
        f"(kernel_path={single.kernel_path})")
    del single
    mesh = Mesh(np.asarray(jax.devices()[:n_chips]), ("shards",))
    out = {"single": base}
    for router in ("all", "leaders"):
        sv = ServingIndex.from_index(index, x, mesh=mesh, router=router)
        devices = {s.device for s in sv.points.addressable_shards}
        if len(devices) != n_chips or len(sv.graph.sharding.device_set) \
                != n_chips:
            raise AssertionError(f"shards sit on {len(devices)} devices, "
                                 f"expected {n_chips}")
        t0 = time.perf_counter()
        r = recall_at_k(sv.search(queries, k=10, beam=beam), truth, 10)
        log(f"sharded router={router}: {sv.n_shards} shards on "
            f"{sorted(str(d) for d in devices)}, recall@10 {r:.4f} "
            f"(single {base:.4f}), {time.perf_counter() - t0:.1f}s incl. "
            f"compile")
        if abs(r - base) > SHARDED_RECALL_TOL:
            raise AssertionError(f"router={router} recall {r:.4f} is more "
                                 f"than {SHARDED_RECALL_TOL} from the single-"
                                 f"chip {base:.4f}")
        out[router] = r
        del sv
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--queries", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--beams", default="64,128")
    args = ap.parse_args(argv)

    _use_repo_src()
    import logging

    from repro.compile_cache import use_compile_cache

    device = accelerator(args.chips)
    logging.basicConfig(stream=sys.stderr, format="%(name)s: %(message)s")
    logging.getLogger("repro").setLevel(logging.INFO)
    log(f"device: {device}; compile cache {use_compile_cache()}")
    beams = tuple(int(b) for b in args.beams.split(","))

    if args.chips == 1:
        kernel_phase(seed=args.seed)
    x, queries = make_corpus(args.n, args.queries, args.seed)
    index, _ = build_index(x, args.seed)
    truth = brute_force(x, queries)
    if args.chips == 1:
        serve_phase(index, x, queries, truth, beams=beams)
    else:
        sharded_phase(index, x, queries, truth, n_chips=args.chips,
                      beam=max(beams))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
