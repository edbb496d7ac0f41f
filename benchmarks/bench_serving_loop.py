"""Resilient serving-loop benchmark: Poisson load, straggler drain A/B,
and the deterministic fault drill's degraded-mode recall.

Three measurements, appended to BENCH_serving.json
(``common.append_bench_json``) so the loop's latency trajectory is
tracked across PRs:

  * **Poisson smoke load** — open-loop arrivals at a configurable rate
    against the continuous-batching loop; reports p50/p99 request
    latency, timeout rate and throughput, plus downshift counts under a
    burst (the SLO-degradation path exercised end to end).
  * **Straggler drain A/B** — the deterministic chain-graph straggler
    (one query that cannot converge inside any reasonable cap) batched
    with fast queries, served two-phase vs single-phase over identical
    requests.  Records the drain speedup for the CONVERGED majority and
    verifies their ids are bit-identical between modes — the acceptance
    bar for the drain being real, not a quality trade.
  * **Fault drill (8 forced devices, subprocess)** — the Issue-9
    schedule (1 of 8 shards killed mid-run, 5% NaN queries, one injected
    straggler) against the sharded loop; records healthy vs degraded
    recall, unhandled-error count (must be 0) and shard re-admission.
    Runs in a subprocess because ``--xla_force_host_platform_device_count``
    must precede jax init; skipped with ``--no-sharded``, which a TPU
    host requires (a child cannot reach the chips the parent holds).

  PYTHONPATH=src python benchmarks/bench_serving_loop.py --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import append_bench_json, dataset
from repro.core import pipnn
from repro.core.leaf import LeafParams
from repro.core.pipnn import PiPNNParams
from repro.core.rbc import RBCParams
from repro.core.serving import ServingIndex
from repro.launch.serve_loop import OperatingPoint, ServeLoop

BENCH_SERVING_JSON = (pathlib.Path(__file__).resolve().parent.parent
                      / "BENCH_serving.json")


def _build(x: np.ndarray):
    p = PiPNNParams(rbc=RBCParams(c_max=256, c_min=32, fanout=(4, 2)),
                    leaf=LeafParams(k=2), max_deg=32, seed=0)
    return pipnn.build(x, p)


def _percentiles(lat: list[float]) -> dict:
    a = np.asarray(lat, float)
    return {"p50_ms": round(float(np.percentile(a, 50)) * 1e3, 3),
            "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 3)}


def poisson_load(sv, q: np.ndarray, *, rate: float, seed: int,
                 deadline_s: float, chunk: int) -> dict:
    """Open-loop Poisson arrivals against the serving loop: requests are
    submitted when their arrival time comes due (sleeping while idle),
    the loop steps whenever work is queued."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=len(q)))
    loop = ServeLoop(sv, k=10, query_chunk=chunk, max_queue=4 * chunk,
                     slo_p99=deadline_s)
    # warm the compile caches so the first arrivals don't eat XLA time
    loop.submit(q[0])
    loop.run_until_drained()
    results, nexti = [], 0
    t0 = time.perf_counter()
    while nexti < len(q) or loop.queue_depth:
        now = time.perf_counter() - t0
        while nexti < len(q) and arrivals[nexti] <= now:
            try:
                loop.submit(q[nexti], deadline_s=deadline_s)
            except Exception:
                loop.counters["load_rejected"] += 1
            nexti += 1
        if loop.queue_depth:
            results.extend(loop.step())
        elif nexti < len(q):
            time.sleep(min(0.001, arrivals[nexti] - now))
    wall = time.perf_counter() - t0
    ok = [r for r in results if r.ok]
    lat = [r.latency for r in ok]
    return {
        "bench": "poisson_load",
        "rate_qps": rate,
        "requests": len(q),
        "served": len(ok),
        "timeout_rate": round(
            sum(r.error == "timeout" for r in results) / max(len(q), 1), 4),
        "rejected": int(loop.counters["load_rejected"]),
        "downshifts": int(loop.counters["downshift"]),
        "throughput_qps": round(len(ok) / wall, 1),
        **_percentiles(lat),
    }


def straggler_drain_ab(*, n: int = 2048, fast: int = 14, seed: int = 5
                       ) -> dict:
    """Two-phase vs single-phase over an identical batch holding one
    deterministic never-converging straggler (path graph, far-end
    query): the drain must beat single-phase wall-clock for the batch
    AND return bit-identical ids for every converged query."""
    d = 8
    rng = np.random.default_rng(seed)
    x = np.zeros((n, d), np.float32)
    x[:, 0] = np.arange(n)
    x[:, 1:] = 0.01 * rng.standard_normal((n, d - 1))
    graph = np.full((n, 2), -1, np.int32)
    graph[:, 0] = np.arange(n) - 1
    graph[: n - 1, 1] = np.arange(1, n)
    sv = ServingIndex.from_graph(graph, x, start=0)
    # fast queries sit within a few hops of the entry (they converge well
    # inside drain_iters); the single far-end query is the straggler
    q = np.concatenate([x[rng.integers(0, 5, size=fast)] + 0.001,
                        x[n - 1 :] + 0.001])
    kw = dict(k=4, query_chunk=fast + 1, straggler_chunk=2,
              ladder=(OperatingPoint("b8", beam=8, expansions=4),),
              drain_iters=12, backstop_iters=64)

    def run(two_phase: bool):
        loop = ServeLoop(sv, two_phase=two_phase, **kw)
        rids = [loop.submit(qi) for qi in q]
        loop.run_until_drained()          # warm both compiled variants
        loop = ServeLoop(sv, two_phase=two_phase, **kw)
        rids = [loop.submit(qi) for qi in q]
        t0 = time.perf_counter()
        res = {r.rid: r for r in loop.run_until_drained()}
        wall = time.perf_counter() - t0
        drained = [res[r].latency for r in rids
                   if res[r].ok and res[r].phase == 1]
        return loop, rids, res, wall, drained

    loop2, rids2, res2, wall2, drained2 = run(True)
    loop1, rids1, res1, wall1, drained1 = run(False)
    mismatches = 0
    for i in range(len(q)):
        a, b = res2[rids2[i]], res1[rids1[i]]
        if a.phase == 1 and not np.array_equal(a.ids, b.ids):
            mismatches += 1
    return {
        "bench": "straggler_drain_ab",
        "batch": len(q),
        "stragglers_rerun": int(loop2.counters["rerun_phase2"]),
        "drained_p99_ms": round(
            float(np.percentile(drained2, 99)) * 1e3, 3),
        "single_phase_p99_ms": round(
            float(np.percentile(drained1, 99)) * 1e3, 3),
        "drain_speedup": round(
            float(np.percentile(drained1, 99))
            / max(float(np.percentile(drained2, 99)), 1e-9), 2),
        "wall_two_phase_ms": round(wall2 * 1e3, 2),
        "wall_single_phase_ms": round(wall1 * 1e3, 2),
        "drained_bit_identical": mismatches == 0,
    }


def fault_drill(*, n: int = 4096, d: int = 32, n_queries: int = 128,
                seed: int = 0) -> dict:
    """The Issue-9 deterministic fault schedule against the sharded loop:
    1 of 8 shards killed for search calls [1, 6), one straggling shard,
    5% NaN queries.  Must run in a process where jax already sees 8
    devices (``fault_drill_subprocess`` arranges that from a plain run).
    """
    import jax
    from jax.sharding import Mesh

    from repro.core.beam_search import brute_force_knn, recall_at_k
    from repro.testing.faults import FaultPlan, inject_faults, poison_queries

    S = 8
    assert len(jax.devices()) == S, len(jax.devices())
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((n_queries, d)).astype(np.float32)
    idx = _build(x)
    mesh = Mesh(np.array(jax.devices()), ("shards",))
    ssv = ServingIndex.from_index(idx, x, mesh=mesh)
    truth = brute_force_knn(x, q, 10)
    r_healthy = recall_at_k(np.asarray(ssv.search(q, k=10, beam=32)),
                            truth, 10)
    qp, rows = poison_queries(q, 0.05, seed=7)
    plan = FaultPlan(shard_down={S - 1: (1, 6)}, straggle={2: 0.02})
    unhandled = 0
    with inject_faults(ssv, plan):
        loop = ServeLoop(ssv, k=10, query_chunk=16, straggler_chunk=8,
                         max_queue=256, probe_every=1)
        rid_to_row = {loop.submit(qp[i]): i for i in range(len(qp))}
        try:
            res = loop.run_until_drained()
            for _ in range(16):       # idle steps: probe readmits the shard
                loop.step()
                if not loop.index.down_shards:
                    break
        except Exception:
            unhandled += 1
            res = []
    ids = np.full((len(qp), 10), -1, np.int64)
    for r in res:
        if r.ok:
            ids[rid_to_row[r.rid]] = r.ids
    ok_rows = np.setdiff1d(np.arange(len(qp)), rows)
    r_deg = recall_at_k(ids[ok_rows], truth[ok_rows], 10)
    bad = sorted(rid_to_row[r.rid] for r in res if r.error)
    return {
        "bench": "fault_drill",
        "n_shards": S,
        "requests": len(qp),
        "completed": len(res),
        "unhandled_errors": unhandled,
        "poisoned": int(rows.size),
        "structured_errors": sum(1 for r in res if r.error),
        "errors_match_poisoned": bad == sorted(rows.tolist()),
        "recall_healthy": round(float(r_healthy), 4),
        "recall_degraded": round(float(r_deg), 4),
        "degraded_ratio": round(float(r_deg / max(r_healthy, 1e-9)), 4),
        "shard_readmitted": int(loop.counters["shards_readmitted"]),
    }


_FAULT_DRILL_CHILD = r"""
import json
from benchmarks.bench_serving_loop import fault_drill
print(json.dumps(fault_drill()))
"""


def fault_drill_subprocess() -> dict:
    """The fault drill in a child process that forces 8 CPU host devices.
    Never called on a TPU host: the parent holds the chips there (``main``
    refuses first).  A failed child fails the run."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    root = pathlib.Path(__file__).resolve().parent.parent
    env["PYTHONPATH"] = f"{root / 'src'}:{root}:{env.get('PYTHONPATH', '')}"
    proc = subprocess.run([sys.executable, "-c", _FAULT_DRILL_CHILD],
                          env=env, capture_output=True, text=True,
                          timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(f"fault-drill child failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--d", type=int, default=32)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=400.0)
    ap.add_argument("--deadline", type=float, default=2.0)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the 8-device fault-drill subprocess")
    args = ap.parse_args(argv)
    if not args.no_sharded:
        import jax

        if jax.default_backend() == "tpu":
            raise SystemExit("the fault drill runs on 8 forced CPU host "
                             "devices in a child process, which cannot reach "
                             "the chips this process holds; pass "
                             "--no-sharded on a TPU host")

    x, q = dataset(args.n, args.d, seed=args.seed)
    rng = np.random.default_rng(args.seed + 1)
    qq = q[rng.integers(0, len(q), args.requests)]
    idx = _build(x)
    sv = ServingIndex.from_index(idx, x)

    records = []
    rec = poisson_load(sv, qq, rate=args.rate, seed=args.seed,
                       deadline_s=args.deadline, chunk=args.chunk)
    records.append(rec)
    print(json.dumps(rec))
    rec = straggler_drain_ab()
    records.append(rec)
    print(json.dumps(rec))
    if not args.no_sharded:
        rec = fault_drill_subprocess()
        records.append(rec)
        print(json.dumps(rec))
    append_bench_json(records, path=BENCH_SERVING_JSON,
                      bench="serving_loop_smoke", n=args.n, d=args.d,
                      requests=args.requests)
    print(f"appended {len(records)} records to {BENCH_SERVING_JSON.name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
