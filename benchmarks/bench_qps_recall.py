"""Fig. 5: QPS-vs-recall curves for PiPNN (1 and 2 replicas) vs Vamana,
plus the serving-engine comparison the multi-expansion PR is about:

  * ``serve_E{1,4}`` — the device-resident multi-expansion serving path
    (``ServingIndex``: prepacked graph/points/norms, sort-free rank
    merges, early exit) at expansion widths 1 and 4,
  * ``serve_i8``    — the same engine over the scalar-quantized int8
    packing (int8 points + per-point f32 scales, exact norm terms); its
    summary row records the recall delta vs f32 serving and the device
    footprint of both packings,
  * ``single``      — the legacy one-expansion-per-step double-sort scan
    (``beam_search_single``), the pre-ServingIndex baseline,
  * ``np_oracle``   — the pointer-chasing numpy reference, timed on a
    query subset (it is per-query host code by design).

Two sweeps ride along for the sharded-serving PR:

  * an expansion-width (E) sweep at the serving beam, isolating the
    multi-expansion knob from the beam knob,
  * a sharded SPMD sweep over 1/2/4/8 devices — on CPU each point runs
    in a SUBPROCESS with ``--xla_force_host_platform_device_count`` (the
    flag must be set before jax initializes); on a TPU host the points
    run in this process over its chips — and each builds the same
    deterministic index, and serves through the mesh-sharded
    ``ShardedServingIndex`` (replicate-to-all router, halo shards,
    cross-shard merge); rows record recall parity vs the parent's
    single-device serving and per-shard footprints.

Emits one row per (index, engine, beam) point so the full trade-off curve
is in the CSV; the summary rows report QPS at the 0.9-recall operating
point, and everything is appended to BENCH_qps.json
(``common.append_bench_json``) so the serving trajectory is tracked
across PRs — including the multi-expansion-vs-single-expansion speedup
and the int8-vs-f32 serving deltas.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.common import (BENCH_QPS_JSON, Row, append_bench_json,
                               dataset, ground_truth, qps_at_recall, timed)
from repro.core import pipnn
from repro.core.baselines.vamana import VamanaParams, build_vamana
from repro.core.beam_search import beam_search_np, pad_ids, recall_at_k
from repro.core.leaf import LeafParams
from repro.core.pipnn import PiPNNParams
from repro.core.rbc import RBCParams
from repro.core.serving import ServingIndex

N, D = 4096, 32
NP_QUERIES = 32   # subset for timing the per-query host oracle
E_SWEEP = (1, 2, 4, 8)      # expansion widths at the serving beam
SHARD_DEVICES = (1, 2, 4, 8)
SHARD_BEAM = 32


def _shard_params() -> PiPNNParams:
    """The pipnn_1rep build, shared between parent and sharded children so
    every sweep point serves the SAME graph."""
    return PiPNNParams(rbc=RBCParams(c_max=256, c_min=32, fanout=(4, 2)),
                       leaf=LeafParams(k=2), max_deg=32, seed=0)


def _sharded_point(ndev: int) -> dict:
    """One sharded sweep point over the first ``ndev`` devices of this
    process.  Prints nothing; returns the record."""
    import jax

    from benchmarks.common import dataset, ground_truth, timed
    from jax.sharding import Mesh
    from repro.core.serving import ServingIndex

    assert len(jax.devices()) >= ndev, (len(jax.devices()), ndev)
    x, q = dataset(N, D)
    truth = ground_truth(N, D)
    idx = pipnn.build(x, _shard_params())
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("shards",))
    ssv = ServingIndex.from_index(idx, x, mesh=mesh)
    fn = lambda: ssv.search(q, k=10, beam=SHARD_BEAM, expansions=4)
    ids, _ = timed(fn)                        # warm-up/compile
    ids, secs = timed(fn, repeat=3)
    r = recall_at_k(pad_ids(ids, 10), truth[:, :10], 10)
    return {
        "engine": "serve_sharded", "ndev": ndev, "beam": SHARD_BEAM,
        "recall": round(float(r), 4),
        "qps": round(q.shape[0] / max(secs, 1e-9), 1),
        "per_shard_bytes": ssv.device_bytes(per_shard=True),
        "shard_capacity": ssv.shard_capacity,
        "kernel_path": ssv.kernel_path,
    }


def _run_sharded_sweep() -> list[dict]:
    """The sharded sweep points; a failed point fails the run.

    On a TPU the points run in this process over the host's own chips
    (a child process could not reach a chip this process holds), up to
    the number of chips it has.  On CPU each point is a subprocess whose
    ``--xla_force_host_platform_device_count`` forces its device count
    (the flag must precede jax init)."""
    import jax

    if jax.default_backend() == "tpu":
        have = len(jax.devices())
        return [_sharded_point(ndev) for ndev in SHARD_DEVICES
                if ndev <= have]
    out = []
    for ndev in SHARD_DEVICES:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count={ndev}"
                            ).strip()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_qps_recall",
             "--sharded-child", str(ndev)],
            capture_output=True, text=True, env=env, timeout=1200)
        if proc.returncode != 0:
            raise RuntimeError(f"sharded sweep point ndev={ndev} failed: "
                               f"{proc.stderr.strip()[-300:]}")
        out.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    return out


def run() -> list[Row]:
    import jax.numpy as jnp

    from repro.core import beam_search as bs

    x, q = dataset(N, D)
    truth = ground_truth(N, D)
    rows: list[Row] = []
    records: list[dict] = []

    indexes = {}
    for reps in (1, 2):
        p = PiPNNParams(rbc=RBCParams(c_max=256, c_min=32, fanout=(4, 2),
                                      replicas=reps),
                        leaf=LeafParams(k=2), max_deg=32, seed=0)
        idx = pipnn.build(x, p)
        indexes[f"pipnn_{reps}rep"] = (idx.graph, idx.start)
    g, start, _ = build_vamana(x, VamanaParams(max_deg=32, beam=48, passes=1))
    indexes["vamana_1pass"] = (g, start)

    xj, qj = jnp.asarray(x), jnp.asarray(q)
    for name, (graph, start) in indexes.items():
        gj = jnp.asarray(graph)
        sv = ServingIndex.from_graph(graph, x, start)
        sv8 = ServingIndex.from_graph(graph, x, start, dtype="int8")
        engines = {
            "serve_E1": lambda beam: sv.search(q, k=10, beam=beam,
                                               expansions=1),
            "serve_E4": lambda beam: sv.search(q, k=10, beam=beam,
                                               expansions=4),
            "serve_i8": lambda beam: sv8.search(q, k=10, beam=beam,
                                                expansions=4),
            "single": lambda beam: np.asarray(bs.beam_search_single(
                gj, xj, qj, start=start, beam=beam,
                iters=bs.default_iters(beam))[0]),
        }
        at09 = {}
        for ename, efn in engines.items():
            for beam in (8, 16, 32, 64):
                ids, _ = timed(efn, beam)
                ids, secs = timed(efn, beam, repeat=3)
                # -1 padding keeps beam<10 an honest 10@10 number
                r = recall_at_k(pad_ids(ids, 10), truth[:, :10], 10)
                qps = q.shape[0] / secs
                rows.append((f"qps_recall/{name}/{ename}/beam{beam}",
                             secs / q.shape[0] * 1e6,
                             f"recall={r:.3f} qps={qps:.0f}"))
                records.append({"index": name, "engine": ename, "beam": beam,
                                "recall": round(r, 4), "qps": round(qps, 1)})
            qps, r, beam = qps_at_recall(
                graph, start, x, q, truth, target=0.9, search_ids_fn=efn)
            at09[ename] = (qps, r, beam)
            rows.append((f"qps_recall/{name}/{ename}/at0.9",
                         1e6 / max(qps, 1e-9),
                         f"qps={qps:.0f} recall={r:.3f} beam={beam}"))
            records.append({"index": name, "engine": ename, "at": 0.9,
                            "beam": beam, "recall": round(r, 4),
                            "qps": round(qps, 1)})
        # the acceptance delta: multi-expansion serving vs the legacy scan
        speedup = at09["serve_E4"][0] / max(at09["single"][0], 1e-9)
        rows.append((f"qps_recall/{name}/serve_vs_single_at0.9", 0.0,
                     f"speedup={speedup:.2f}x"))
        records.append({"index": name, "metric_name": "serve_vs_single_at0.9",
                        "speedup": round(speedup, 2)})
        # int8 serving deltas vs f32: recall at the operating points +
        # device footprint of both packings
        r_delta = at09["serve_E4"][1] - at09["serve_i8"][1]
        qps_ratio = at09["serve_i8"][0] / max(at09["serve_E4"][0], 1e-9)
        rows.append((f"qps_recall/{name}/int8_vs_f32_at0.9", 0.0,
                     f"recall_delta={r_delta:.4f} qps_ratio={qps_ratio:.2f} "
                     f"bytes={sv8.device_bytes()}/{sv.device_bytes()}"))
        records.append({"index": name, "metric_name": "int8_vs_f32_at0.9",
                        "recall_delta": round(r_delta, 4),
                        "qps_ratio": round(qps_ratio, 2),
                        "device_bytes_i8": sv8.device_bytes(),
                        "device_bytes_f32": sv.device_bytes()})
        # np pointer-chasing oracle on a subset (recall parity + QPS scale)
        op_beam = at09["serve_E4"][2]
        qs = q[:NP_QUERIES]

        def run_np():
            out = np.full((NP_QUERIES, 10), -1, dtype=np.int64)
            for i, qq in enumerate(qs):
                ids, _, _ = beam_search_np(graph, x, qq, start=start,
                                           beam=op_beam)
                out[i, : min(10, len(ids))] = ids[:10]
            return out

        ids_np, secs = timed(run_np)
        r_np = recall_at_k(ids_np, truth[:NP_QUERIES, :10], 10)
        qps_np = NP_QUERIES / secs
        rows.append((f"qps_recall/{name}/np_oracle/beam{op_beam}",
                     secs / NP_QUERIES * 1e6,
                     f"recall={r_np:.3f} qps={qps_np:.0f}"))
        records.append({"index": name, "engine": "np_oracle", "beam": op_beam,
                        "recall": round(r_np, 4), "qps": round(qps_np, 1),
                        "n_queries": NP_QUERIES})
    # ---- expansion-width sweep at the serving beam (pipnn_1rep) --------
    graph, start = indexes["pipnn_1rep"]
    sv = ServingIndex.from_graph(graph, x, start)
    r_single = 0.0
    for e in E_SWEEP:
        fn = lambda: sv.search(q, k=10, beam=SHARD_BEAM, expansions=e)
        ids, _ = timed(fn)                       # warm-up/compile
        ids, secs = timed(fn, repeat=3)
        r = recall_at_k(pad_ids(ids, 10), truth[:, :10], 10)
        qps = q.shape[0] / max(secs, 1e-9)
        rows.append((f"qps_recall/pipnn_1rep/E{e}/beam{SHARD_BEAM}",
                     secs / q.shape[0] * 1e6,
                     f"recall={r:.3f} qps={qps:.0f}"))
        records.append({"index": "pipnn_1rep", "engine": "serve",
                        "expansions": e, "beam": SHARD_BEAM,
                        "recall": round(r, 4), "qps": round(qps, 1)})
        if e == 4:
            r_single = r                         # sharded-parity reference
    # ---- sharded SPMD sweep (subprocess per simulated device count) ----
    for rec in _run_sharded_sweep():
        rec["recall_delta_vs_single"] = round(r_single - rec["recall"], 4)
        rows.append((
            f"qps_recall/pipnn_1rep/sharded_ndev{rec['ndev']}"
            f"/beam{SHARD_BEAM}",
            q.shape[0] / max(rec["qps"], 1e-9) / q.shape[0] * 1e6,
            f"recall={rec['recall']:.3f} qps={rec['qps']:.0f} "
            f"delta={rec['recall_delta_vs_single']:+.4f} "
            f"per_shard_bytes={rec['per_shard_bytes']}"))
        records.append({"index": "pipnn_1rep", **rec})
    append_bench_json(records, path=BENCH_QPS_JSON, bench="qps_recall",
                      n=N, d=D, n_queries=q.shape[0])
    return rows


if __name__ == "__main__":
    # sharded-sweep child entry: the parent spawns
    #   python -m benchmarks.bench_qps_recall --sharded-child NDEV
    # with XLA_FLAGS forcing NDEV host devices (set before jax init).
    if "--sharded-child" in sys.argv:
        n = int(sys.argv[sys.argv.index("--sharded-child") + 1])
        print(json.dumps(_sharded_point(n)))
        sys.exit(0)
    for row in run():
        print(",".join(str(c) for c in row))
