"""Quickstart: build a PiPNN index, query it, check recall.

  PYTHONPATH=src python examples/quickstart.py
"""
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import pipnn
from repro.core.beam_search import brute_force_knn, recall_at_k
from repro.core.leaf import LeafParams
from repro.core.pipnn import PiPNNParams
from repro.core.rbc import RBCParams
from repro.data.pipeline import VectorPipelineConfig, make_queries, make_vectors


def main():
    use_compile_cache()
    # 1. data: 16k Gaussian-mixture vectors, 200 held-out queries
    cfg = VectorPipelineConfig(n=16384, dim=48, n_clusters=64, seed=0)
    x = make_vectors(cfg)
    queries = make_queries(cfg, 200)

    # 2. build — the paper's pipeline: RBC partition -> leaf 2-NN via
    #    batched GEMM -> HashPrune -> final RobustPrune
    params = PiPNNParams(
        rbc=RBCParams(c_max=512, c_min=64, fanout=(4, 2)),
        leaf=LeafParams(k=3),
        hash_bits=12, l_max=64, max_deg=32, alpha=1.3, seed=0,
    )
    t0 = time.perf_counter()
    index = pipnn.build(x, params)
    print(f"built index over {x.shape[0]} points in "
          f"{time.perf_counter() - t0:.2f}s "
          f"(phases: { {k: round(v, 2) for k, v in index.timings.items()} })")
    print(f"average degree {index.average_degree():.1f}, "
          f"{index.stats['n_leaves']} leaves, "
          f"point repeat {index.stats['point_repeat']:.1f}x")

    # 3. query with beam search; 10@10 recall vs brute force
    t0 = time.perf_counter()
    found = pipnn.search(index, x, queries, k=10, beam=96)
    qps = len(queries) / (time.perf_counter() - t0)
    truth = brute_force_knn(x, queries, 10)
    print(f"10@10 recall {recall_at_k(found, truth, 10):.3f} "
          f"at {qps:.0f} QPS (beam 96)")


if __name__ == "__main__":
    main()
