"""Downstream task (paper Fig. 6): build a 95%-recall k-NN graph — the
substrate for clustering / dedup pipelines — and compare against the
Vamana-based route.

  PYTHONPATH=src python examples/knn_graph.py
"""
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.knn_graph import knn_graph_pipnn, knn_graph_recall
from repro.core.leaf import LeafParams
from repro.core.pipnn import PiPNNParams
from repro.core.rbc import RBCParams
from repro.data.pipeline import VectorPipelineConfig, make_vectors


def main():
    use_compile_cache()
    x = make_vectors(VectorPipelineConfig(n=8192, dim=32, n_clusters=32,
                                          seed=1))
    params = PiPNNParams(
        rbc=RBCParams(c_max=256, c_min=32, fanout=(4, 2)),
        leaf=LeafParams(k=3), l_max=64, max_deg=32, seed=0)
    knn, timings = knn_graph_pipnn(x, k=10, beam=48, params=params)
    recall = knn_graph_recall(x, knn, k=10, sample=512)
    print(f"k-NN graph over {x.shape[0]} points: "
          f"build {timings['build']:.2f}s + query {timings['query']:.2f}s "
          f"= {timings['total']:.2f}s, recall {recall:.3f}")
    assert recall >= 0.90, "quality bar"
    # example downstream use: mutual-kNN connected components (clustering)
    n = x.shape[0]
    mutual = set()
    kset = [set(r[r >= 0].tolist()) for r in knn]
    for i in range(n):
        for j in knn[i]:
            if j >= 0 and i in kset[j]:
                mutual.add((min(i, int(j)), max(i, int(j))))
    parent = list(range(n))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for a, b in mutual:
        parent[find(a)] = find(b)
    n_comp = len({find(i) for i in range(n)})
    print(f"mutual-kNN graph: {len(mutual)} edges, "
          f"{n_comp} connected components (planted: 32 clusters)")


if __name__ == "__main__":
    main()
