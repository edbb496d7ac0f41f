"""Seeded corpora and queries, from a configuration's ``data`` section.

The generator is a copy of the program's ``data.pipeline.make_vectors`` /
``make_queries``: a Gaussian mixture of ``clusters`` isotropic unit
clusters whose centres are drawn with standard deviation
``cluster_scale``.  It is copied so that a change to the program cannot
change the data it is measured on.  The queries come from the same
mixture.  Everything else about the data is a parameter of the file:

* ``cluster_weights`` — ``"equal"`` (each point picks a cluster
  uniformly, as the program's generator does) or ``{"zipf": s}``
  (cluster ``i`` drawn with weight ``1 / (i + 1) ** s``: skewed sizes);
* ``grid`` (optional) — ``{"scale": a, "lo": l, "hi": h}`` maps every
  coordinate to the integer ``clip(round(a v), l, h)``, as integer
  descriptors (SIFT, BIGANN: ``0 .. 255``) are; points and queries are
  still handed to the program as float32.
"""
from __future__ import annotations

import numpy as np

# A run's seed is any whole number; the program's RNG keys take 31 bits.
_SEED_MASK = 0x7FFFFFFF


def streams(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for each random choice a run makes, all
    derived from ``--seed``."""
    words = np.random.SeedSequence(int(seed)).generate_state(4)
    keys = ("data", "build", "order", "arrivals")
    return {k: int(w) & _SEED_MASK for k, w in zip(keys, words)}


def _centres(data: dict, d: int, rng: np.random.Generator) -> np.ndarray:
    return rng.standard_normal((data["clusters"], d)) * data["cluster_scale"]


def _assign(data: dict, size: int, rng: np.random.Generator) -> np.ndarray:
    weights, c = data["cluster_weights"], data["clusters"]
    if weights == "equal":
        return rng.integers(0, c, size)
    p = 1.0 / np.arange(1, c + 1) ** float(weights["zipf"])
    return rng.choice(c, size=size, p=p / p.sum())


def _encode(v: np.ndarray, data: dict) -> np.ndarray:
    grid = data.get("grid")
    if grid is not None:
        v = np.clip(np.round(v * grid["scale"]), grid["lo"], grid["hi"])
    return v.astype(np.float32)


def make_points(cfg: dict, seed: int) -> np.ndarray:
    """[n, d] float32 corpus of configuration ``cfg``."""
    data, n, d = cfg["data"], cfg["n"], cfg["d"]
    rng = np.random.default_rng(seed)
    centres = _centres(data, d, rng)
    assign = _assign(data, n, rng)
    return _encode(centres[assign] + rng.standard_normal((n, d)), data)


def make_queries(cfg: dict, seed: int, n_queries: int | None = None
                 ) -> np.ndarray:
    """[queries, d] float32 queries from the corpus's own mixture."""
    data, d = cfg["data"], cfg["d"]
    nq = cfg["queries"] if n_queries is None else n_queries
    centres = _centres(data, d, np.random.default_rng(seed))
    rng = np.random.default_rng(seed + 1)
    assign = _assign(data, nq, rng)
    return _encode(centres[assign] + rng.standard_normal((nq, d)), data)
