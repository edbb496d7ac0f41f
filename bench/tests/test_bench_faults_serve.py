"""The serving cells' check sees each fault serving can have: a run with
the timed path broken underneath does not come out correct."""
from __future__ import annotations

import pytest

from _bench_small import run_small, small_checkout

CELLS = ["sift-serve-batch"]


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return small_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_serving_is_correct(small, cell):
    r = run_small(small, cell)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_serving_fault_is_caught(small, monkeypatch, cell, fault):
    import numpy as np

    from repro.core.serving import ServingIndex

    search = ServingIndex.search

    def broken(self, queries, **kw):
        ids, stats = search(self, queries, **kw)
        ids = np.array(ids)
        if fault == "state_unchanged":        # the initial beam, unmoved
            ids[:] = -1
            ids[:, 0] = self.start
        elif fault == "half_batch":           # half served for all
            half = (len(ids) + 1) // 2
            ids[half:] = ids[:len(ids) - half]
        else:                                 # one id of one answer
            ids[0, 0] = (ids[0, 0] + self.n // 2) % self.n
        return ids, stats

    monkeypatch.setattr(ServingIndex, "search", broken)
    try:
        r = run_small(small, cell)
    except Exception:  # noqa: BLE001 — a crash is a failed run too
        return
    assert not r["correct"], r["checks"]
