"""The build cell's check sees each fault the build can have: a run with
the timed path broken underneath does not come out correct."""
from __future__ import annotations

import pytest

from _bench_small import run_small, small_checkout


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return small_checkout(tmp_path_factory.mktemp("checkout"))


def _broken(result_or_error) -> bool:
    return isinstance(result_or_error, Exception) or \
        not result_or_error["correct"]


def _run(small, cell):
    try:
        return run_small(small, cell)
    except Exception as e:  # noqa: BLE001 — a crash is a failed run too
        return e


def test_sound_build_is_correct(small):
    r = run_small(small, "sift-build")
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_build_fault_is_caught(small, monkeypatch, fault):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pipnn

    make_step = pipnn._make_stream_step

    def broken_make_step(*a, **kw):
        step = make_step(*a, **kw)

        def unchanged(res_ids, res_hashes, res_dists, xj, sketches, ids):
            return res_ids, res_hashes, res_dists, jnp.int32(0)

        def half(res_ids, res_hashes, res_dists, xj, sketches, ids):
            # the step's batch is the [leaves, c_max] block of point ids:
            # leave out the second half of every leaf's members
            cut = ids.shape[1] // 2
            return step(res_ids, res_hashes, res_dists, xj, sketches,
                        ids.at[:, cut:].set(-1))

        return unchanged if fault == "state_unchanged" else half

    if fault == "answer_altered":
        prune = pipnn.final_prune

        def altered(*a, **kw):
            graph, dists = prune(*a, **kw)
            graph = np.array(graph)
            graph[0, 0] = (graph[0, 0] + 1) % graph.shape[0]
            return graph, dists

        monkeypatch.setattr(pipnn, "final_prune", altered)
    else:
        monkeypatch.setattr(pipnn, "_make_stream_step", broken_make_step)
    assert _broken(_run(small, "sift-build"))
