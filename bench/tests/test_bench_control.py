"""The low-precision control comes out not correct where the program comes
out correct, at a size a test run can hold (``bench/readings.py``, the
script whose chip runs set the limits)."""
from __future__ import annotations

import json

import pytest

import _bench_small  # noqa: F401 — puts bench/ and src/ on the path


def _readings(tmp_path, config):
    import readings
    from benchlib import spec

    out = tmp_path / "r.jsonl"
    readings.main(["--config", config, "--seeds", "1", "--control-seeds",
                   "101", "--n", "2048", "--queries", "256",
                   "--out", str(out)])
    limits = spec.load_named_config(config)["guarantees"]
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    return recs, limits


def _fails(numbers, limits):
    bad = []
    for name, v in numbers.items():
        g = limits[name]
        if ("max" in g and v > g["max"]) or ("min" in g and v < g["min"]):
            bad.append(name)
    return bad


@pytest.mark.parametrize("config,control_fails", [
    ("sift-128-l2", {"build": "edge_dist_gap", "serve": "order_gap"}),
])
def test_control_fails_where_the_program_passes(tmp_path, config,
                                                control_fails):
    recs, limits = _readings(tmp_path, config)
    program = [r for r in recs if not r["control"]]
    control = [r for r in recs if r["control"]]
    assert program and control
    for r in program:
        for part in control_fails:
            assert _fails(r[part], limits) == [], (part, r[part])
    for r in control:
        for part, number in control_fails.items():
            assert number in _fails(r[part], limits), (part, r[part])
