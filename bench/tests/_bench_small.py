"""Small-size settings shared by the benchmark's CPU tests: a copy of the
checkout's declarations with the configuration and the mixes cut to a
size a test run can hold."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = {"n": 1024, "queries": 128}
TRAFFIC = {"build_loop": {"query_chunk": 128},
           "batch": {"query_chunk": 64}}


def _edit(path: pathlib.Path, changes: dict) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data, indent=1))


def small_checkout(root: pathlib.Path) -> pathlib.Path:
    """``BENCHMARK.json`` and ``bench/`` copied into ``root``, with every
    configuration cut to ``SMALL`` and every mix's batch to ``TRAFFIC``."""
    shutil.copy(REPO / "BENCHMARK.json", root)
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    for cfg in (root / "bench" / "configs").glob("*.json"):
        _edit(cfg, SMALL)
    for name, changes in TRAFFIC.items():
        _edit(root / "bench" / "traffic" / f"{name}.json", changes)
    return root


def run_small(root: pathlib.Path, cell: str, seed: int = 2**31 + 99,
              seconds: float = 0.5):
    """One run of ``cell`` in the small checkout, past the look for a
    chip."""
    from benchlib.cell import run_cell

    return run_cell(cell, seed, seconds, False, root=root)
