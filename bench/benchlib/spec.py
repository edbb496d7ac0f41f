"""The benchmark's declarations: ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the one ``configs[].file`` gives, the
mix is ``bench/traffic/<name>.json``, the mix's ``runner`` is run by
``bench/runners/<runner>.py``, and each per-layer metric is read by
``bench/metrics/<name>.py``.  All are found by name, so a new cell, mix,
runner or metric is a new file and no edit here.

Every function takes the checkout it reads (``root``, by default the
one this file lies in), so that tests can run cells from a copy.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Any, Callable

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def _bench_dir(root: pathlib.Path | None) -> pathlib.Path:
    return pathlib.Path(root or CHECKOUT) / "bench"


def load_benchmark(root: pathlib.Path | None = None) -> dict:
    with open(pathlib.Path(root or CHECKOUT) / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def load_config(bench: dict, cell: dict,
                root: pathlib.Path | None = None) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == cell["config"]:
            with open(pathlib.Path(root or CHECKOUT) / cfg["file"]) as f:
                return json.load(f)
    raise KeyError(f"workload {cell['name']!r} names configuration "
                   f"{cell['config']!r}, which BENCHMARK.json does not list")


def load_named_config(name: str, root: pathlib.Path | None = None) -> dict:
    """``bench/configs/<name>.json``."""
    with open(_bench_dir(root) / "configs" / f"{name}.json") as f:
        return json.load(f)


def load_traffic(name: str, root: pathlib.Path | None = None) -> dict:
    with open(_bench_dir(root) / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end_for(bench: dict, cell_name: str) -> list[dict]:
    """The end-to-end metrics a ``--trace 0`` run of the cell reports."""
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer_for(bench: dict, cell_name: str) -> list[dict]:
    """The per-layer metrics a ``--trace 1`` run of the cell reports."""
    return [m for m in bench["per_layer"] if _applies(m, cell_name)]


def _load_module(path: pathlib.Path, kind: str, name: str):
    """The module in ``path``.  File names may hold dots, so it is loaded
    by path, not imported by name."""
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric_name: str, root: pathlib.Path | None = None
                ) -> Callable[[dict], Any]:
    """``read(ctx)`` of ``bench/metrics/<metric_name>.py``."""
    return _load_module(_bench_dir(root) / "metrics" / f"{metric_name}.py",
                        "metric", metric_name).read


def load_runner(name: str, root: pathlib.Path | None = None):
    """The module ``bench/runners/<name>.py``; its ``run(args)`` drives
    the system through one run of a cell (``benchlib.cell.RunArgs``)."""
    return _load_module(_bench_dir(root) / "runners" / f"{name}.py",
                        "runner", name)


def peaks(device_kind: str, root: pathlib.Path | None = None) -> dict:
    """The published peaks of ``device_kind`` from ``peaks.json``.  A
    device missing from the table is an error, never a default."""
    with open(_bench_dir(root) / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]
