"""The on-chip benchmark of PiPNN: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs``) and
a traffic mix (``bench/traffic``).  A run builds the corpus and the index
from ``--seed``, warms every shape the window uses (set-up), measures for
``--seconds``, checks what the window returned against the brute-force
reference, and prints one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device", ["breakdown"],
     "checks"}

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics (``bench/metrics/<name>.py``).  The numbers
compared for ``correct`` are printed beside their limits as the last
lines of stderr and under ``checks``.

The run exits non-zero and prints no result when JAX finds no TPU, or
fewer chips than the cell asks for, or when the checkout holds no
program.  JAX's compilation cache is kept where
``JAX_COMPILATION_CACHE_DIR`` says, and in ``<checkout>/.jax_cache``
where it is not set.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def fail(msg: str, code: int = 2) -> None:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def cache_dir() -> pathlib.Path:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else a fixed directory in the checkout."""
    return pathlib.Path(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                        or CHECKOUT / ".jax_cache")


def use_checkout() -> None:
    """Put the checkout's program and the benchmark's library on the path,
    and JAX's compilation cache where ``cache_dir`` says."""
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        fail(f"no program at {src}/repro: run from a checkout of the "
             "repository")
    for p in (str(BENCH), str(src)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir())


def drop_entries_without_atime(cache: pathlib.Path) -> None:
    """JAX's cache keeps ``<key>-cache`` beside ``<key>-atime`` and fails
    every write while one entry lacks its ``-atime`` (as entries written
    with eviction off do).  Drop such entries so the cache fills."""
    for entry in cache.glob("*-cache"):
        if not entry.with_name(entry.name[:-len("-cache")] + "-atime").exists():
            entry.unlink(missing_ok=True)


def accelerator(chips: int) -> None:
    """Exit, printing no result, unless JAX finds ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r}); nothing "
             "was run", 3)
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX found {len(devs)}", 3)
    cache = cache_dir()
    cache.mkdir(parents=True, exist_ok=True)   # JAX writes entries only
    drop_entries_without_atime(cache)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_checkout()
    from benchlib import spec

    try:
        cell = spec.find_cell(spec.load_benchmark(), args.workload)
    except (OSError, KeyError) as e:
        fail(str(e))
    accelerator(cell["chips"])

    from benchlib.cell import run_cell

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start=_T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
