"""CPU tests of the on-chip benchmark's harness (``bench/``): its
declarations, traffic arithmetic, window rules, roofline counts, trace
reduction, reference and the refusal to run without a TPU."""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import checks, corpus, reference, roofline, spec, trace, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


# ------------------------------------------------------------ declarations --

def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


@pytest.mark.parametrize("cell", ["sift-build", "sift-serve-batch"])
def test_cell_found_by_name(bench, cell):
    c = spec.find_cell(bench, cell)
    cfg = spec.load_config(bench, c)
    tr = spec.load_traffic(c["traffic"])
    assert cfg["name"] == c["config"]
    assert callable(spec.load_runner(tr["runner"]).run)
    assert c["chips"] == 1
    assert spec.end_to_end_for(bench, cell), "every cell reports a metric"
    assert any(m["name"] == "setup_s"
               for m in spec.end_to_end_for(bench, cell))
    assert spec.per_layer_for(bench, cell)


def test_unknown_cell_is_refused(bench):
    with pytest.raises(KeyError):
        spec.find_cell(bench, "no-such-cell")


def test_unknown_runner_and_reader_are_refused():
    with pytest.raises(FileNotFoundError):
        spec.load_runner("no_such_runner")
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no.such.metric")


def test_names_units_and_entries(bench):
    names = [e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in bench[group]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        for key in c["reduced"]:
            assert NAME.match(key)
        with open(REPO / c["file"]) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for cell in m["workloads"]:
            assert cell in cells
            assert any(x["name"] == m["moves"]
                       for x in spec.end_to_end_for(bench, cell))
        assert m["moves"] in e2e


def test_every_reader_loads_and_finds_nothing_in_an_empty_run(bench):
    for m in bench["per_layer"]:
        assert spec.load_reader(m["name"])({}) is None


def test_build_fields_all_reach_the_program(bench):
    """Every build parameter the configuration writes out is one the
    program has, so none is silently left out."""
    sys.path.insert(0, str(REPO / "src"))
    from benchlib.cell import build_params

    for c in bench["configs"]:
        with open(REPO / c["file"]) as f:
            cfg = json.load(f)
        p = build_params(cfg, 5)
        for key, v in cfg["build"]["rbc"].items():
            got = getattr(p.rbc, key)
            assert (list(got) if isinstance(got, tuple) else got) == v, key
        for key, v in cfg["build"]["leaf"].items():
            assert getattr(p.leaf, key) == v, key
        for key, v in cfg["build"].items():
            if key not in ("rbc", "leaf"):
                assert getattr(p, key) == v, key
        assert p.seed == p.rbc.seed == 5


@pytest.mark.parametrize("group,change", [
    ("rbc", {"no_such_field": 1}),          # the program has no such field
    ("leaf", {"drop": "mst_sparsify"}),     # a program field left out
    ("build", {"no_such_field": 1}),
    ("build", {"drop": "hash_bits"}),
])
def test_build_params_refuse_a_mismatch(group, change):
    """A field the program lacks, or one the file leaves to the program's
    default, stops the run: a program edit cannot move the yardstick."""
    sys.path.insert(0, str(REPO / "src"))
    from benchlib.cell import build_params

    cfg = json.loads(json.dumps(spec.load_named_config("sift-128-l2")))
    fields = cfg["build"] if group == "build" else cfg["build"][group]
    if "drop" in change:
        del fields[change["drop"]]
    else:
        fields.update(change)
    with pytest.raises(ValueError, match="does not match the program"):
        build_params(cfg, 5)


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


# ------------------------------------------------------------ corpus ------

def test_corpus_is_seeded_and_uint8_valued():
    """The corpus comes from the seed; with a ``grid`` in its data section
    it takes integer values in [0, 255], as uint8 descriptors do."""
    base = spec.load_named_config("sift-128-l2")
    cfg = {**base, "n": 512, "queries": 64}
    a = corpus.make_points(cfg, 3)
    assert a.shape == (512, 128) and a.dtype == np.float32
    assert np.array_equal(a, corpus.make_points(cfg, 3))
    assert not np.array_equal(a, corpus.make_points(cfg, 4))
    assert corpus.make_queries(cfg, 3).shape == (64, 128)
    assert not np.array_equal(corpus.make_queries(cfg, 3),
                              corpus.make_queries(cfg, 4))
    grid = {**cfg, "data": {**base["data"], "grid": {"scale": 16, "lo": 0,
                                                     "hi": 255}}}
    u = corpus.make_points(grid, 3)
    assert np.array_equal(u, np.round(u)) and u.min() >= 0 and u.max() <= 255
    assert 0.3 < np.mean(u == 0) < 0.7
    big = corpus.streams(2**31 + 12345)
    assert all(0 <= v < 2**31 for v in big.values())
    assert corpus.streams(7) == corpus.streams(7)


@pytest.mark.parametrize("weights,lo,hi", [
    ("equal", 0.10, 0.15),
    ({"zipf": 1.5}, 0.50, 0.55),     # 1/(i+1)^1.5 over 8: the first ~52.7%
])
def test_corpus_cluster_weights_are_data(weights, lo, hi):
    """Skewed cluster sizes are a parameter of the one generator, so a
    deployment that needs them is a new data file."""
    data = {**spec.load_named_config("sift-128-l2")["data"], "clusters": 8,
            "cluster_weights": weights}
    rng = np.random.default_rng(3)
    share = np.bincount(corpus._assign(data, 20000, rng)).max() / 20000
    assert lo < share < hi


# ----------------------------------------------------------- traffic ------

class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-6)


class Result:
    def __init__(self, rid, ids, error=None):
        self.rid, self.ids, self.error = rid, ids, error


class FakeLoop:
    """A serving loop whose every step takes ``step_s`` of the fake clock
    and answers up to ``chunk`` queued requests."""

    def __init__(self, clock, step_s, chunk, max_queue=10**6):
        self.clock, self.step_s, self.chunk = clock, step_s, chunk
        self.max_queue = max_queue
        self.q, self.next = [], 0

    @property
    def queue_depth(self):
        return len(self.q)

    def submit(self, query):
        if len(self.q) >= self.max_queue:
            raise type("QueueFull", (RuntimeError,), {})()
        self.q.append(self.next)
        self.next += 1
        return self.next - 1

    def step(self):
        batch, self.q = self.q[:self.chunk], self.q[self.chunk:]
        self.clock.t += self.step_s
        return [Result(r, np.arange(10)) for r in batch]


def test_closed_loop_window_rate():
    clock = FakeClock()
    loop = FakeLoop(clock, step_s=0.25, chunk=4)
    win = spec.load_runner("closed_loop").run_passes(
        loop, np.zeros((10, 2)), np.arange(10), 1.9, clock=clock)
    # passes of 10 queries in steps of 4, 4, 2: the window ends with the
    # first step that finishes at or after 1.9 s (the 8th, at 2.0 s)
    assert len(win.steps) == 8
    assert win.seconds == pytest.approx(2.0)
    assert win.answered_in_window() == 28
    assert win.answered_in_window() / win.seconds == pytest.approx(14.0)
    # what was queued when the window closed is still answered (checked)
    assert sum(a is not None for a in win.ids) == 30


def test_closed_loop_counts_refusals_as_failed():
    clock = FakeClock()
    loop = FakeLoop(clock, step_s=1.0, chunk=4, max_queue=4)
    win = spec.load_runner("closed_loop").run_passes(
        loop, np.zeros((6, 2)), np.arange(6), 1.0, clock=clock)
    # one pass of 6: the queue takes 4, refuses 2; one step serves the 4
    assert win.attempted == 6 and win.failed == 2
    assert win.answered_in_window() == 4
    assert sorted(win.latency_ms)[-1] == traffic.NO_ANSWER_MS


class FakeIndex:
    timings = {"partition": 1.0}


@pytest.mark.parametrize("durations,seconds,builds,window", [
    ([10, 10, 10, 10], 25.0, 2, 20.0),    # a third would not fit
    ([10, 10, 10, 10], 30.0, 3, 30.0),    # exactly fits
    ([40, 40], 25.0, 1, 40.0),            # one build is always timed
    ([10, 16, 10], 40.0, 2, 26.0),        # the last build's time decides
])
def test_build_loop_window_rule(durations, seconds, builds, window):
    clock = FakeClock()
    it = iter(durations)

    def build():
        clock.t += next(it)
        return FakeIndex()

    win = spec.load_runner("build_loop").run_builds(build, seconds,
                                                    clock=clock)
    assert len(win.builds) == builds
    assert win.seconds == pytest.approx(window)
    assert win.build_s == pytest.approx(window / builds)


# ---------------------------------------------------------- roofline ------

def test_roofline_counts_at_cell_shapes():
    ops, nbytes = roofline.gather_work(1000, 128, "f32")
    assert ops == 2 * 128 * 1000 and nbytes == (4 * 128 + 4) * 1000
    ops, nbytes = roofline.gather_work(1000, 128, "int8")
    assert ops == 2 * 128 * 1000 and nbytes == (128 + 4) * 1000
    peak = spec.peaks("TPU v5 lite")
    t, bound = roofline.least_seconds(*roofline.gather_work(
        10**9, 128, "f32"), peak, "f32")
    assert bound == "memory" and t == pytest.approx(516e9 / 819e9)
    share = roofline.roofline_share(10**9, 128, "f32", 2 * t, peak)
    assert share == pytest.approx(50.0)
    assert roofline.roofline_share(10**9, 128, "f32", 0.0, peak) is None
    assert roofline.roofline_share(0, 128, "int8", 1.0, peak) is None


# ------------------------------------------------------------- trace ------

def test_union_and_gaps():
    busy = trace.union_ns([(5, 10), (0, 3), (8, 12), (20, 30)], 1, 25)
    assert busy == [(1, 3), (5, 12), (20, 25)]
    assert trace.gaps_ns(busy, 0, 30) == [(0, 1), (3, 5), (12, 20),
                                          (25, 30)]


def test_idle_gaps_go_to_the_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.serve_loop.step", 10, 60),
             ("bench.index.search", 20, 40), ("bench.serve_loop.step",
                                              70, 90)]
    gaps = [(12, 18), (30, 32), (45, 55), (62, 68), (75, 85)]
    got = dict((k, v * 1e9) for k, v in trace.attribute(gaps, spans))
    assert got == pytest.approx({"bench.serve_loop.step": 26.0,
                                 "bench.index.search": 2.0,
                                 trace.NO_SPAN: 6.0})


# ------------------------------------------------------------ reference ---

def test_exact_topk_matches_float64_brute_force():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((700, 16)).astype(np.float32)
    q = rng.standard_normal((37, 16)).astype(np.float32)
    got = reference.exact_topk(x, q, 10, block=16, margin=6)
    d = ((q[:, None, :].astype(np.float64) - x[None].astype(np.float64))
         ** 2).sum(-1)
    assert np.array_equal(got, np.argsort(d, axis=1, kind="stable")[:, :10])
    assert reference.recall(got, got, 10) == 1.0
    half = got.copy()
    half[:, 5:] = -1
    assert reference.recall(half, got, 10) == 0.5


def test_edge_lengths_and_reachability():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((50, 8)).astype(np.float32)
    g = np.full((50, 3), -1, np.int32)
    g[:-1, 0] = np.arange(1, 50)          # a path 0 -> 1 -> ... -> 49
    d = reference.edge_sq_dists(x, g, block=16)
    want = ((x[:-1] - x[1:]) ** 2).sum(-1)
    assert np.allclose(d[:-1, 0], want, rtol=1e-6)
    assert np.isinf(d[:, 1:]).all()
    assert reference.reachable(g, 0).all()
    assert reference.reachable(g, 10).sum() == 40


def test_order_gap_and_bad_answers():
    d = np.array([[1.0, 2.0, 3.0], [1.0, 1.5, 1.2]])
    assert checks.order_gap(d) == pytest.approx(0.3 / 1.2)
    assert checks.order_gap(d[:1]) == 0.0
    ids = np.array([[0, 1, 2], [0, 0, 1], [0, 1, -1], [0, 1, 9]])
    assert checks.bad_answer_rows(ids, 5, 3).tolist() == [False, True,
                                                          True, True]


def test_bad_edges_counted():
    g = np.array([[1, 2, -1], [0, 0, -1], [1, 7, -1]], np.int32)
    d = np.ones(g.shape, np.float32)
    d[2, 0] = np.nan
    # row 1 repeats 0; row 2 has an id out of range and an edge of no
    # length; padding does not count
    assert checks.bad_edge_count(g, d) == 3


# ------------------------------------------------------------ refusal -----

def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift-build",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_compiles_inside_the_window_are_counted():
    import jax
    import jax.numpy as jnp

    from benchlib.cell import CompileCounter

    counter = CompileCounter()
    f = jax.jit(lambda v: v * 3 + 1)
    f(jnp.ones(3))                       # outside: not counted
    with counter.window():
        f(jnp.ones(3))                   # cached: no compile
        assert counter.count == 0
        f(jnp.ones(5))                   # a new shape compiles
    assert counter.count >= 1
    n = counter.count
    f(jnp.ones(7))
    assert counter.count == n


def test_reduction_of_the_recorded_chip_trace():
    """``tests/data/serve_v5e.xplane.pb`` is eight ServeLoop steps traced
    on a TPU v5e (``record_trace.py``); ``serve_v5e.json`` is what the
    reduction read from it there.  Reading it again must give the same,
    and the numbers must hang together."""
    data = BENCH / "tests" / "data"
    want = json.loads((data / "serve_v5e.json").read_text())
    got = trace.reduce(str(data / "serve_v5e.xplane.pb"))
    assert got.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert got.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert got.top_ops == want["top_ops"]
    assert got.idle_by_span == want["idle_by_span"]
    assert got.kernel_seconds("gather_distance_hbm") == pytest.approx(
        want["kernel_seconds"]["gather_distance_hbm"], rel=1e-12)
    # the device ran something, the kernel is among it, and the idle
    # time put down to spans is the window less the busy time
    assert 0 < got.busy_s < got.window_s
    assert 0 < got.kernel_seconds("gather_distance_hbm") < got.busy_s
    idle = sum(v for _, v in got.idle_by_span)
    assert idle <= got.window_s - got.busy_s + 1e-9
    assert any(k == "bench.index.search" for k, _ in got.idle_by_span)
    # busy time is the union of the op intervals: never more than their sum
    assert got.busy_s <= sum(o.dur_ns for o in got.ops) * 1e-9


def _run_module():
    import importlib.util

    spec_ = importlib.util.spec_from_file_location("bench_run",
                                                   BENCH / "run.py")
    run = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(run)
    return run


@pytest.mark.parametrize("given", [None, "elsewhere"])
def test_cache_dir_honours_the_environment(monkeypatch, tmp_path, given):
    run = _run_module()
    if given is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert run.cache_dir() == REPO / ".jax_cache"
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / given))
        assert run.cache_dir() == tmp_path / given


def test_window_holds_the_collector_off():
    import gc

    from benchlib import cell

    class Counter:
        @contextlib.contextmanager
        def window(self):
            yield

    args = cell.RunArgs(cfg={}, traffic={}, seeds={}, seconds=1.0,
                        trace=False, chips=1, compiles=Counter(),
                        t_start=0.0)
    assert gc.isenabled()
    with cell.window(args):
        assert not gc.isenabled()
    assert gc.isenabled() and gc.get_freeze_count() == 0


def test_cache_entries_without_atime_are_dropped(tmp_path):
    run = _run_module()
    (tmp_path / "a-cache").write_bytes(b"x")
    (tmp_path / "a-atime").write_bytes(b"t")
    (tmp_path / "b-cache").write_bytes(b"y")
    run.drop_entries_without_atime(tmp_path)
    assert sorted(p.name for p in tmp_path.iterdir()) == ["a-atime",
                                                          "a-cache"]
