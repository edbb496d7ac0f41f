"""Per-rule fixtures for the static contract checker (repro.analysis).

Every rule gets a minimal positive fixture (the checker must fire) and a
negative twin (it must stay quiet) — plus the acceptance-level assertions:
the full linter is clean on this repository with the EMPTY checked-in
baseline, and the recompilation audit proves the serving engine compiles
a bounded number of jit variants across a session sweep.
"""
from __future__ import annotations

import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import ast_lint, contracts, jaxpr_audit, lint
from repro.analysis.contracts import (KernelSpec, PallasCallRecord,
                                      capture_pallas_calls, check_record)

REPO = pathlib.Path(__file__).resolve().parents[1]


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# AST lint — PIPA001-PIPA004
# ---------------------------------------------------------------------------

def test_ast_traced_branch_fires():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n")
    fs = ast_lint.lint_source(src, "fx.py")
    assert rules(fs) == ["PIPA001"] and fs[0].line == 4


def test_ast_traced_branch_propagates_through_assignment():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    y = x * 2\n"
        "    while y.sum() > 0:\n"
        "        y = y - 1\n"
        "    return y\n")
    assert rules(ast_lint.lint_source(src, "fx.py")) == ["PIPA001"]


def test_ast_static_and_metadata_branches_are_quiet():
    src = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnames=('flag',))\n"
        "def f(x, flag, opt=None):\n"
        "    if flag:\n"
        "        return x\n"
        "    if opt is None:\n"
        "        opt = 0\n"
        "    if x.shape[0] > 4 and len(x.shape) == 2:\n"
        "        return x[:4]\n"
        "    return x + opt\n")
    assert ast_lint.lint_source(src, "fx.py") == []


def test_ast_host_sync_fires():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    a = float(x)\n"
        "    b = x.sum().item()\n"
        "    c = np.asarray(x)\n"
        "    return a + b + c\n")
    fs = ast_lint.lint_source(src, "fx.py")
    assert [f.rule for f in fs] == ["PIPA002"] * 3


def test_ast_host_sync_on_static_shape_is_quiet():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    rows = int(x.shape[0])\n"
        "    total = np.prod(x.shape)\n"
        "    return x.reshape(rows, total // rows)\n")
    assert ast_lint.lint_source(src, "fx.py") == []


def test_ast_mutable_default_fires():
    src = "def f(a, out=[], cfg={}):\n    return out\n"
    fs = ast_lint.lint_source(src, "fx.py")
    assert [f.rule for f in fs] == ["PIPA003"] * 2


def test_ast_missing_static_shape_param_fires():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x, k):\n"
        "    return x[:k]\n")
    fs = ast_lint.lint_source(src, "fx.py")
    assert rules(fs) == ["PIPA004"] and "'k'" in fs[0].message


def test_ast_call_form_jit_detected():
    src = (
        "import jax\n"
        "def factory():\n"
        "    def step(state, beam):\n"
        "        return state\n"
        "    return jax.jit(step, static_argnames=('beam',))\n")
    assert ast_lint.lint_source(src, "fx.py") == []
    # same, but beam left traced -> flagged
    src_bad = src.replace(", static_argnames=('beam',)", "")
    assert rules(ast_lint.lint_source(src_bad, "fx.py")) == ["PIPA004"]


def test_ast_package_scan_of_repo_is_clean():
    assert ast_lint.lint_package(REPO / "src" / "repro", root=REPO) == []


# ---------------------------------------------------------------------------
# kernel contracts — PIPK001-PIPK005
# ---------------------------------------------------------------------------

def _spec(name="fixture"):
    return KernelSpec(name, "repro.kernels.gather_distance",
                      "repro.kernels.ref:gather_distance_ref", lambda: [])


class _Block:
    """Stand-in BlockSpec for direct check_record tests."""

    def __init__(self, block_shape, index_map=None):
        self.block_shape = block_shape
        self.index_map = index_map or (lambda *g: tuple(0 for _ in block_shape))


def _record(specs_avals, grid, out=(), scratch=()):
    return PallasCallRecord(
        grid=grid,
        out_shape=tuple(jax.ShapeDtypeStruct(s, d) for _, (s, d) in out),
        in_specs=[b for b, _ in specs_avals],
        out_specs=tuple(b for b, _ in out),
        scratch_shapes=tuple(scratch),
        arg_avals=tuple((s, np.dtype(d)) for _, (s, d) in specs_avals))


def test_contract_vmem_overflow_fires():
    # one grid-invariant f32 block of 24 MiB > the 16 MiB capacity
    rec = _record(
        [(_Block((24 * 1024, 256)), ((24 * 1024, 256), np.float32))],
        grid=(1,))
    assert rules(check_record(rec, _spec(), "case")) == ["PIPK001"]


def test_contract_vmem_double_buffers_grid_varying_blocks():
    # 5 MiB block, grid-varying -> 10 MiB working set: fits 16, not 8
    block = _Block((10 * 1024, 128), lambda r: (r, 0))
    rec = _record([(block, ((20 * 1024, 128), np.float32))], grid=(2,))
    assert check_record(rec, _spec(), "c") == []
    assert rules(check_record(rec, _spec(), "c", capacity=8 << 20)) == \
        ["PIPK001"]


def test_contract_tile_misalignment_fires():
    # (5, 128) f32: sublane 5 is not 1, not %8, not the extent
    rec = _record([(_Block((5, 128)), ((40, 128), np.float32))], grid=(1,))
    assert "PIPK002" in rules(check_record(rec, _spec(), "c"))
    # (16, 128) int8 against a larger extent: 16 is not %32
    rec8 = _record([(_Block((16, 128)), ((64, 128), np.int8))], grid=(1,))
    assert "PIPK002" in rules(check_record(rec8, _spec(), "c"))
    # full-extent trailing dims are exempt even when unaligned
    ok = _record([(_Block((8, 100)), ((8, 100), np.float32))], grid=(1,))
    assert check_record(ok, _spec(), "c") == []


def test_contract_grid_undercover_fires():
    # 2 grid steps x 8 rows cover 16 of 32 rows
    block = _Block((8, 128), lambda r: (r, 0))
    rec = _record([(block, ((32, 128), np.float32))], grid=(2,))
    assert rules(check_record(rec, _spec(), "c")) == ["PIPK003"]
    full = _record([(block, ((32, 128), np.float32))], grid=(4,))
    assert check_record(full, _spec(), "c") == []


def test_contract_missing_oracle_fires():
    import dataclasses
    bad = dataclasses.replace(contracts.REGISTRY[0],
                              oracle="repro.kernels.ref:does_not_exist",
                              cases=lambda: [])
    assert rules(contracts.check_kernel(bad)) == ["PIPK004"]


def test_contract_unregistered_site_census_fires(tmp_path):
    pkg = tmp_path / "src" / "repro" / "kernels"
    pkg.mkdir(parents=True)
    (pkg / "rogue.py").write_text(
        "from jax.experimental import pallas as pl\n"
        "def f(x):\n"
        "    return pl.pallas_call(lambda i, o: None, out_shape=None)(x)\n")
    fs = contracts.check_site_census(tmp_path)
    assert rules(fs) == ["PIPK005"]
    assert fs[0].path == "src/repro/kernels/rogue.py" and fs[0].line == 3


def test_contract_capture_sees_real_blockspecs():
    from repro.kernels.gather_distance import gather_distance

    sds = jax.ShapeDtypeStruct
    recs = capture_pallas_calls(
        gather_distance,
        sds((100, 16), jnp.float32), sds((100,), jnp.float32),
        sds((7, 16), jnp.float32), sds((7, 40), jnp.int32),
        metric="l2")
    assert len(recs) == 1
    rec = recs[0]
    # wrapper pads Q 7->8 (tq), d 16->128 (lane), C 40->128 (lane)
    assert rec.grid == (1,)
    assert tuple(rec.in_specs[0].block_shape) == (8, 128)
    assert rec.arg_avals[1][0] == (8, 128)      # nbr_ids, padded
    # and the captured launch passes every contract check
    assert check_record(rec, _spec("gather_distance"), "probe") == []


def test_contract_registry_covers_every_pallas_site():
    assert contracts.check_site_census(REPO) == []


def test_contract_full_registry_is_clean():
    assert contracts.check_kernel_contracts(root=REPO) == []


def test_contract_admitted_sweep_would_catch_unpadded_pricing():
    """The PIPK001 sweep guards the fits_vmem fix: with the old unpadded
    ``size * itemsize`` pricing, a narrow-d shard is admitted whose
    lane-padded block alone exceeds VMEM capacity."""
    from repro.kernels.tiling import padded_bytes

    d, budget = 8, contracts.VMEM_CAPACITY  # 16 MiB "budget" as the old bound
    n = budget // (d * 4)                   # admitted by unpadded pricing
    assert padded_bytes((n, d), np.float32) > contracts.VMEM_CAPACITY
    rec = _record([(_Block((n, 128)), ((n, 128), np.float32))], grid=(1,))
    assert rules(check_record(rec, _spec(), "c")) == ["PIPK001"]


# ---------------------------------------------------------------------------
# jaxpr audit — PIPJ001-PIPJ004
# ---------------------------------------------------------------------------

def test_jaxpr_host_callback_fires():
    def f(x):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1

    fs = jaxpr_audit.trace_and_audit(
        f, (jax.ShapeDtypeStruct((4,), jnp.float32),), "fx.py", "f")
    assert rules(fs) == ["PIPJ001"]


def test_jaxpr_debug_callback_fires_inside_scan():
    def f(x):
        def body(c, v):
            jax.debug.callback(lambda _: None, v)
            return c + v, v
        out, _ = jax.lax.scan(body, x[0], x)
        return out

    fs = jaxpr_audit.trace_and_audit(
        f, (jax.ShapeDtypeStruct((4,), jnp.float32),), "fx.py", "f")
    assert "PIPJ001" in rules(fs)


def test_jaxpr_f64_fires_only_under_x64():
    def f(x):
        return x * np.float64(2.0)

    args = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    assert jaxpr_audit.trace_and_audit(f, args, "fx.py", "f") == []
    with jax.enable_x64(True):
        def g(x):
            return x.astype(jnp.float64) * 2.0
        fs = jaxpr_audit.trace_and_audit(g, args, "fx.py", "g")
    assert rules(fs) == ["PIPJ002"]


def test_jaxpr_donation_dropped_fires():
    # no output matches the donated input's shape -> XLA drops the alias
    dropped = jax.jit(lambda x: x[:1] * 2.0, donate_argnums=(0,))
    args = (jax.ShapeDtypeStruct((64, 64), jnp.float32),)
    fs = jaxpr_audit.check_donation(dropped, args, 1, "fx.py", "dropped")
    assert rules(fs) == ["PIPJ003"]
    honored = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    assert jaxpr_audit.check_donation(honored, args, 1, "fx.py", "ok") == []


def test_jaxpr_hot_paths_are_clean():
    assert jaxpr_audit.audit_hot_paths() == []


def test_jaxpr_recompilation_bound_holds():
    """Acceptance: the serving engine compiles at most one variant per
    (dtype, beam, expansions) across a session sweeping batch sizes."""
    assert jaxpr_audit.audit_recompilation() == []


def test_jaxpr_recompilation_audit_has_teeth():
    """Without query_chunk padding, batch size leaks into the dispatch
    shape and the audit must flag the cache blowup."""
    fs = jaxpr_audit.audit_recompilation(query_chunk=None)
    assert rules(fs) == ["PIPJ004"]


# ---------------------------------------------------------------------------
# baseline + CLI
# ---------------------------------------------------------------------------

def test_finding_key_is_line_free():
    f = lint.Finding("PIPK001", "src/a.py", 42, "kern", "msg")
    assert f.key == "PIPK001 src/a.py:kern"
    assert "42" in f.render() and "PIPK001" in f.render()


def test_baseline_load_ignores_comments(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text("# comment\n\nPIPA003 src/x.py:f\n  PIPK001 src/y.py:g\n")
    assert lint.load_baseline(p) == {"PIPA003 src/x.py:f",
                                     "PIPK001 src/y.py:g"}
    assert lint.load_baseline(tmp_path / "missing.txt") == set()


def test_checked_in_baseline_is_empty():
    assert lint.load_baseline(lint.default_baseline_path()) == set()


def test_cli_list_rules_and_ast_pass():
    env = {"PYTHONPATH": str(REPO / "src")}
    import os
    env = {**os.environ, **env}
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--list-rules"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert out.returncode == 0 and "PIPK001" in out.stdout
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--pass", "ast"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout and "RuntimeWarning" not in out.stderr
