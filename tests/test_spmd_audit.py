"""SPMD sharding auditor tests — every PIPS rule gets a positive fixture
(a deliberately broken program/contract the rule MUST flag) and a
negative (the real registry must stay clean).

Synthetic specs reuse the auditor's own registry types
(``SPMDSpec``/``SPMDProgram``), so the positives exercise the exact code
path the lint pass runs — not a parallel re-implementation.  Multi-mesh
positives are gated on the forced-device host (the CI job runs this file
under ``--xla_force_host_platform_device_count=8``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import spmd_audit as sa
from repro.analysis.spmd_audit import SPMDProgram, SPMDSpec

NDEV = len(jax.devices())

multidevice = pytest.mark.skipif(
    NDEV < 4, reason="needs >= 4 devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _spec(name, build, *, collectives=frozenset(), replicated_ok=frozenset()):
    return SPMDSpec(name=name, path=f"tests/{name}.py", symbol=name,
                    build=build, collectives=frozenset(collectives),
                    replicated_ok=frozenset(replicated_ok))


def _mesh(s, axis="ax"):
    return Mesh(np.array(jax.devices()[:s]), (axis,))


# ------------------------------------------------------------- PIPS001 ---

def _psum_program(s):
    """A 'per-shard' body that sneaks in a psum — works even on a
    1-device mesh, so the positive runs everywhere."""
    mesh = _mesh(s)

    def body(x):
        return jax.lax.psum(x, "ax")

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("ax"),),
                               out_specs=P("ax"), check_vma=False))
    return SPMDProgram(fn=fn, args=(jax.ShapeDtypeStruct((s, 4), jnp.float32),),
                       arg_names=("x",), sharded=frozenset({"x"}))


def test_pips001_flags_undeclared_collective():
    spec = _spec("sneaky_psum", _psum_program)
    findings = sa.audit_collectives(specs=(spec,))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "PIPS001"
    assert "psum" in f.message and "'ax'" in f.message


def test_pips001_quiet_when_contract_declares_it():
    spec = _spec("declared_psum", _psum_program,
                 collectives={("psum", "ax")})
    assert sa.audit_collectives(specs=(spec,)) == []


def test_collectives_in_sees_through_nesting():
    mesh = _mesh(1)

    def body(x):
        # collective buried under scan -> cond nesting
        def step(c, _):
            c = jax.lax.cond(c.sum() > 0,
                             lambda v: jax.lax.psum(v, "ax"),
                             lambda v: v, c)
            return c, None
        c, _ = jax.lax.scan(step, x, None, length=2)
        return c

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("ax"),),
                               out_specs=P("ax"), check_vma=False))
    got = sa.collectives_in(fn, (jax.ShapeDtypeStruct((1, 4), jnp.float32),))
    assert ("psum", "ax") in got


# ------------------------------------------------------------- PIPS002 ---

def _mislabeled_program(s):
    """in_specs says replicated (P()) for an operand the registry claims
    is sharded — the exact drift PIPS002 exists to catch."""
    mesh = _mesh(s)

    def body(x, y):
        return x + y.sum()

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                               in_specs=(P("ax"), P()),
                               out_specs=P("ax"), check_vma=False))
    args = (jax.ShapeDtypeStruct((s * 4, 8), jnp.float32),
            jax.ShapeDtypeStruct((4, 8), jnp.float32))
    return SPMDProgram(fn=fn, args=args, arg_names=("x", "y"),
                       sharded=frozenset({"x", "y"}))


@multidevice
def test_pips002_flags_declared_sharded_but_replicated():
    spec = _spec("mislabeled", _mislabeled_program)
    findings = sa.audit_replication(specs=(spec,))
    assert [f.rule for f in findings] == ["PIPS002"]
    assert "'y'" in findings[0].message


@multidevice
def test_pips002_flags_unwhitelisted_replication():
    def build(s):
        prog = _mislabeled_program(s)
        # correctly declared replicated, but NOT whitelisted
        return SPMDProgram(fn=prog.fn, args=prog.args,
                           arg_names=prog.arg_names,
                           sharded=frozenset({"x"}))

    assert [f.rule for f in sa.audit_replication(specs=(_spec("norep", build),))
            ] == ["PIPS002"]
    # whitelisting it is the fix
    ok = _spec("norep_ok", build, replicated_ok={"y"})
    assert sa.audit_replication(specs=(ok,)) == []


# ------------------------------------------------------------- PIPS003 ---

def test_pips003_envelope_fires_under_tiny_budget():
    findings = sa.audit_footprint(budget=1024)
    assert findings, "a 1KiB HBM budget must trip the envelope pricing"
    assert all(f.rule == "PIPS003" for f in findings)


def test_pips003_quiet_at_default_budget():
    assert sa.audit_footprint() == []


def test_price_shard_packing_monotone_in_halo():
    lo = sa.price_shard_packing(1 << 20, 64, 32, 16, halo_fraction=0.0)
    hi = sa.price_shard_packing(1 << 20, 64, 32, 16, halo_fraction=0.5)
    assert hi["total"] > lo["total"]
    assert hi["rows"] > lo["rows"]
    # int8 points shrink the footprint vs f32
    q = sa.price_shard_packing(1 << 20, 64, 32, 16, int8=True)
    f = sa.price_shard_packing(1 << 20, 64, 32, 16, int8=False)
    assert q["points"] < f["points"]


# ------------------------------------------------------------- PIPS004 ---

def test_pips004_flags_implicit_transfer():
    # a serving path that feeds raw numpy straight into a jit dispatch:
    # an unrouted h2d the guard must catch
    def bad_call(sv, q):
        sv.search(q, k=4, beam=8)
        jax.jit(jnp.sum)(np.asarray(q)).block_until_ready()

    findings = sa.audit_transfers(search_call=bad_call)
    assert [f.rule for f in findings] == ["PIPS004"]
    assert "implicit host transfer" in findings[0].message


def test_pips004_flags_over_budget():
    findings = sa.audit_transfers(budget={"h2d": 0, "d2h": 0})
    assert [f.rule for f in findings] == ["PIPS004"]
    assert "more than" in findings[0].message


def test_pips004_quiet_at_declared_budget():
    assert sa.audit_transfers() == []


# ------------------------------------------------------------- PIPS005 ---

def _unrolled_program(s):
    """Shard count leaked into Python control flow: the traced program
    grows one sin() per shard."""
    def fn(x):
        for _ in range(s):
            x = jnp.sin(x)
        return x

    return SPMDProgram(fn=fn, args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
                       arg_names=("x",), sharded=frozenset())


def _scanned_program(s):
    """The same computation folded into lax control flow: structurally
    identical for every s."""
    def fn(x):
        def step(c, _):
            return jnp.sin(c), None
        c, _ = jax.lax.scan(step, x, None, length=s)
        return c

    return SPMDProgram(fn=fn, args=(jax.ShapeDtypeStruct((4,), jnp.float32),),
                       arg_names=("x",), sharded=frozenset())


def test_fingerprint_distinguishes_unrolled_from_scanned():
    u1, u2 = (_unrolled_program(s) for s in (1, 2))
    assert (sa.structural_fingerprint(u1.fn, u1.args)
            != sa.structural_fingerprint(u2.fn, u2.args))
    s1, s2 = (_scanned_program(s) for s in (1, 2))
    assert (sa.structural_fingerprint(s1.fn, s1.args)
            == sa.structural_fingerprint(s2.fn, s2.args))


@multidevice
def test_pips005_flags_unrolled_program():
    findings = sa.audit_mesh_stability(specs=(_spec("unrolled",
                                                    _unrolled_program),))
    assert [f.rule for f in findings] == ["PIPS005"]


@multidevice
def test_pips005_quiet_for_scanned_program():
    assert sa.audit_mesh_stability(specs=(_spec("scanned",
                                                _scanned_program),)) == []


# ----------------------------------------------------------- acceptance ---

def test_registry_collectives_clean():
    """PIPS001 over the real registry: the per-shard search body is
    proven collective-free, the build supersteps match their declared
    contracts — at every shard count this host can mesh."""
    assert sa.audit_collectives() == []


@multidevice
def test_registry_mesh_stable():
    assert sa.audit_mesh_stability() == []


@multidevice
def test_registry_replication_clean():
    assert sa.audit_replication() == []


def test_every_pips_rule_documented():
    from repro.analysis.lint import RULES

    for rule in ("PIPS001", "PIPS002", "PIPS003", "PIPS004", "PIPS005"):
        assert rule in RULES
