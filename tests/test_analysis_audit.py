"""Layer-2 fixtures: the jaxpr auditor must flag a deliberately broken
toy closure (baked bulk constant, float64 leak, dropped donation,
leftover debug callback) and pass a clean one — plus one real matrix
cell audited end-to-end through the trainer capture hooks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_audit import audit_closure, iter_eqns
from repro.analysis.registry import CellSpec, run_cell


def rules_of(report):
    return sorted(f.rule for f in report.findings)


# ------------------------------------------------------- toy closures --
def test_clean_closure_passes():
    fn = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    rep = audit_closure("clean", fn, (jnp.ones((8,)),))
    assert rep.ok and rep.n_eqns >= 2 and rep.const_bytes == 0


def test_baked_constant_flagged():
    big = jnp.ones((100_000,))                 # 400 KB closure const
    fn = jax.jit(lambda x: x + big.sum())
    rep = audit_closure("baked", fn, (jnp.ones(()),),
                        const_budget=256 * 1024)
    assert "baked-constant" in rules_of(rep)
    assert rep.const_bytes >= 400_000


def test_baked_constant_within_budget_ok():
    big = jnp.ones((100_000,))
    fn = jax.jit(lambda x: x + big.sum())
    rep = audit_closure("dense", fn, (jnp.ones(()),),
                        const_budget=1 << 20)
    assert rep.ok


def test_float64_flagged():
    with jax.enable_x64():
        fn = jax.jit(lambda x: jnp.asarray(x, jnp.float64) * 2.0)
        rep = audit_closure("wide", fn, (jnp.ones((4,), jnp.float32),))
    assert "float64-op" in rules_of(rep)


def test_dropped_donation_flagged():
    fn = jax.jit(lambda s, x: s + x)           # no donate_argnums
    rep = audit_closure("chunk", fn,
                        (jnp.ones((8,)), jnp.ones((8,))),
                        expect_donation=True)
    assert rules_of(rep) == ["donation-mismatch"]
    assert rep.donated is False


def test_unexpected_donation_flagged():
    fn = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
    rep = audit_closure("chunk", fn,
                        (jnp.ones((8,)), jnp.ones((8,))),
                        expect_donation=False)
    assert rules_of(rep) == ["donation-mismatch"]
    assert rep.donated is True


def test_donation_match_passes():
    fn = jax.jit(lambda s, x: s + x, donate_argnums=(0,))
    rep = audit_closure("chunk", fn,
                        (jnp.ones((8,)), jnp.ones((8,))),
                        expect_donation=True)
    assert rep.ok and rep.donated is True


def test_debug_callback_flagged():
    def f(x):
        # repro: allow(jax-debug) -- deliberately broken audit fixture
        jax.debug.print("x sum = {}", x.sum())
        return x * 2
    rep = audit_closure("dbg", jax.jit(f), (jnp.ones((4,)),))
    assert "callback-in-jit" in rules_of(rep)


def test_everything_broken_at_once():
    big = jnp.ones((100_000,))

    def f(s, x):
        # repro: allow(jax-debug) -- deliberately broken audit fixture
        jax.debug.print("s = {}", s.sum())
        return s + x + big.sum()

    fn = jax.jit(f)
    with jax.enable_x64():
        rep = audit_closure(
            "broken", fn,
            (jnp.ones((4,), jnp.float64), jnp.ones((4,), jnp.float64)),
            const_budget=256 * 1024, expect_donation=True)
    assert {"baked-constant", "float64-op", "callback-in-jit",
            "donation-mismatch"} <= set(rules_of(rep))


def test_iter_eqns_descends_into_scan():
    def f(xs):
        return jax.lax.scan(lambda c, x: (c + jnp.sin(x), x), 0.0, xs)
    closed = jax.jit(f).trace(jnp.ones((4,))).jaxpr
    prims = {e.primitive.name for e in iter_eqns(closed.jaxpr)}
    assert "scan" in prims and "sin" in prims


# ------------------------------------------------ real trainer matrix --
@pytest.mark.parametrize("spec", [
    CellSpec("single", "dense", False),
    CellSpec("single", "lazy", True),
])
def test_matrix_cell_audits_clean(spec):
    captured = run_cell(spec, engines=("eager", "scan"))
    names = {c.name for c in captured}
    assert "round" in names and "chunk:scan" in names
    for cap in captured:
        rep = cap.audit()
        assert rep.ok, (rep.name, rules_of(rep))
        if cap.name.startswith("chunk"):
            assert rep.donated is spec.sharded
    # the lazy plane must not bake the store's packed rows
    if spec.plane == "lazy":
        assert all(cap.audit().const_bytes < 256 * 1024
                   for cap in captured)
