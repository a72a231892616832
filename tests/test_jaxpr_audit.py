"""repro.analysis.jaxpr_audit: fingerprint stability + violation detection."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import jaxpr_audit


def _toy_program():
    def program(x, key):
        noise = jax.random.normal(key, x.shape)

        def tick(c, v):
            return c + v, v

        total, _ = jax.lax.scan(tick, jnp.float32(0), x + noise)
        return total

    args = (jnp.ones((8,), jnp.float32), jax.random.PRNGKey(0))
    return program, args


def test_fingerprint_stable_within_process():
    program, args = _toy_program()
    r1 = jaxpr_audit.audit_program("toy", program, args)
    r2 = jaxpr_audit.audit_program("toy", program, args)
    assert r1.ok, r1.violations
    assert r1.fingerprint == r2.fingerprint
    assert r1.n_eqns == r2.n_eqns
    assert r1.primitives == r2.primitives


def test_topology_family_matches_golden_pin():
    # cross-process stability: the family re-traced here must reproduce the
    # fingerprint pinned by `python -m repro.analysis.jaxpr_audit --write`
    result = jaxpr_audit.audit_family("topology")
    assert result.ok, result.violations
    golden = jaxpr_audit.load_golden()
    problems = jaxpr_audit.check_against_golden([result], golden)
    assert problems == []


def test_golden_covers_every_family():
    golden = jaxpr_audit.load_golden()
    assert sorted(golden) == sorted(jaxpr_audit.FAMILIES)
    for family, pin in golden.items():
        assert set(pin) == {"fingerprint", "n_eqns", "primitives"}, family
        assert len(pin["fingerprint"]) == 64, family


def test_f64_program_fails_audit():
    def program(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        result = jaxpr_audit.audit_program(
            "f64", program, (jnp.ones((4,), jnp.float32),)
        )
    assert not result.ok
    assert any("float64" in v for v in result.violations)


def test_callback_program_fails_audit():
    import numpy as np

    def program(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            x,
        )

    result = jaxpr_audit.audit_program(
        "cb", program, (jnp.ones((4,), jnp.float32),)
    )
    assert not result.ok
    assert any("callback" in v for v in result.violations)


def test_drift_reports_primitive_delta():
    program, args = _toy_program()
    r = jaxpr_audit.audit_program("toy", program, args)
    pin = {
        "toy": {
            "fingerprint": "0" * 64,
            "n_eqns": r.n_eqns + 3,
            "primitives": dict(r.primitives, scan=r.primitives.get("scan", 0) + 1),
        }
    }
    problems = jaxpr_audit.check_against_golden([r], pin)
    assert len(problems) == 1
    assert "drift" in problems[0]
    assert "n_eqns" in problems[0]
    assert "scan" in problems[0]


def test_missing_pin_is_a_problem():
    program, args = _toy_program()
    r = jaxpr_audit.audit_program("unpinned", program, args)
    problems = jaxpr_audit.check_against_golden([r], {})
    assert problems and "no golden fingerprint" in problems[0]
