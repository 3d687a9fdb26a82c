"""Tests for the jaxpr-level consensus analyzer (`analysis/`).

Three families:

- pins: the analyzer's derived per-limb intervals for the settled field
  ops must equal the hand-tracked constants documented in ops/limbs.py
  (W2, and the `_pass`/`_fold_high` Bounds bookkeeping). A drift in
  either direction is a finding: looser means the analyzer regressed,
  tighter means the hand bounds are stale.
- negatives: deliberately broken toy kernels (float creep, an
  overflowing 14-bit radix, int64 intermediates, data-dependent while
  loops, non-allowlisted primitives, understated hand bounds) must each
  be flagged with the right violation kind.
- sweeps (slow-marked): every registered kernel proves clean end to end,
  exactly as the CI `analysis` job runs it.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from bitcoinconsensus_tpu.analysis import host_lint, registry
from bitcoinconsensus_tpu.analysis import interval as IV
from bitcoinconsensus_tpu.ops import limbs as L

B = 2


def _fe():
    return jax.ShapeDtypeStruct((L.NLIMB, B), jnp.int32)


def _w2_rows():
    return [(0, int(w)) for w in L.W2]


# ---------------------------------------------------------------------------
# Pins: derived intervals == hand-tracked constants.


def test_fe_add_output_rows_pin_w2():
    rep = registry.get_kernel("limbs.fe_add").analyze()
    assert rep.ok, rep.violations[:3]
    assert rep.out_bounds[0] == _w2_rows()


def test_fe_mul_output_rows_pin_w2():
    rep = registry.get_kernel("limbs.fe_mul").analyze()
    assert rep.ok, rep.violations[:3]
    assert rep.out_bounds[0] == _w2_rows()


def test_pass_derived_bounds_equal_hand_bounds():
    # One carry pass from the fe_add pre-settle state (2*W2): the hand
    # Bounds arithmetic in L._pass and the analyzer must agree row by row.
    bounds = [2 * int(w) for w in L.W2]
    _, hand = L._pass(np.zeros((L.NLIMB, 1), np.int32), bounds)
    rep = IV.analyze(
        lambda x: L._pass(x, bounds)[0], (_fe(),), "limbs._pass",
        in_bounds={0: [(0, b) for b in bounds]},
    )
    assert rep.ok, rep.violations[:3]
    assert rep.out_bounds[0] == [(0, int(b)) for b in hand]


def test_fold_high_derived_bounds_equal_hand_bounds():
    bounds = [int(w) for w in L.W2] + [37]
    shape = jax.ShapeDtypeStruct((L.NLIMB + 1, B), jnp.int32)
    _, hand = L._fold_high(np.zeros((L.NLIMB + 1, 1), np.int32), bounds)
    rep = IV.analyze(
        lambda x: L._fold_high(x, bounds)[0], (shape,), "limbs._fold_high",
        in_bounds={0: [(0, b) for b in bounds]},
    )
    assert rep.ok, rep.violations[:3]
    assert rep.out_bounds[0] == [(0, int(b)) for b in hand]


# ---------------------------------------------------------------------------
# Negatives: broken toy kernels must be flagged, with the right kind.


def _kinds(rep):
    return {v.kind for v in rep.violations}


def test_float_creep_is_flagged():
    def bad(x):
        return (x.astype(jnp.float32) * 0.5).astype(jnp.int32)

    rep = IV.analyze(bad, (_fe(),), "bad.float_creep", in_bounds={0: (0, 100)})
    assert not rep.ok
    assert "float" in _kinds(rep)


def test_radix14_mul_overflow_is_flagged():
    # fe_mul is only int32-safe under the 13-bit weak contract; feed it
    # 14-bit limbs and the convolution must be caught exceeding int32.
    rows = [(0, (1 << 14) - 1)] * L.NLIMB
    rep = IV.analyze(L.fe_mul, (_fe(), _fe()), "bad.radix14",
                     in_bounds={0: rows, 1: rows})
    assert not rep.ok
    assert "overflow" in _kinds(rep)


def test_int64_intermediate_is_flagged():
    def bad(x):
        y = x.astype(jnp.int64)
        return (y * y).astype(jnp.int32)

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(bad)(jax.ShapeDtypeStruct((4,), jnp.int32))
    rep = IV.analyze_closed(closed, "bad.int64", in_bounds={0: (0, 10)})
    assert not rep.ok
    assert "dtype64" in _kinds(rep)


def test_data_dependent_while_is_flagged():
    def bad(x):
        return lax.while_loop(
            lambda c: c[0] < c[1], lambda c: (c[0] + 1, c[1]),
            (x[0, 0], x[1, 0]),
        )[0]

    rep = IV.analyze(bad, (_fe(),), "bad.while", in_bounds={0: (0, 100)})
    assert not rep.ok
    assert "loop" in _kinds(rep)


def test_non_allowlisted_primitive_is_flagged():
    def bad(x):
        return lax.sort(x, dimension=0)

    rep = IV.analyze(bad, (_fe(),), "bad.sort", in_bounds={0: (0, 100)})
    assert not rep.ok
    assert "allowlist" in _kinds(rep)


def test_understating_hand_bound_fails_loudly():
    rep = IV.analyze(
        L.fe_add, (_fe(), _fe()), "bad.understate",
        in_bounds={0: _w2_rows(), 1: _w2_rows()},
        out_within=[[(0, 7)] * L.NLIMB],
    )
    assert not rep.ok
    assert any("understates" in v.msg for v in rep.violations)


# ---------------------------------------------------------------------------
# Exact-float certificate: soundness edges of the carried domain.


def test_unvetted_prim_demotes_certificate_with_source():
    # integer_pow is on the determinism allowlist but has no vetted
    # exact-float transfer: the certificate demotes there, and the
    # downstream astype(int32) cites the demotion site.
    def bad(x):
        return (x.astype(jnp.float32) ** 2).astype(jnp.int32)

    rep = IV.analyze(bad, (_fe(),), "bad.unvetted", in_bounds={0: (0, 100)})
    assert not rep.ok
    assert "float" in _kinds(rep)
    demote = next(v for v in rep.violations if "integer_pow" in v.msg)
    assert "vetted" in demote.msg
    conv = next(v for v in rep.violations if "float->int" in v.msg)
    assert "integer_pow" in conv.msg  # sourced via the carried fwhy


def test_dot_accumulation_boundary():
    # The sound dot rule is the ACCUMULATED sum bound: K * max|product|
    # <= 2^24. K = 16, |x| <= 1024 sits exactly at 16 * 1024^2 = 2^24
    # (every partial sum representable); one past the operand bound
    # overflows the mantissa and must fail.
    def dotk(x):
        xf = x.astype(jnp.float32)
        y = lax.dot_general(xf, xf, (((0,), (0,)), ((), ())),
                            precision=lax.Precision.HIGHEST)
        return y.astype(jnp.int32)

    shape = jax.ShapeDtypeStruct((16, B), jnp.int32)
    rep = IV.analyze(dotk, (shape,), "dot.at_bound", in_bounds={0: (0, 1024)})
    assert rep.ok, rep.violations[:3]
    entry = next(e for e in rep.exactness if e["prim"] == "dot_general")
    assert entry["exact"] and entry["k_terms"] == 16
    assert entry["sum_abs_bound"] == 1 << 24

    rep = IV.analyze(dotk, (shape,), "dot.past_bound",
                     in_bounds={0: (0, 1025)})
    assert not rep.ok
    assert "float" in _kinds(rep)


def test_reduce_sum_cancellation_is_caught():
    # Witness for why the result-hull check was unsound: rows pinned to
    # +/-(2^24 - 1) sum to the exact hull [0, 0], but a partial sum
    # reaches 2 * (2^24 - 1) > 2^24 — only the accumulated Sigma|terms|
    # bound is sound.
    m = (1 << 24) - 1
    rows = [(m, m), (-m, -m), (m, m), (-m, -m)]

    def bad(x):
        return x.astype(jnp.float32).sum(axis=0).astype(jnp.int32)

    shape = jax.ShapeDtypeStruct((4, B), jnp.int32)
    rep = IV.analyze(bad, (shape,), "bad.cancel", in_bounds={0: rows})
    assert not rep.ok
    assert "float" in _kinds(rep)


def test_astype_roundtrip_recovers_certificate():
    # int->f32 re-grants the certificate regardless of history: the
    # round-tripped chain proves clean and every f32 value in the trace
    # is certified exact.
    def fn(x):
        y = (x.astype(jnp.float32) + 1.0).astype(jnp.int32)
        return (y * 1000).astype(jnp.float32).astype(jnp.int32)

    rep = IV.analyze(fn, (_fe(),), "roundtrip", in_bounds={0: (0, 100)})
    assert rep.ok, rep.violations[:3]
    f32 = [e for e in rep.exactness if e["dtype"] == "float32"]
    assert f32 and all(e["exact"] for e in f32)
    assert rep.to_dict()["exactness"] == rep.exactness


def test_unproven_f32_output_is_flagged_at_the_gate():
    def bad(x):
        return x.astype(jnp.float32) * 0.5

    rep = IV.analyze(bad, (_fe(),), "bad.f32out", in_bounds={0: (0, 100)})
    assert not rep.ok
    assert any("consensus-visible output" in v.msg for v in rep.violations)


def test_exact_f32_output_passes_the_gate():
    rep = IV.analyze(lambda x: x.astype(jnp.float32), (_fe(),),
                     "ok.f32out", in_bounds={0: (0, 100)})
    assert rep.ok, rep.violations[:3]


# ---------------------------------------------------------------------------
# Host-side AST lint.


def test_host_lint_flags_violations(tmp_path):
    p = tmp_path / "bad.py"
    p.write_text(
        "import random\n"
        "import time\n"
        "x = 0.5\n"
        "y = float(3)\n"
        "z = 1 / 2\n"
        "t = time.time()\n"
    )
    rules = {f.rule for f in host_lint.lint_paths([str(p)])}
    assert {"nondeterminism", "float-literal", "float-op",
            "time-dependence"} <= rules


def test_host_lint_timing_rules_subset(tmp_path):
    # crypto/ is scanned with TIMING_RULES only: floats and `/` are fine
    # there (jax config, fill ratios), but ad-hoc clock reads must still
    # be flagged — all timing flows through obs spans.
    p = tmp_path / "driver.py"
    p.write_text(
        "x = 0.5\n"
        "ratio = 3 / 4\n"
        "t0 = time.perf_counter()\n"
    )
    findings = host_lint.lint_paths([str(p)], rules=host_lint.TIMING_RULES)
    assert [f.rule for f in findings] == ["time-dependence"]
    assert findings[0].line == 3
    assert "obs spans" in findings[0].msg


def test_host_lint_sync_rule_flags_hidden_blocking(tmp_path):
    # The dispatch path may not force device buffers to host outside the
    # settle seam: bare np.asarray / .block_until_ready / jax.device_get
    # are hidden synchronization points that re-serialize the pipeline.
    p = tmp_path / "pipeline.py"
    p.write_text(
        "def drive(x, y):\n"
        "    a = x.block_until_ready()\n"
        "    b = np.asarray(y)\n"
        "    c = jax.device_get(y)\n"
        "    return a, b, c\n"
        "def _materialize_guarded(x):\n"
        "    return np.asarray(x)\n"  # the settle seam itself is exempt
        "def settle_array(x):\n"
        "    return np.asarray(x)\n"  # the sanctioned helper is exempt
    )
    findings = host_lint.lint_paths([str(p)], rules=host_lint.SYNC_RULES)
    assert [f.rule for f in findings] == ["sync"] * 3
    assert [f.line for f in findings] == [2, 3, 4]
    assert all("settle" in f.msg for f in findings)


def test_host_lint_flags_unpinned_dot_precision(tmp_path):
    p = tmp_path / "bad_dot.py"
    p.write_text(
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "y = jnp.dot(a, b)\n"
        "z = lax.dot_general(a, b, dn, precision=lax.Precision.DEFAULT)\n"
        "ok = jax.lax.dot_general(a, b, dn,\n"
        "                         precision=lax.Precision.HIGHEST)\n"
    )
    findings = host_lint.lint_paths([str(p)],
                                    rules=host_lint.PRECISION_RULES)
    assert [f.rule for f in findings] == ["dot-precision"] * 2
    assert [f.line for f in findings] == [3, 4]
    assert all("HIGHEST" in f.msg for f in findings)


def test_host_lint_clean_on_consensus_path():
    # Covers crypto/ (timing rule) as well as core/ + models/ (full rules):
    # the instrumented pipeline itself must satisfy its own lint.
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(host_lint.__file__))))
    assert host_lint.lint_consensus_host(repo) == []


# ---------------------------------------------------------------------------
# Full sweeps (slow: these re-prove whole kernels; the CI `analysis` job
# is the canonical runner, these keep `pytest -m slow` equivalent).


@pytest.mark.slow
def test_every_quick_kernel_proves():
    for spec in registry.all_kernels(include_heavy=False):
        rep = spec.analyze()
        assert rep.ok, (spec.name, rep.violations[:3])


@pytest.mark.slow
def test_glv_ladder_proves():
    rep = registry.get_kernel("curve.double_scalar_mult_glv").analyze()
    assert rep.ok, rep.violations[:3]


@pytest.mark.slow
def test_verify_kernel_proves():
    rep = registry.get_kernel("jax_backend.verify_kernel").analyze()
    assert rep.ok, rep.violations[:3]
