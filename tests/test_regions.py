"""Kernel regions: region naming, the region-attributed jaxpr walk and
the region-coverage lint (positive + negative fixture).

The contract: every consensus kernel executes under a ``region:<name>``
scope (`ops/regions.py`), which is the name its ops carry in a profiler
trace of the chip; `analysis/host_lint.lint_kernel_regions` holds it.
"""

import jax
import jax.numpy as jnp
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu.analysis import host_lint
from bitcoinconsensus_tpu.analysis import registry
from bitcoinconsensus_tpu.ops import limbs as L
from bitcoinconsensus_tpu.ops import regions as R


# ---------------------------------------------------------------------------
# ops/regions naming metadata.


def test_region_name_and_extraction():
    assert R.region_name("fe_mul") == "region:fe_mul"
    stack = "jit_f/region:scalar_mult/region:fe_mul/mul.3"
    assert R.extract_regions(stack) == ["scalar_mult", "fe_mul"]
    assert R.extract_region(stack) == "fe_mul"
    assert R.extract_regions("jit_f/transpose/mul.3") == []
    assert R.extract_region("no regions here") is None


def test_named_region_decorator_tags_jaxpr():
    @R.named_region("toy_region")
    def f(x):
        return x * 2 + 1

    assert f.__consensus_region__ == "toy_region"
    closed = jax.make_jaxpr(f)(jnp.arange(4))
    acc = host_lint.walk_jaxpr_regions(closed.jaxpr)
    named = sum(n for s, n in acc.items() if s)
    total = sum(acc.values())
    assert total > 0 and named == total
    assert all(s[-1] == "toy_region" for s in acc if s)


def test_scan_body_inherits_enclosing_region():
    """scan/while bodies are re-traced without the caller's name stack;
    the walk must charge their ops to the inherited region."""

    @R.named_region("scan_owner")
    def f(x):
        def body(c, _):
            return c * 2 + 1, ()

        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    closed = jax.make_jaxpr(f)(jnp.arange(4))
    acc = host_lint.walk_jaxpr_regions(closed.jaxpr)
    named = sum(n for s, n in acc.items() if s)
    total = sum(acc.values())
    assert named == total
    # scan multiplies body ops by length: 2 eqns x 4 elems x 5 trips.
    assert total >= 2 * 4 * 5


@pytest.mark.parametrize("kernel, region", [
    ("limbs.fe_mul", "fe_mul"), ("sha256.bip340_challenge", "sighash_prep"),
])
def test_consensus_kernels_are_annotated(kernel, region):
    """The real kernels carry their regions (the field multiply, the
    challenge hash): read off the jaxpr, nothing is compiled."""
    fn, args = registry.get_kernel(kernel).build(4)
    closed = jax.make_jaxpr(fn)(*args)
    acc = host_lint.walk_jaxpr_regions(closed.jaxpr)
    leaves = {s[-1] for s in acc if s}
    assert region in leaves
    named = sum(n for s, n in acc.items() if s)
    total = sum(acc.values())
    assert named / total > 0.95


# ---------------------------------------------------------------------------
# Region-coverage lint: registry kernels pass, a bare toy is a finding.


def test_lint_kernel_regions_clean_on_registry():
    assert host_lint.lint_kernel_regions(include_heavy=False) == []


def test_lint_kernel_regions_negative_fixture():
    """A deliberately unannotated kernel spec must produce a finding —
    the gate proving the lint still fires."""

    def bare(a, b):
        return a * b + a  # no region scope anywhere

    spec = registry.KernelSpec(
        name="toy.unannotated",
        build=lambda B: (
            bare,
            (jax.ShapeDtypeStruct((L.NLIMB, B), jnp.int32),) * 2,
        ),
    )
    findings = host_lint.lint_kernel_regions(specs=[spec])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "region" and "toy.unannotated" in f.path
    assert "named_region" in f.msg


def test_lint_kernel_regions_untraceable_is_a_finding():
    def boom(_B):
        raise RuntimeError("cannot build")

    spec = registry.KernelSpec(name="toy.broken", build=boom)
    findings = host_lint.lint_kernel_regions(specs=[spec])
    assert len(findings) == 1 and "trace failed" in findings[0].msg
