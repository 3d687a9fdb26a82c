"""Pallas verify kernel vs the XLA-traced kernel: bit-equality.

The pallas path (`ops/pallas_kernel.py`) is the TPU production backend;
the XLA kernel is the reference semantics (itself oracle-tested against
`crypto/secp_host.py`). On CPU the pallas kernel runs in interpreter
mode, in fresh processes (`pallas_equality_check.py`, for the reason
`child_checks.py` gives). Both children start with the file's first test
and each test waits for its own check.
"""

import os

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from child_checks import Children

RUN = os.environ.get("PALLAS_INTERPRET_TESTS", "1") != "0"

pytestmark = pytest.mark.skipif(
    not RUN, reason="pallas interpreter equality disabled (PALLAS_INTERPRET_TESTS=0)"
)

_HELPER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pallas_equality_check.py")
# `small` and `collision` compile the same interpret-mode program (tile=8)
# and share a child; its limit is from its cold time under the tier-1
# command (CHANGES.md, PR 25).
_CHILDREN = {("small", "collision"): 900}


@pytest.fixture(scope="module")
def children(tmp_path_factory):
    with Children(_HELPER, _CHILDREN, tmp_path_factory.mktemp("pallas")) as started:
        yield started


@pytest.mark.limit(930)
def test_pallas_matches_xla_kernel(children):
    """tile=8 adversarial mix, bit-equality (fresh process)."""
    children.expect("small")


@pytest.mark.slow  # a second 10-minute compile the tier-1 run has no room for
@pytest.mark.limit(1530)
def test_pallas_production_shape_matches_xla(tmp_path):
    """PRODUCTION tile (LANE_TILE=512) equality incl. the w=128 Fermat
    narrowing in _tile_batch_inv (fresh process)."""
    with Children(_HELPER, {("production",): 1500}, tmp_path) as child:
        child.expect("production")


@pytest.mark.limit(930)
@pytest.mark.usefixtures("warm_kernel")
def test_exceptional_case_deferred_to_host(children):
    """Crafted equal-points tweak: device-side deferral flag asserted in
    the subprocess; the verify_checks host-fixup loop asserted here
    in-process (it runs the XLA kernel, no pallas compile)."""
    children.expect("collision")

    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.crypto import secp_host as H
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier

    qx, qy = H.G.mul(2).to_affine()
    collision = SigCheck(
        "tweak",
        (
            qx.to_bytes(32, "big"),
            qy & 1,
            H.G_X.to_bytes(32, "big"),
            (1).to_bytes(32, "big"),
        ),
    )
    checks = ge._example_checks(7)
    checks[0] = collision
    v = TpuSecpVerifier(min_batch=8)

    # Full fixup loop through verify_checks (device part simulated: the
    # CPU test env runs the XLA kernel, so inject the pallas-shaped
    # (ok, needs) result).
    orig = v._run_kernel

    def pallas_shaped(args, n):
        res = np.asarray(orig(args, n))
        needs = np.zeros(res.shape[0], dtype=bool)
        needs[0] = True
        res = res.copy()
        res[0] = False
        return res, needs

    v._run_kernel = pallas_shaped
    out = v.verify_checks(checks)
    assert out.all(), "host fixup must resolve the deferred lane TRUE"
    assert not v._fixup_failed
