"""Pallas verify kernel vs the XLA-traced kernel: bit-equality.

The pallas path (`ops/pallas_kernel.py`) is the TPU production backend;
the XLA kernel is the reference semantics (itself oracle-tested against
`crypto/secp_host.py`). On CPU the pallas kernel runs in interpreter
mode, in a fresh process (`pallas_equality_check.py`, for the reason
`child_checks.py` gives). The child starts with the file's first test
(`children`) and each test waits for its own check.
"""

import os

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from child_checks import Children

RUN = os.environ.get("PALLAS_INTERPRET_TESTS", "1") != "0"

pytestmark = [
    pytest.mark.skipif(
        not RUN, reason="pallas interpreter equality disabled (PALLAS_INTERPRET_TESTS=0)"
    ),
    # the file's first test starts the child; the two that wait for it run
    # last of their worker's files (`conftest.py` `_KERNEL_SCOPES`)
    pytest.mark.usefixtures("children"),
]

# -- the two pieces of the kernel body that know a tile is (S, L), as plain
# jnp functions on the CPU, and the choice of the tile ----------------------


@pytest.mark.parametrize("lanes,tile,want", [
    (512, None, (4, 128, 1)),    # the warm and the served cell: one half-filled tile
    (1024, None, (8, 128, 1)),
    (2048, None, (8, 128, 2)),   # a shard of the four-chip mesh
    (8192, None, (8, 128, 8)),   # the chunk
    (1536, None, (4, 128, 3)),   # a `pad_step` shape 1,024 does not divide
    (16, 16, (2, 8, 1)),         # the interpret-mode checks: S > 1 at 8 lanes a row
    (8, 8, (1, 8, 1)),
    (2048, 512, (4, 128, 4)),    # an explicit tile (scripts/kernel_ab.py --tile)
])
def test_tile_follows_from_the_lanes_dispatched(lanes, tile, want):
    from bitcoinconsensus_tpu.ops.pallas_kernel import LANE_TILE, tile_grid

    assert tile_grid(lanes, tile) == want
    assert LANE_TILE == 512  # what jax_backend and mesh test `padded` against


def _random_elements(rng, shape):
    """Weak field elements (20,) + shape and their values as Python ints."""
    from bitcoinconsensus_tpu.ops import limbs as L

    n = int(np.prod(shape))
    vals = [int.from_bytes(rng.bytes(32), "big") % L.P_INT or 1 for _ in range(n)]
    arr = L.ints_to_limbs_batch(vals).T.reshape((L.NLIMB,) + shape)
    return arr, vals


@pytest.mark.parametrize("shape", [(1, 8), (2, 8), (4, 128)])
def test_tile_batch_inverse_matches_the_per_lane_inverse(shape):
    """Every live lane's output is that lane's own inverse, whatever its
    row's infinity and deferred lanes hold (a zero among them would zero
    the row's product, were it not replaced by one)."""
    import jax
    import jax.numpy as jnp

    from bitcoinconsensus_tpu.ops import limbs as L
    from bitcoinconsensus_tpu.ops.pallas_kernel import _tile_batch_inv

    rng = np.random.default_rng(42 + shape[0] * shape[1])
    z, vals = _random_elements(rng, shape)
    skip = rng.random(shape) < 0.25
    skip[0, 0], skip[-1, -1] = True, False  # a row's first lane out, its last in
    z = np.where(skip[None] & (rng.random(shape) < 0.5)[None], 0, z)  # Z = 0: infinity
    ones = np.zeros_like(z)
    ones[0] = 1
    got = jax.jit(lambda a, m, o: L.fe_canon(_tile_batch_inv(a, m, o)))(
        jnp.asarray(z), jnp.asarray(skip), jnp.asarray(ones))
    got = np.asarray(got).reshape(L.NLIMB, -1)
    live = np.flatnonzero(~skip.ravel())
    assert live.size > skip.size // 2
    for i in live:
        assert L.limbs_to_int(got[:, i]) == pow(vals[i], -1, L.P_INT), i


@pytest.mark.parametrize("shape", [(1, 8), (2, 8), (8, 128)])
def test_g_select_relayout_matches_the_two_dimensional_product(shape):
    """The a·G select of an (S, L) tile, a row at a time and stacked
    behind the limbs, is the XLA path's (255, B) one-hot product over the
    flattened tile, and the table's own rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bitcoinconsensus_tpu.ops.curve import _g_table
    from bitcoinconsensus_tpu.ops.pallas_kernel import _g_select

    rng = np.random.default_rng(7)
    da = rng.integers(0, 256, size=shape).astype(np.int32)
    da[0, :3] = (0, 1, 255)  # no row, the first, the last
    gx, gy = (np.asarray(t[5]) for t in _g_table())  # window 5: (255, 20)
    selx, sely = jax.jit(_g_select)(
        jnp.asarray(da), jnp.asarray(gx, jnp.float32), jnp.asarray(gy, jnp.float32))
    flat = da.reshape(-1)
    oh = (flat[None, :] == np.arange(1, 256)[:, None]).astype(np.float32)
    for sel, table in ((selx, gx), (sely, gy)):
        assert sel.shape == (20,) + shape and sel.dtype == jnp.int32
        flat_product = jnp.dot(jnp.asarray(table, jnp.float32).T, oh,
                               precision=lax.Precision.HIGHEST).astype(jnp.int32)
        assert np.array_equal(np.asarray(sel).reshape(20, -1), np.asarray(flat_product))
        rows = np.where(flat[:, None] > 0, table[np.maximum(flat, 1) - 1], 0)
        assert np.array_equal(np.asarray(sel).reshape(20, -1), rows.T)


_HELPER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "pallas_equality_check.py")
# `small` and `collision` compile the same interpret-mode program (tile=16:
# two sublane rows of 8 lanes) and share a child. XLA:CPU compiles that
# program on one thread for most of its time: with tracing and lowering 12
# minutes alone on the sandbox and over 15 beside a cold tier-1 run, whose
# last process it is. Its limit is the one it had before PR 44, so from an
# empty cache the child is killed and its two tests fail, as on the trees
# before; from a warm one it loads in 7 to 10 minutes. The issue's bound (800 s,
# the child inside two thirds of it) takes a smaller traced program (ROADMAP
# S1d, D10 (a)). Times: CHANGES.md, PR 44.
_CHILDREN = {("small", "collision"): 900}


@pytest.fixture(scope="session")
def children(tmp_path_factory):
    """The child, for as long as the session."""
    with Children(_HELPER, _CHILDREN, tmp_path_factory.mktemp("pallas")) as started:
        yield started


@pytest.mark.limit(930)
def test_pallas_matches_xla_kernel(children):
    """tile=16 adversarial mix, bit-equality (fresh process)."""
    children.expect("small")


@pytest.mark.slow  # a second 10-minute compile the tier-1 run has no room for
@pytest.mark.limit(1530)
def test_pallas_production_shape_matches_xla(tmp_path):
    """PRODUCTION tile (LANE_TILE=512, four rows of 128 lanes) equality
    incl. the 128-lane trees of _tile_batch_inv (fresh process)."""
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
    # CPU test env runs the XLA program, so inject the pallas-shaped
    # result: lane 0 deferred, its ok False, the checksum pair over that).
    from packed_stub import pack_result, unpack_result

    orig = v._run_packed

    def pallas_shaped(packed, n):
        ok, needs, _sums = unpack_result(orig(packed, n))
        assert ok[0] and not needs.any()  # the XLA complete adds resolve it
        ok, needs = ok.copy(), needs.copy()
        ok[0], needs[0] = False, True
        return pack_result(ok, needs)

    v._run_packed = pallas_shaped
    out = v.verify_checks(checks)
    assert out.all(), "host fixup must resolve the deferred lane TRUE"
    assert not v._fixup_failed
