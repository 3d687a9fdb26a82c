"""The mesh dispatch's wire format: one packed buffer in, one packed result out.

`parallel/mesh.py` sends a dispatch to the shards as one uint8 buffer of
`ROW_BYTES` a lane and takes its verdicts back as one int32 array. These
cases hold the format from both sides without compiling the sharded kernel
(that runs in `tests/mesh_checks.py`'s children, check `packing`): the
layout against the eight-array layout it replaced, kept here as the loop it
was; the program's unpack ops, jitted alone under the mesh's own sharding,
against their host twin; the result's unpack; and the paths that need the
seven separate arrays back from a packed ticket.
"""

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

import jax

from bitcoinconsensus_tpu.parallel import mesh as M
from bitcoinconsensus_tpu.resilience import guards as G
from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

from mesh_stub import pack_result, traced_unpack
from test_parallel import _fd_checks, _mesh_stub_verifier

# The four-chip cell's shape: 8,192 rows over four shards of 2,047 lanes and
# a sentinel. Its last dispatch holds 6,308 lanes; 2,100 leave two shards
# empty; 8,188 fill every shard; 5 leave three empty.
_CELL = dict(mesh=4, min_batch=512, chunk=8192)
_FILLS = {
    "last-dispatch": (6308, [2047, 2047, 2047, 167]),
    "empty-shards": (2100, [2047, 53, 0, 0]),
    "full": (8188, [2047] * 4),
    "one-shard": (5, [5, 0, 0, 0]),
}


def _cell_verifier():
    return M.ShardedSecpVerifier(
        mesh=M.make_mesh(_CELL["mesh"]), min_batch=_CELL["min_batch"],
        chunk=_CELL["chunk"],
    )


def _random_lanes(rows: int, seed: int):
    """The kernel's seven arguments with every byte and flag value the
    format must carry: all 256 field bytes, flags of -1, 0 and 1."""
    rng = np.random.default_rng(seed)
    fields = rng.integers(0, 256, (rows, 4, 32), dtype=np.uint8)
    flags = [rng.integers(-1, 2, rows).astype(np.int32) for _ in range(5)]
    return (fields, *flags, rng.integers(0, 2, rows).astype(bool))


def _eight_array_layout(args, n, padded, d, rotation):
    """The layout before the packing (PR 33), as the loop it was: eight
    fresh arrays, one block copy a shard and array, pad rows, sentinels."""
    shard = padded // d
    cap = shard - 1
    fill = M._shard_fill(n, shard, d)
    out = []
    for a, pv in zip(args, M._PAD_VALUES):
        buf = np.empty((padded,) + a.shape[1:], dtype=a.dtype)
        for s, k in enumerate(fill):
            row = s * shard
            buf[row : row + k] = a[s * cap : s * cap + k]
            buf[row + k : row + shard] = pv
        out.append(buf)
    live = np.zeros(padded, dtype=bool)
    for s, k in enumerate(fill):
        live[s * shard : s * shard + k] = True
    sset = G.install_sentinels_at(
        tuple(out), [s * shard + cap for s in range(d)], rotation=rotation
    )
    return tuple(out) + (live,), sset


@pytest.mark.parametrize("name", list(_FILLS))
def test_packed_layout_is_the_eight_array_layout(name, monkeypatch):
    """`_build_layout`'s one buffer unpacks, bit for bit and dtype for
    dtype, to the eight arrays the step took before: real lanes, pad rows
    (parity -1), sentinel rows, the `live` mask."""
    n, fill = _FILLS[name]
    v = _cell_verifier()
    assert M._shard_fill(n, 2048, 4) == fill
    args = _random_lanes(8192, seed=n)
    for a in args:
        a.flags.writeable = False  # the native arena's buffers are
    monkeypatch.setattr(G, "_rotation", 3)
    (packed,), layout = v._build_layout(args, n)
    want, sset = _eight_array_layout(args, n, 8192, 4, rotation=3)
    assert packed.shape == (8192, M.ROW_BYTES) == (8192, 135)
    assert packed.dtype == np.uint8 and packed.flags.c_contiguous
    got = M.unpack_lanes(packed)
    assert len(got) == len(want) == 8
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    assert list(layout.flat_sset.positions) == list(sset.positions)
    assert list(layout.flat_sset.expected) == list(sset.expected)
    assert (got[2] == -1).any() and (want[2][:n] == -1).any()
    assert int(got[7].sum()) == n
    # the program's dtypes, and `pack_lanes` gives the same bytes back
    assert [a.dtype for a in got] == [np.uint8] + [np.int32] * 5 + [np.bool_] * 2
    assert np.array_equal(M.pack_lanes(got[:7], got[7]), packed)


def test_lane_views_write_through():
    """What the layout and the sentinel install write through the views is
    in the buffer: no view is a copy."""
    packed = np.zeros((6, M.ROW_BYTES), dtype=np.uint8)
    views = M._lane_views(packed)
    assert all(np.shares_memory(v, packed) for v in views)
    views[0][2, 3, 31] = 0xAB
    views[2][4] = -1
    views[6][5] = True
    views[7][1] = 1
    assert packed[2, 3 * 32 + 31] == 0xAB and packed[4, 129] == 0xFF
    assert packed[5, 133] == 1 and packed[1, 134] == 1
    assert int(packed.astype(np.int64).sum()) == 0xAB + 0xFF + 2


@pytest.mark.parametrize("name", ["last-dispatch", "empty-shards"])
def test_traced_unpack_is_the_host_unpack(name):
    """The first ops of the sharded program, jitted alone under the mesh's
    sharding: every shard's slice widens to what `unpack_lanes` gives."""
    n, _fill = _FILLS[name]
    v = _cell_verifier()
    (packed,), _layout = v._build_layout(_random_lanes(8192, seed=7 * n), n)
    on_mesh = jax.device_put(packed, v._packed_sharding)
    assert len(on_mesh.addressable_shards) == 4
    traced = traced_unpack(v.mesh)(on_mesh)
    host = M.unpack_lanes(packed)
    assert len(traced) == len(host) == 8
    for t, h in zip(traced, host):
        assert t.dtype == h.dtype and np.array_equal(np.asarray(t), h)


@pytest.mark.parametrize("n_shards,shard", [(4, 2048), (4, 4), (8, 2), (3, 5)])
def test_packed_result_unpacks_to_the_five_results(n_shards, shard):
    """ok, the deferral mask, the psum verdict and a checksum pair a shard
    come back out of the one array as they went in."""
    rng = np.random.default_rng(shard)
    rows = n_shards * shard
    ok = rng.integers(0, 2, rows).astype(bool)
    needs = rng.integers(0, 2, rows).astype(bool) & ~ok
    for all_ok in (True, False):
        raw = pack_result(ok, needs, all_ok, n_shards)
        assert raw.shape == (rows + 3 * n_shards,) and raw.dtype == np.int32
        got_ok, got_needs, got_all, cnts, wsums = M.unpack_result(raw, n_shards)
        assert got_ok.dtype == np.bool_ and np.array_equal(got_ok, ok)
        assert np.array_equal(got_needs, needs.astype(np.int32))
        assert got_all is all_ok
        pairs = [G.verdict_checksum_host(s) for s in np.split(ok, n_shards)]
        assert list(zip(cnts.tolist(), wsums.tolist())) == pairs


def test_one_shard_without_the_psum_verdict_is_not_all_ok():
    ok = np.ones(8, dtype=bool)
    raw = pack_result(ok, ~ok, True, 4)
    raw[2 + 3 - 1] = 0  # shard 0's copy of the verdict
    assert M.unpack_result(raw, 4)[2] is False


@pytest.mark.parametrize("shape", [(21,), (12,), (5, 4)])
def test_unpack_result_refuses_a_buffer_that_does_not_split(shape):
    """Not a multiple of the shards, no row beside the tail, not flat."""
    with pytest.raises(ValueError, match="packed result"):
        M.unpack_result(np.zeros(shape, dtype=np.int32), 4)


def _faulted(v, checks, site="mesh.shard.2", kind="flip", count=1):
    with inject(FaultPlan([FaultSpec(site, kind, count=count)])) as inj:
        res, verdict = v.verify_checks_with_verdict(checks)
    assert inj.total_fired() == count
    return np.asarray(res, dtype=bool), verdict


def test_settle_partial_redispatches_from_a_packed_ticket():
    """A shard convicted at settle: its real lanes, and no others, come out
    of the ticket's packed buffer as the seven arrays they went in as."""
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    lanes = v._pack_lanes(v._prep_lanes(checks))
    seen = []
    redispatch = v._redispatch_lanes
    v._redispatch_lanes = lambda sub, k: seen.append((sub, k)) or redispatch(sub, k)
    res, verdict = _faulted(v, checks)
    assert np.array_equal(res, oracle) and not verdict
    (sub, k), = seen
    assert k == 3 and len(sub) == 7  # shard 2 of 8 holds lanes 6, 7, 8
    for got, src in zip(sub, lanes):
        assert got.dtype == src.dtype and np.array_equal(got, src[6:9])


def test_redispatch_falls_to_one_device_from_a_packed_ticket():
    """With the mesh refusing the re-dispatch, the single-device rung
    answers the convicted shard's lanes from the unpacked arrays."""
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    v._redispatch_mesh = lambda sub, k: None
    xla0 = M._MESH_REDISPATCH_LANES.value(level="xla")
    res, verdict = _faulted(v, checks)
    assert np.array_equal(res, oracle) and not verdict
    assert M._MESH_REDISPATCH_LANES.value(level="xla") == xla0 + 3


def test_quarantined_mesh_runs_a_packed_ticket_on_one_device():
    """A mesh launch that raises twice quarantines the mesh rung: the
    packed ticket is unpacked for the single-device kernel and settles
    behind the flat sentinel set, verdicts as the oracle's."""
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    unpacked = []
    kernel = v._run_kernel
    v._run_kernel = lambda args, n: unpacked.append(args) or kernel(args, n)
    res, verdict = _faulted(v, checks, site="mesh.dispatch", kind="raise", count=2)
    assert np.array_equal(res, oracle) and not verdict
    assert v._resilience.ladder.current == "xla"
    (args,), lanes = unpacked, v._pack_lanes(v._prep_lanes(checks))
    assert [a.dtype for a in args] == [a.dtype for a in lanes]
    assert args[0].shape == (32, 4, 32)  # 14 lanes + 8 sentinels pad to 32


def test_transfers_are_counted_a_piece():
    """A packed buffer put to four shards is four pieces in; the stand-in
    step's host result asks for no copy, so none is counted out."""
    v = _cell_verifier()
    v._step = lambda on_mesh: np.zeros(8192 + 12, dtype=np.int32)
    (packed,), _layout = v._build_layout(_random_lanes(8192, seed=1), 100)
    before = {d: M._MESH_TRANSFERS.value(dir=d) for d in ("in", "out")}
    v._run_step(packed)
    assert M._MESH_TRANSFERS.value(dir="in") == before["in"] + 4
    assert M._MESH_TRANSFERS.value(dir="out") == before["out"]
    phases = v.phases.report()
    assert phases["shard_put"]["calls"] == phases["shard_exec"]["calls"] == 1


@pytest.mark.parametrize("row", [4, 7, -1, 2**31 - 1])
def test_a_row_outside_the_format_convicts_its_shard(row):
    """A result row that is neither ok, deferred nor both fails its shard's
    domain guard: the other shards' verdicts stand, the shard's lanes are
    answered again."""
    checks = _fd_checks(13)
    v, oracle = _mesh_stub_verifier(checks)
    step, fired = v._step, []

    def bent(packed):
        raw = step(packed)
        if not fired:  # shard 1 of 8, four rows and the tail a shard
            raw[(4 + 3) * 1] = row
            fired.append(row)
        return raw

    v._step = bent
    bad0 = M._MESH_SHARD_FAILURES.value(device="1", reason="domain")
    res, verdict = v.verify_checks_with_verdict(checks)
    assert fired and np.array_equal(np.asarray(res, dtype=bool), oracle) and not verdict
    assert M._MESH_SHARD_FAILURES.value(device="1", reason="domain") == bad0 + 1
