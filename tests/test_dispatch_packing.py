"""A one-device dispatch on the wire: one packed buffer in, one result out.

`crypto/jax_backend.py` sends every dispatch as the mesh does
(`crypto/lane_wire.py`): one uint8 buffer of `ROW_BYTES` a lane, one device
program (unpack, the kernel, the verdict checksum), one int32 result of
`padded + 2` entries whose host copy is asked for at launch. These cases
hold the program to the seven-argument kernel it wraps, bit for bit, and the
settle seam to every guard it had when a dispatch was seven puts, two
programs and four pulls.

The wrapper is compiled at 8, 64 and 512 lanes around a stand-in kernel
whose verdict every byte and flag of a lane moves (the EC kernel itself
compiles for minutes a shape on the CPU: it runs once here, in the packed
program at the 8-lane rung `warm_kernel` has made, against the host oracle).
"""

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

import jax
import jax.numpy as jnp

import __graft_entry__ as ge
from bitcoinconsensus_tpu.crypto import jax_backend as JB
from bitcoinconsensus_tpu.crypto import lane_wire as W
from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck, TpuSecpVerifier
from bitcoinconsensus_tpu.obs import get_registry
from bitcoinconsensus_tpu.resilience import guards as G
from bitcoinconsensus_tpu.resilience.faults import FaultPlan, FaultSpec, inject

from packed_stub import host_lane_verdicts, pack_result, unpack_result
from test_batch import _stub_fixpoint
from test_resilience import _checks, _stub_verifier

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls


# -- the program against the kernel it wraps ---------------------------------


def _random_lanes(rows: int, seed: int):
    """The kernel's seven arguments with every byte and flag value the
    format must carry, read-only as the native arena's are."""
    rng = np.random.default_rng(seed)
    lanes = (
        rng.integers(0, 256, (rows, 4, 32), dtype=np.uint8),
        *(rng.integers(-1, 2, rows).astype(np.int32) for _ in range(5)),
        rng.integers(0, 2, rows).astype(bool),
    )
    for a in lanes:
        a.flags.writeable = False
    return lanes


def _mixing_kernel(fields, want_odd, parity, has_t2, neg1, neg2, valid):
    """A stand-in for the kernel's seven-argument signature: a verdict and
    a deferral that every byte and every flag of the lane moves."""
    weights = jnp.arange(1, 129, dtype=jnp.int32).reshape(4, 32)
    acc = jnp.sum(fields.astype(jnp.int32) * weights, axis=(1, 2))
    acc = acc + 3 * want_odd + 5 * parity + 7 * has_t2 + 11 * neg1 + 13 * neg2
    ok = valid & (acc % 3 != 0)
    return ok, ~ok & (acc % 5 == 0)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("rows", [8, 64, 512])
def test_packed_program_is_the_kernel_and_the_checksum(rows, backend, monkeypatch):
    """Unpack, kernel, checksum and result in one program give, bit for bit,
    what the kernel gives on the seven arrays and the checksum on its `ok`:
    both rungs (the XLA kernel defers no lane, the Pallas one may)."""
    from bitcoinconsensus_tpu.ops import pallas_kernel

    monkeypatch.setattr(JB, "_verify_kernel", lambda *lanes: _mixing_kernel(*lanes)[0])
    monkeypatch.setattr(pallas_kernel, "verify_tiles", jax.jit(_mixing_kernel))  # a jit, as the kernel is
    lanes = _random_lanes(rows, seed=rows)
    packed = W.pack_lanes(lanes, rows - 1)
    assert packed.shape == (rows, W.ROW_BYTES) == (rows, 135) and packed.dtype == np.uint8
    program = JB._packed_program.__wrapped__(backend)  # a fresh trace: not the cached one
    assert program.__name__ == {"xla": "packed__verify_kernel",
                                "pallas": "packed_verify_tiles"}[backend]
    raw = np.asarray(program(packed))
    assert raw.dtype == np.int32 and raw.shape == (rows + 2,)
    want_ok, want_needs = (np.asarray(a) for a in jax.jit(_mixing_kernel)(*lanes))
    if backend == "xla":
        want_needs = np.zeros_like(want_needs)
    ok, needs, sums = unpack_result(raw)
    assert np.array_equal(ok, want_ok) and np.array_equal(needs, want_needs)
    assert want_ok.any() and not want_ok.all() and (backend == "xla" or want_needs.any())
    assert sums == G.verdict_checksum_host(want_ok)
    assert np.array_equal(raw, pack_result(want_ok, want_needs))


def _mixed_checks():
    """Seven checks over the three kinds with invalid ones among them: a
    Schnorr signature with a flipped bit and a commitment with the wrong
    parity (the device says no), an ECDSA key with a bad prefix (the host
    prep says no: `valid` False)."""
    checks = ge._example_checks(7)  # i % 3: 0 ECDSA, 1 Schnorr, 2 tweak
    pk32, sig64, msg = checks[1].data
    checks[1] = SigCheck("schnorr", (pk32, sig64[:40] + bytes([sig64[40] ^ 1]) + sig64[41:], msg))
    q, par, internal, tweak = checks[2].data
    checks[2] = SigCheck("tweak", (q, par ^ 1, internal, tweak))
    pub, sig, msg = checks[3].data
    checks[3] = SigCheck("ecdsa", (b"\x05" + pub[1:], sig, msg))
    return checks


def test_packed_kernel_is_the_seven_argument_kernel_at_the_8_lane_rung():
    """The EC kernel inside the packed program against the host oracle over
    the three kinds, the invalid ones among them and sentinels in the pad
    row: `ok`, no deferral, both sums. (The wrapper against a seven-argument
    kernel is the stand-in cases' above, at 8, 64 and 512 lanes; the EC
    kernel is compiled in the packed form alone, `conftest.py`.)"""
    v = TpuSecpVerifier()
    checks = _mixed_checks()
    lanes = v._pack_lanes(v._prep_lanes(checks))
    assert not lanes[6][3] and lanes[6][:3].all()
    packed, sset = v._pack_ticket(lanes, len(checks))
    raw = np.asarray(JB._packed_program("xla")(packed))
    ok, needs, sums = unpack_result(raw)
    assert not needs.any() and sums == G.verdict_checksum_host(ok)
    # the stand-in other tests put in the kernel's place answers the same rows
    assert np.array_equal(ok, host_lane_verdicts(*W.unpack_lanes(packed)[:-1]))
    oracle = [v._host_check(c) for c in checks]
    assert list(ok[:7]) == oracle == [True, False, False, False, True, True, True]
    sset.check(ok, needs, "test")
    assert np.array_equal(v.verify_checks(checks), oracle)


# -- the settle seam's guards, on a packed result ------------------------------


def _settled(v, result, padded, sset):
    """`_settle_packed` as the seam calls it: `(ok, needs)`, or the reason
    the guards gave."""
    try:
        return v._settle_packed(result, padded, sset, seam=True)
    except G.VerdictAnomaly as exc:
        return exc.reason


def _ticket_and_answer(n=5, rotation=0, monkeypatch=None):
    """A packed 8-row ticket of `n` oracle-true lanes and the result a
    healthy device would send back for it."""
    v = TpuSecpVerifier()
    lanes = v._pack_lanes(v._prep_lanes(_checks(n, bad_last=False)))
    if monkeypatch is not None:
        monkeypatch.setattr(G, "_rotation", rotation)
    packed, sset = v._pack_ticket(lanes, n)
    ok = np.zeros(8, dtype=bool)
    ok[:n] = True
    ok[sset.positions] = sset.expected
    return v, packed, sset, pack_result(ok)


def test_a_clean_result_passes_every_guard():
    v, packed, sset, raw = _ticket_and_answer()
    ok, needs = _settled(v, raw, 8, sset)
    assert ok.dtype == np.bool_ and list(ok[:5]) == [True] * 5 and not needs.any()


@pytest.mark.parametrize("row", [0, 4, 6])
def test_a_flipped_verdict_byte_is_convicted_by_the_checksum(row):
    """One row's `ok` bit flipped behind the program (a real lane or a
    sentinel; the tail is the pristine buffer's): the recomputed sums
    differ. The sentinel guard runs first, so a flipped sentinel row is
    convicted there."""
    v, packed, sset, raw = _ticket_and_answer()
    raw[row] ^= 1
    assert _settled(v, raw, 8, sset) == ("sentinel" if row in sset.positions else "checksum")


def test_a_replayed_result_is_convicted_by_the_sentinel(monkeypatch):
    """The previous dispatch's whole result, tail and all, sums up: its
    sentinel pattern is the previous rotation's."""
    v, _packed, sset0, raw0 = _ticket_and_answer(rotation=0, monkeypatch=monkeypatch)
    _v, _packed, sset1, raw1 = _ticket_and_answer(rotation=1, monkeypatch=monkeypatch)
    assert list(sset0.expected) != list(sset1.expected)
    assert _settled(v, raw0, 8, sset1) == "sentinel"
    assert not isinstance(_settled(v, raw1, 8, sset1), str)


@pytest.mark.parametrize("cut", ["short", "long", "rows-only", "two-d"])
def test_a_result_of_another_shape_is_convicted_by_the_shape_guard(cut):
    v, _packed, sset, raw = _ticket_and_answer()
    bad = {"short": raw[:-1], "long": np.append(raw, 0).astype(np.int32),
           "rows-only": raw[:8], "two-d": raw.reshape(2, 5)}[cut]
    before = G.GUARD_ANOMALIES.value(site="jax_backend", reason="shape")
    assert _settled(v, bad, 8, sset) == "shape"
    assert G.GUARD_ANOMALIES.value(site="jax_backend", reason="shape") == before + 1


@pytest.mark.parametrize("row_value", [4, 7, -1, 2**31 - 1])
def test_a_row_outside_the_format_is_convicted_by_the_domain_guard(row_value):
    """Neither ok, deferred nor both: the deferral mask is not masked to a
    bit, so the row fails the verdict domain."""
    v, _packed, sset, raw = _ticket_and_answer()
    raw[2] = row_value
    assert _settled(v, raw, 8, sset) == "domain"


def test_the_fault_site_sits_on_the_unpacked_ok():
    """`jax_backend.verdict`, the site the benchmark's `fault-plan` control
    arms: one flip there is caught by the checksum, retried, and the
    verdicts stay the oracle's."""
    checks = _checks(6)
    v, oracle, state = _stub_verifier(checks)
    before = G.GUARD_ANOMALIES.value(site="jax_backend", reason="checksum")
    with inject(FaultPlan([FaultSpec("jax_backend.verdict", "flip", count=1)]), seed=5) as inj:
        out = v.verify_checks(checks)
    assert inj.total_fired() == 1 and state["calls"] == 2
    assert np.array_equal(out, oracle)
    assert G.GUARD_ANOMALIES.value(site="jax_backend", reason="checksum") == before + 1


# -- the ladder, from a packed ticket --------------------------------------------


def _laddered(checks, dead=("pallas",)):
    """A verifier with the chip's ladder (pallas, xla, host) over a host
    stand-in kernel; launches on a rung in `dead` raise."""
    v, oracle, state = _stub_verifier(checks)
    v._use_pallas = True
    from bitcoinconsensus_tpu.resilience import degrade as D

    v._resilience = D.DispatchResilience(v._ladder_levels(), name="packing-test")
    answer = v._run_packed
    seen = []

    def run_packed(packed, n):
        seen.append((v._dispatch_level, packed))
        if v._dispatch_level in dead:
            raise RuntimeError(f"{v._dispatch_level} rung is down")
        return answer(packed, n)

    v._run_packed = run_packed
    return v, oracle, seen


def test_demotion_to_xla_relaunches_the_packed_ticket():
    """The Pallas rung fails its launches: the ladder demotes, and the SAME
    packed buffer, sentinels and all, is answered by the XLA rung."""
    checks = _checks(6)
    v, oracle, seen = _laddered(checks)
    assert v._resilience.ladder.levels == ("pallas", "xla", "host")
    out = v.verify_checks(checks)
    assert np.array_equal(out, oracle)
    assert v._resilience.ladder.current == "xla"
    levels = [level for level, _ in seen]
    assert levels[0] == "pallas" and levels[-1] == "xla"
    assert all(p is seen[0][1] and p.shape == (8, W.ROW_BYTES) for _, p in seen)


def test_demotion_to_the_host_oracle_from_a_packed_ticket():
    """No device rung answers: `verify_checks` resolves on the host oracle,
    and `sync_lanes` hands every lane back as needs_host; the seven arrays
    a caller may want come back out of the ticket bit for bit."""
    checks = _checks(6)
    v, oracle, _seen = _laddered(checks, dead=("pallas", "xla"))
    assert np.array_equal(v.verify_checks(checks), oracle)
    assert v._resilience.ladder.current == "host"
    v, oracle, _seen = _laddered(checks, dead=("pallas", "xla"))
    lanes = v._pack_lanes(v._prep_lanes(checks))
    ticket = v.dispatch_lanes(lanes, len(checks))
    ok, needs = v.sync_lanes(ticket, len(checks))
    assert not ok.any() and needs.all()
    (packed,) = ticket.args
    back = W.unpack_lanes(packed)
    assert back[7][: len(checks)].all() and not back[7][len(checks):].any()  # live
    for got, src in zip(back[:7], lanes):
        assert got.dtype == src.dtype and np.array_equal(got[: len(checks)], src[: len(checks)])


def test_abandon_leaves_no_ticket():
    """A fixpoint abandoned with packed tickets in flight settles them all:
    the queue is empty, every ticket settled."""
    checks = _checks(6)
    v, _oracle, _state = _stub_verifier(checks)
    lanes = v._pack_lanes(v._prep_lanes(checks))
    tickets = [v.dispatch_lanes(lanes, len(checks)) for _ in range(3)]
    assert v._inflight.depth == 3 and not any(t.settled for t in tickets)
    run = _stub_fixpoint(v)
    run._in_flight = (("interp",), ("grow", (), [(t, list(range(7))) for t in tickets]))
    run.abandon()
    assert v._inflight.depth == 0 and all(t.settled for t in tickets)
    assert run._in_flight is None


# -- the counter that says the mechanism engaged -----------------------------------


def _counter(name, **labels):
    samples = get_registry().snapshot().get(name, {"samples": []})["samples"]
    return sum(s["value"] for s in samples
               if all(s["labels"].get(k) == val for k, val in labels.items()))


def test_a_dispatch_is_one_piece_in_and_one_out():
    """`consensus_dispatch_transfers_total{dir}` rises where the pieces are
    made: one put and one host copy asked for a dispatch, whatever it
    holds (`transfers_per_dispatch.*` reads 2.0)."""
    names = ("consensus_dispatch_transfers_total", "consensus_dispatch_total")
    v = TpuSecpVerifier()
    checks = ge._example_checks(7)
    before = {(n, d): _counter(n, **({"dir": d} if d else {}))
              for n in names for d in (("in", "out") if n == names[0] else (None,))}
    for _ in range(3):
        assert v.verify_checks(checks).all()
    rose = {k: _counter(k[0], **({"dir": k[1]} if k[1] else {})) - was
            for k, was in before.items()}
    assert rose == {(names[0], "in"): 3, (names[0], "out"): 3, (names[1], None): 3}
    ticket = v.dispatch_lanes(v._pack_lanes(v._prep_lanes(checks)), 7)
    assert len(ticket.args) == 1 and ticket.aux is None
    assert ticket.result.shape == (8 + 2,) and ticket.result.dtype == jnp.int32
    v.sync_lanes(ticket, 7)
