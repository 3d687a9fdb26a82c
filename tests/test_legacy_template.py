"""The native legacy digest against the executable spec, and its template.

`native/interp.hpp` `legacy_sighash` builds no preimage: a transaction lays
the part of `CTransactionSignatureSerializer`'s output that does not depend
on the signing input down once (`LegacyTemplate`: for every input prevout,
an empty script and the sequence, then the outputs; a second string with
the sequences zeroed for SIGHASH_NONE and SIGHASH_SINGLE), and a digest
hashes spans of it around its own script code, the first of them resumed
from the SHA-256 state the template keeps every `GRID` bytes of the stream
`version || compact_size(n_in) || string` where the digest's own script lies
past one (PR 47). Here every digest comes out
of the ctypes entries that reach the function (`nat_verify_input` and
`nat_verify_inputs_idx`, deferring: the check an `OP_CHECKSIG` records
carries the digest) and is held to
`core/sighash.py`'s, hash-type byte by hash-type byte; use never changes a
template; thirteen workers that ask one transaction for its first
digest at once build it once; and a digest resumed from the grid is the
digest `core/sighash.py` and the benchmark's plain reference
(`benchmarks/harness/sighashref.py`, which never takes the short cut) give,
from fewer bytes, at the grid's edges too.
"""

import functools
import hashlib

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from benchmarks.harness import sighashref, sigopref
from bitcoinconsensus_tpu import native_bridge as NB
from bitcoinconsensus_tpu.core.sighash import legacy_sighash
from bitcoinconsensus_tpu.core.tx import OutPoint, Tx, TxIn, TxOut

pytestmark = pytest.mark.skipif(
    not NB.available(), reason="native library unavailable (no compiler?)"
)

ALL, NONE, SINGLE, ACP = 0x01, 0x02, 0x03, 0x80
OP_CODESEPARATOR, OP_CHECKSIG = b"\xab", b"\xac"
KEY = b"\x02" + hashlib.sha256(b"legacy-template/key").digest()
CHECKSIG = bytes([len(KEY)]) + KEY + OP_CHECKSIG
# name -> (the coin's script, the script code its CHECKSIG hashes before the
# serialiser leaves the OP_CODESEPARATORs out)
SCRIPTS = {
    "plain": (CHECKSIG, CHECKSIG),
    # one separator executed, one still ahead in the script code
    "codeseparator": (OP_CODESEPARATOR + CHECKSIG + OP_CODESEPARATOR,
                      CHECKSIG + OP_CODESEPARATOR),
}
SALT = b"legacy-template/salt"
GRID = 4096  # native/interp.hpp LegacyTemplate::GRID


def _grid_point(n_inputs: int, index: int) -> int:
    """The bytes a digest of input `index` does not hash again: the last
    multiple of GRID at or before the end of the prefix it shares with the
    transaction's other digests (version, the input count, the inputs before
    it blanked, its own prevout), 0 where the prefix reaches none."""
    header = 4 + (1 if n_inputs < 253 else 3)
    return (header + 41 * index + 36) // GRID * GRID


def _script_sig(hash_type: int) -> bytes:
    """A push of a signature whose last byte is `hash_type`: with no flag
    set nothing reads the rest before the curve does."""
    sig = b"\x30\x06\x02\x01\x01\x02\x01\x01" + bytes([hash_type])
    return bytes([len(sig)]) + sig


@functools.lru_cache(maxsize=None)
def _tx(n_in: int, n_out: int, hash_types: tuple) -> Tx:
    """`n_in` inputs, input i signing under `hash_types[i % len]`, no two
    fields alike so that a span laid one byte off hashes differently."""
    vin = [TxIn(OutPoint(hashlib.sha256(b"legacy-template/%d" % i).digest(), 7 * i + 1),
                _script_sig(hash_types[i % len(hash_types)]), 0xFFFF0000 + i)
           for i in range(n_in)]
    vout = [TxOut(50_000 + i, b"\x76\xa9\x14" + hashlib.sha256(b"%d" % i).digest()[:20] + b"\x88\xac")
            for i in range(n_out)]
    return Tx(version=2, vin=vin, vout=vout, locktime=400_000)


def _native(tx: Tx) -> NB.NativeTx:
    ntx = NB.NativeTx(tx.serialize())
    ntx.precompute()
    return ntx


def _digest(sess: NB.NativeSession, ntx: NB.NativeTx, n_in: int, spk: bytes) -> bytes:
    """The digest the native interpreter hashes for input `n_in`'s CHECKSIG."""
    ok, err, unknown = sess.verify_input(ntx, n_in, 0, spk, 0)
    assert (ok, err, unknown) == (True, 0, 1)
    ((kind, (key, _sig, msg)),) = sess.take_records()
    assert (kind, key) == ("ecdsa", KEY)
    return msg


# -- every hash-type byte, transaction size, signing input and script code -----

SIZES = (1, 2, 17, 300)
HASH_TYPES = (0x01, 0x02, 0x03, 0x81, 0x82, 0x83, 0x00, 0x04, 0xFF)
POSITIONS = ("first", "middle", "last", "past-the-outputs")
ONE = b"\x01" + b"\x00" * 31


@pytest.mark.parametrize("script", list(SCRIPTS))
@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("hash_type", HASH_TYPES, ids=[f"{h:#04x}" for h in HASH_TYPES])
@pytest.mark.parametrize("n_in", SIZES)
def test_the_native_digest_is_the_specs(n_in, hash_type, position, script):
    # more outputs than inputs, so that SIGHASH_SINGLE blanks up to 299 of
    # them, or so few that the last input signs past them
    past = position == "past-the-outputs"
    tx = _tx(n_in, n_in // 2 if past else n_in + 2, (hash_type,))
    index = {"first": 0, "middle": n_in // 2}.get(position, n_in - 1)
    spk, code = SCRIPTS[script]
    sess = NB.NativeSession()
    got = _digest(sess, _native(tx), index, spk)
    assert got == legacy_sighash(code, tx, index, hash_type)
    one = hash_type & 0x1F == SINGLE and past
    assert (got == ONE) == one
    # what the digest hashed, and what it hashed it from
    serialized_code = len(code) - code.count(OP_CODESEPARATOR)
    n_bytes, seconds = sess.sighash_work()["legacy"]
    assert (n_bytes == 0) == one and (seconds > 0 or one)
    templated = not one and not hash_type & ACP
    point = _grid_point(n_in, index) if templated else 0
    assert (point > 0) == (templated and n_in == 300 and index >= 150)
    if not one and hash_type & 0x1F not in (NONE, SINGLE) and not hash_type & ACP:
        blanked = tx.serialize()  # every script a one-byte string of 10 bytes
        assert n_bytes == len(blanked) - 10 * n_in + serialized_code + 4 - point
    assert sess.sighash_templates() == {"built": int(templated), "served": int(templated),
                                        "resumed": int(point > 0)}


# -- use never changes a template ------------------------------------------------

MIXED = (ALL, NONE, SINGLE, ALL | ACP, 0x00, SINGLE | ACP)  # by input index mod 6
TARGETS = {"all": 6, "none": 7, "single": 8, "all-anyonecanpay": 9, "undefined": 10}
BEFORE = {
    "after-a-later-input": (204,),
    "after-a-none-digest": (7 + 6 * 30,),
    "after-a-single-digest": (8 + 6 * 30,),
    "after-all-three-and-itself": (1, 2, 0, 204, 6, 7, 8, 9, 10),
}


@pytest.mark.parametrize("before", list(BEFORE))
@pytest.mark.parametrize("target", list(TARGETS))
def test_a_digest_does_not_depend_on_the_digests_asked_before_it(target, before):
    tx = _tx(300, 302, MIXED)
    index = TARGETS[target]
    spk, code = SCRIPTS["plain"]
    want = legacy_sighash(code, tx, index, MIXED[index % 6])
    assert _digest(NB.NativeSession(), _native(tx), index, spk) == want  # a fresh transaction's
    sess, ntx = NB.NativeSession(), _native(tx)
    for i in BEFORE[before]:
        assert _digest(sess, ntx, i, spk) == legacy_sighash(code, tx, i, MIXED[i % 6])
    assert _digest(sess, ntx, index, spk) == want
    served = [i for i in BEFORE[before] + (index,) if not MIXED[i % 6] & ACP]
    kinds = {MIXED[i % 6] & 0x1F in (NONE, SINGLE) for i in served}
    resumed = [i for i in served if _grid_point(300, i)]
    assert set(resumed) == {i for i in served if i > 100}
    assert sess.sighash_templates() == {"built": len(kinds), "served": len(served),
                                        "resumed": len(resumed)}
    ntx.precompute()  # clears the template as it clears the BIP 143 aggregates
    assert _digest(sess, ntx, index, spk) == want
    assert sess.sighash_templates()["built"] == len(kinds) + (MIXED[index % 6] & ACP == 0)


# -- thirteen workers, one transaction -----------------------------------------------

N_CONCURRENT, THREADS, RUNS = 600, 13, 20


def _idx_run(tx: Tx, n_threads: int):
    """One index-mode call over every input of a fresh parse of `tx`:
    verdicts, the salted digest of every recorded check (key, signature and
    message digest), and the session's template counts."""
    n = len(tx.vin)
    sess, ntx = NB.NativeSession(), _native(tx)
    ok, err, unknown, rec_idx, bounds = sess.verify_inputs_idx(
        [ntx] * n, list(range(n)), [0] * n, [SCRIPTS["plain"][0]] * n, [0] * n,
        n_threads=n_threads)
    assert bounds.tolist() == list(range(n + 1))  # one check an input, in input order
    keys = sess.uniq_digests(SALT, rec_idx).tobytes()
    return (ok.tolist(), err.tolist(), unknown.tolist(), keys), sess.sighash_templates()


@pytest.fixture(scope="module")
def one_thread():
    """Per hash-type mix: the transaction, what one thread reads of it, and
    the spec's digests as the salted keys of the checks they make."""
    made = {}

    def get(hash_types: tuple):
        if hash_types not in made:
            tx = _tx(N_CONCURRENT, N_CONCURRENT + 2, hash_types)
            code = SCRIPTS["plain"][1]
            checks = [("ecdsa", (KEY, tx.vin[i].script_sig[1:-1],
                                 legacy_sighash(code, tx, i, hash_types[i % len(hash_types)])))
                      for i in range(N_CONCURRENT)]
            got, templates = _idx_run(tx, 1)
            assert got[3] == b"".join(NB.digest_checks(SALT, checks))
            made[hash_types] = tx, got, templates
        return made[hash_types]

    return get


@pytest.mark.parametrize("run", range(RUNS))
def test_thirteen_workers_build_one_template_and_hash_the_same_digests(one_thread, run):
    tx, want, templates = one_thread((ALL,))
    counts = {"built": 1, "served": N_CONCURRENT, "resumed": N_CONCURRENT - 99}
    assert templates == counts
    got, templates = _idx_run(tx, THREADS)
    assert got == want
    assert templates == counts


@pytest.mark.parametrize("run", range(5))
def test_thirteen_workers_build_the_zeroed_template_once_too(one_thread, run):
    mix = (ALL, NONE, SINGLE, NONE | ACP)
    tx, want, templates = one_thread(mix)
    counts = {"built": 2, "served": N_CONCURRENT * 3 // 4,
              "resumed": sum(i % 4 != 3 for i in range(99, N_CONCURRENT))}
    assert templates == counts
    got, templates = _idx_run(tx, THREADS)
    assert got == want
    assert templates == counts


def test_a_shared_transaction_is_templated_once_across_two_sessions():
    """The template is the transaction's, not a session's: a second session
    on the same parse is served from what the first laid down."""
    tx = _tx(17, 19, (ALL,))
    ntx, spk = _native(tx), SCRIPTS["plain"][0]
    first, second = NB.NativeSession(), NB.NativeSession()
    assert _digest(first, ntx, 3, spk) == _digest(second, ntx, 3, spk)
    assert first.sighash_templates() == {"built": 1, "served": 1, "resumed": 0}
    assert second.sighash_templates() == {"built": 0, "served": 1, "resumed": 0}


# -- a digest resumed from the template's grid of SHA-256 states ---------------------

N_LONG = 400  # the prefix of input 99 is the first to reach GRID bytes
BASE_TYPES = (ALL, NONE, SINGLE, ALL | ACP, NONE | ACP, SINGLE | ACP)


def _reference(tx: Tx, code: bytes, index: int, hash_type: int):
    """(digest, bytes hashed for it) by the plain reference, which builds
    every preimage whole; the digest is `core/sighash.py`'s too."""
    digest, size = sighashref.signature_hash(
        sigopref.parse_tx(tx.serialize()), index, code, hash_type)
    assert digest == legacy_sighash(code, tx, index, hash_type)
    return digest, size


@pytest.mark.parametrize("outputs", ["an-output-an-input", "single-past-the-outputs"])
@pytest.mark.parametrize("hash_type", BASE_TYPES, ids=[f"{h:#04x}" for h in BASE_TYPES])
def test_every_input_of_a_long_transaction_resumes_to_the_references_digest(hash_type, outputs):
    n_out = N_LONG + 2 if outputs == "an-output-an-input" else N_LONG // 2
    tx = _tx(N_LONG, n_out, (hash_type,))
    spk, code = SCRIPTS["plain"]
    ref = sigopref.parse_tx(tx.serialize())
    want = [sighashref.signature_hash(ref, i, code, hash_type) for i in range(N_LONG)]
    for i in (0, 98, 99, N_LONG // 2 - 1, N_LONG // 2, N_LONG - 1):
        assert want[i][0] == legacy_sighash(code, tx, i, hash_type)
    sess, ntx = NB.NativeSession(), _native(tx)
    _ok, _err, unknown, rec_idx, bounds = sess.verify_inputs_idx(
        [ntx] * N_LONG, list(range(N_LONG)), [0] * N_LONG, [spk] * N_LONG, [0] * N_LONG,
        n_threads=1)
    assert unknown.tolist() == [1] * N_LONG and bounds.tolist() == list(range(N_LONG + 1))
    checks = [("ecdsa", (KEY, tx.vin[i].script_sig[1:-1], want[i][0])) for i in range(N_LONG)]
    assert sess.uniq_digests(SALT, rec_idx).tobytes() == b"".join(NB.digest_checks(SALT, checks))
    # which digests started from a grid point, and what the others and they were fed
    ones = [i for i in range(N_LONG) if want[i][1] == 0]
    assert ones == (list(range(n_out, N_LONG)) if hash_type & 0x1F == SINGLE and n_out < N_LONG else [])
    served = [] if hash_type & ACP else [i for i in range(N_LONG) if i not in ones]
    resumed = [i for i in served if _grid_point(N_LONG, i)]
    assert resumed == [i for i in served if i >= 99]
    assert sess.sighash_templates() == {"built": int(bool(served)), "served": len(served),
                                        "resumed": len(resumed)}
    fed, _seconds = sess.sighash_work()["legacy"]
    reference = sum(size for _digest, size in want)
    assert fed == reference - sum(_grid_point(N_LONG, i) for i in resumed)
    assert (fed < reference) == bool(resumed)


# The prefix of input i ends at header + 41 i + 36 bytes of the stream; with a
# three-byte input count (header 7) that is one byte short of, on, and one
# byte past a multiple of GRID at these inputs. In a transaction of 1,998
# inputs the last one's prefix ends on the table's last state.
EDGES = {"one-byte-before": (2997, 2996, 30 * GRID - 1), "on": (2997, 1997, 20 * GRID),
         "one-byte-after": (2997, 998, 10 * GRID + 1), "on-the-tables-last-state": (1998, 1997, 20 * GRID),
         # a one-byte input count (header 5): the first input to resume, and its neighbour that does not
         "one-byte-count-first-resumed": (252, 99, GRID + 4), "one-byte-count-last-whole": (252, 98, GRID - 37),
         "three-byte-count-first-resumed": (253, 99, GRID + 6), "three-byte-count-last-whole": (253, 98, GRID - 35)}
EDGE_TYPES = (ALL, NONE, SINGLE)


@pytest.mark.parametrize("hash_type", EDGE_TYPES, ids=[f"{h:#04x}" for h in EDGE_TYPES])
@pytest.mark.parametrize("edge", list(EDGES))
def test_a_digest_at_the_grids_edge_is_the_references(edge, hash_type):
    n_in, index, prefix_end = EDGES[edge]
    assert 4 + (1 if n_in < 253 else 3) + 41 * index + 36 == prefix_end
    tx = _tx(n_in, n_in + 2, (hash_type,))
    spk, code = SCRIPTS["plain"]
    want, size = _reference(tx, code, index, hash_type)
    sess = NB.NativeSession()
    assert _digest(sess, _native(tx), index, spk) == want
    point = prefix_end // GRID * GRID
    assert point == _grid_point(n_in, index) and prefix_end - point in (0, 1, 4, 6, GRID - 1, GRID - 35, GRID - 37)
    assert sess.sighash_work()["legacy"][0] == size - point
    assert sess.sighash_templates() == {"built": 1, "served": 1, "resumed": int(point > 0)}
