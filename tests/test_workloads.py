"""Adversarial workload gauntlet: corpus pins, replay, differential fuzz.

Covers the three legs of `bitcoinconsensus_tpu.workloads`:

- every corpus entry's pinned verdict on every available engine, plus
  the reference-`.so` differential (agreement under masked libconsensus
  flags) when the reference build is present;
- the negative proof: a PLANTED wrong-verdict corpus entry must fail
  the gauntlet — the pin check is fail-closed, not advisory;
- replay-stream determinism, oracle bit-identity and mempool→block
  cache warm-up;
- diff-fuzz zero-divergence on a smoke seed, and the negative proof
  that a lying engine is caught.

The native-engine comparisons skip cleanly when the native bridge is
unavailable; the reference differential skips cleanly without the
reference checkout (same pattern as tests/test_differential.py).
"""

import dataclasses

import pytest

from conftest import *  # noqa: F401,F403 (env setup)

from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.flags import LIBCONSENSUS_FLAGS
from bitcoinconsensus_tpu.utils.refbridge import load_reference_lib
from bitcoinconsensus_tpu.workloads import (
    ReplayConfig,
    build_corpus,
    generate_stream,
    run_diff_fuzz,
    run_replay,
    run_replay_serving,
)
from bitcoinconsensus_tpu.workloads import diff_fuzz as df
from bitcoinconsensus_tpu.workloads.corpus import run_corpus_check, shape_batch

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls

REF = load_reference_lib()


@pytest.fixture(autouse=True)
def default_verifier_on_a_warm_rung(monkeypatch):
    """The batch engine's default verifier cuts a batch into 16-lane
    dispatches of the real kernel, a rung `warm_kernel` has made: the stock
    one would compile a 128-lane program for the corpus (`conftest.py`
    holds a test to the warm rungs)."""
    from bitcoinconsensus_tpu.crypto import jax_backend

    monkeypatch.setattr(jax_backend, "_default", jax_backend.TpuSecpVerifier(chunk=16))


# ---------------------------------------------------------------- corpus


def test_corpus_pins_hold_on_every_engine():
    """Every adversarial entry reproduces its pinned (ok, Error,
    ScriptError) triple on the python, batch/device and (when built)
    native engines — one gauntlet sweep, zero mismatches."""
    rep = run_corpus_check()
    assert rep["pinned"], rep["mismatches"]
    assert rep["cases"] >= 17
    assert rep["native_available"] == native_bridge.available()


@pytest.mark.skipif(
    REF is None, reason="reference lib not built (scripts/build_reference.sh)"
)
def test_corpus_reference_so_differential():
    """Corpus entries through the reference .so under masked
    libconsensus flags: agreement (not the pin — the mask can change the
    expectation) is the invariant, as in test_differential.py."""
    from bitcoinconsensus_tpu import api
    from bitcoinconsensus_tpu.api import ConsensusError, Error

    checked = 0
    for case in build_corpus():
        item = case.item
        flags = item.flags & LIBCONSENSUS_FLAGS
        idx = item.input_index
        amount, spk = item.spent_outputs[idx]
        try:
            api.verify_with_flags(spk, amount, item.spending_tx, idx, flags)
            ours = (True, 0)
        except ConsensusError as e:
            ours = (False, 0 if e.code == Error.ERR_SCRIPT else int(e.code))
        want = REF.verify_with_flags(
            spk, amount, item.spending_tx, idx, flags
        )
        assert ours == want, (
            f"{case.name}: ours={ours} ref={want} flags={flags:#x}"
        )
        checked += 1
    assert checked >= 17


def test_planted_wrong_pin_fails_gauntlet():
    """Fail-closed proof: flip one entry's pinned verdict and the
    gauntlet must report exactly that mismatch."""
    corpus = build_corpus()
    victim = corpus[0]
    corpus[0] = dataclasses.replace(victim, expect_ok=not victim.expect_ok)
    rep = run_corpus_check(corpus=corpus)
    assert not rep["pinned"]
    assert any(m["case"] == victim.name for m in rep["mismatches"])


def test_shape_batches_are_valid_and_deterministic():
    from bitcoinconsensus_tpu.workloads.corpus import SHAPES

    for shape in ("multisig_fanout", "quadratic_sighash",
                  "max_size_script", "taproot_annex"):
        a = shape_batch(shape, 3, seed=0)
        b = shape_batch(shape, 3, seed=0)
        assert [x.spending_tx for x in a] == [x.spending_tx for x in b]
        assert all(df.python_verdict(it)[0] for it in a), shape
    assert set(DEFAULTED := ("sig_malleation", "boundary_flags")) <= set(SHAPES)
    for shape in DEFAULTED:
        with pytest.raises(ValueError):
            shape_batch(shape, 2)


# ---------------------------------------------------------------- replay


def test_replay_stream_deterministic():
    cfg = ReplayConfig(seed=3, n_blocks=2, txs_per_block=3)
    a, b = generate_stream(cfg), generate_stream(cfg)
    flat = lambda blocks: [  # noqa: E731
        (it.spending_tx, it.input_index, it.flags)
        for blk in blocks for it in blk.block_items
    ]
    assert flat(a) == flat(b)
    c = generate_stream(ReplayConfig(seed=4, n_blocks=2, txs_per_block=3))
    assert flat(a) != flat(c)


def test_replay_bit_identical_and_cache_warm():
    # Tier-1-sized stream; the CI gauntlet job replays larger configs
    # (scripts/consensus_gauntlet.py / consensus_chaos.py --gauntlet).
    # seed 3 keeps a non-empty valid mempool→block overlap at this size
    # (seed 2's two blocks happen to draw zero warmable items).
    rep = run_replay(
        ReplayConfig(seed=3, n_blocks=2, txs_per_block=2, max_inputs=2)
    )
    assert rep["bit_identical"], rep["divergences"]
    assert rep["warmed"], rep
    assert rep["script_cache_hits"] >= rep["expected_warm_hits"] > 0


@pytest.mark.slow
def test_replay_serving_overload_sheds_explicitly():
    rep = run_replay_serving(
        ReplayConfig(seed=9, n_blocks=2, txs_per_block=2),
        mode="serve", overload=True,
    )
    assert rep["bit_identical"], rep["divergences"]
    assert rep["all_accounted"], rep["errors"]
    assert rep["sheds_happened"] and rep["sheds_explicit_only"]


# -------------------------------------------------------------- diff-fuzz


def test_diff_fuzz_smoke_zero_divergence():
    rep = run_diff_fuzz(seed=1, n_cases=12)
    assert rep["bit_identical"], rep["divergences"]
    assert rep["cases"] == 12
    assert rep["engines"] == (3 if native_bridge.available() else 2)


def test_diff_fuzz_deterministic_mutants():
    import random

    base = build_corpus()[0].item
    a = df.mutate(base, random.Random(5))
    b = df.mutate(base, random.Random(5))
    assert a[1] == b[1] and a[0].spending_tx == b[0].spending_tx


def test_diff_fuzz_catches_lying_engine(monkeypatch):
    """Fail-closed proof: an engine that blindly ACCEPTs everything must
    produce divergences against the others (mutants include guaranteed
    rejections)."""
    monkeypatch.setattr(
        df, "python_verdict", lambda item: (True, "ERR_OK", None)
    )
    rep = run_diff_fuzz(seed=1, n_cases=12)
    assert not rep["bit_identical"]
    assert rep["divergences"]


def test_fuzz_seed_file_is_wired():
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "fuzz", "gauntlet_seeds.json",
    )
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["seeds"] and all(isinstance(s, int) for s in doc["seeds"])
