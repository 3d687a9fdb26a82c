"""The program's side of the benchmark's contract.

`benchmarks/` reads the program through names: metrics of the registry
(`consensus_*`) and phases of `verifier.phases`. A per-layer metric whose
source was renamed reads `null` in the ledger, a chip guard that reads a
renamed counter guards nothing, and neither fails a test of the program.
This file makes such a rename fail tier-1 first: one small workload over
the paths the cells drive (a native connect on fresh and on warm caches, a
two-block stream that ends in a rollback, a multisig connect of two chunks
against a queue one deep, the same connect through the mesh verifier's
layout and per-shard settle, a script-path taproot spend, a wire-driver
dispatch, a served request), then a case a name.

It reads `benchmarks/` (the literal lists below must be what its files
name) and imports nothing from it.
"""

import os
import re

import numpy as np
import pytest

from conftest import *  # noqa: F401,F403 (env setup)

import __graft_entry__ as ge
from bitcoinconsensus_tpu import native_bridge
from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_EXTENDED, VERIFY_ALL_LIBCONSENSUS
from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache, SigCache
from bitcoinconsensus_tpu.models.validate import (
    connect_block,
    connect_block_stream,
    disconnect_block,
)
from bitcoinconsensus_tpu.obs import get_registry
from bitcoinconsensus_tpu.obs import spans as S
from bitcoinconsensus_tpu.parallel.mesh import ShardedSecpVerifier, make_mesh
from bitcoinconsensus_tpu.serving import IngressClient, IngressServer, VerifyServer
from bitcoinconsensus_tpu.utils.blockgen import (
    REGTEST_POW_LIMIT,
    build_block,
    build_spend_tx,
    make_funded_view,
)

from mesh_stub import host_step
from packed_stub import xla_lane_verdicts
from test_batch import make_p2tr_scriptpath_spend, make_p2wpkh_spend
from test_native_block import HEIGHT, to_native_view

pytestmark = [
    pytest.mark.skipif(
        not native_bridge.available(), reason="native core unavailable"
    ),
    pytest.mark.usefixtures("warm_kernel"),  # conftest.py: first calls
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What the per-layer readers and the drivers take from the registry: each
# must have a sample after the workload, except the shed counter, which a
# run that sheds nothing never bumps.
READ = (
    "consensus_blocks_disconnected_total",
    "consensus_cache_hits_total",
    "consensus_cache_lookups_total",
    "consensus_checks_total",
    "consensus_coin_probes_total",
    "consensus_compile_seconds_total",
    "consensus_dispatch_lanes_total",
    "consensus_dispatch_new_shapes_total",
    "consensus_dispatch_padded_lanes_total",
    "consensus_dispatch_total",
    "consensus_dispatch_transfers_total",
    "consensus_fan_out_seconds_total",
    "consensus_fixpoint_reinterpreted_inputs_total",
    "consensus_ingress_seconds",
    "consensus_mesh_dispatch_total",
    "consensus_mesh_shard_lanes",
    "consensus_multisig_spec_pairings_total",
    "consensus_multisig_walk_pairings_total",
    "consensus_native_stage_seconds_total",
    "consensus_serving_admitted_total",
    "consensus_serving_batch_fill",
    "consensus_serving_batch_seconds",
    "consensus_serving_batches_total",
    "consensus_serving_queue_wait_seconds",
    "consensus_serving_shed_total",
    "consensus_sighash_bytes_total",
    "consensus_sighash_seconds_total",
    "consensus_sighash_total",
    "consensus_span_duration_seconds",
    "consensus_stream_blocks_in_flight",
    "consensus_stream_rollbacks_total",
    "consensus_taproot_hash_total",
    "consensus_undo_coins_total",
)
# What the benchmark's chip guard holds at zero (`harness/chipguard.py`):
# registered is all a sound run shows of them.
ZERO = (
    "consensus_backend_config_errors_total",
    "consensus_exact_fallback_total",
    "consensus_host_fixup_total",
    "consensus_inflight_deadline_expired_total",
    "consensus_inflight_failures_total",
    "consensus_resilience_contained_total",
    "consensus_resilience_demotions_total",
    "consensus_resilience_guard_anomalies_total",
    "consensus_resilience_host_exact_lanes_total",
    "consensus_resilience_retries_total",
)
# What the four-chip cell's driver holds still (`drivers/connect_mesh.py`).
MESH_STILL = (
    "consensus_mesh_evictions_total",
    "consensus_mesh_redispatch_lanes_total",
    "consensus_mesh_repromotions_total",
    "consensus_mesh_shard_failures_total",
    "consensus_mesh_verdict_mismatch_total",
)
# What only a Pallas dispatch raises (`layers/full_tile_share.connect.py`): a
# CPU run registers it and, launching no Pallas program, never bumps it;
# `test_tile_rows_are_the_ones_read` stands in for the launch.
CHIP_ONLY = ("consensus_dispatch_tiles_total",)
NO_SAMPLE_NEEDED = ZERO + MESH_STILL + CHIP_ONLY + ("consensus_serving_shed_total",)
# `PERF.md` section 3 names it with no reader under `benchmarks/` yet (PR 35):
# the pieces a mesh dispatch crosses the host-device seam in, by direction.
NO_READER_YET = (
    "consensus_mesh_transfers_total",
    # PR 46: a transaction's blanked legacy template, built and served
    # (PR 47: and resumed from its grid of SHA-256 states)
    "consensus_sighash_template_total",
    # PR 49: the keys a bulk cache call walked, by where the set lives
    "consensus_cache_bulk_keys_total",
)
# `PERF.md` section 6 reads it beside `compile_s.setup`, not as a metric: the
# persistent compile cache's hits and the misses it wrote an entry for.
# Registered is all a process that found every program in the cache shows.
PERF_MD_ONLY = ("consensus_compile_cache_total",)
# `PERF.md` section 3: the stretches of a native connect that
# `verifier.phases` names, every one read through `detail.phase_ms_p50`.
PHASES = (
    "interpret", "host_prep", "pack", "dispatch", "sync", "parse",
    "block_check", "accounting", "probe", "results", "apply", "undo",
    "publish", "release", "backpressure",
    # PR 36, siblings of the above: with them the phases tile a connect
    # (`unphased_ms.connect`, `sig_cache_ms.connect`, `teardown_ms.connect`)
    "sig_probe", "sig_insert", "block_free", "session_setup", "accept",
    "gc_sweep",
    # on the batch path (a served batch): every item parsed and prepared
    "prepare",
    # lanes the device would not answer for, resolved on the exact oracle
    "host_fixup",
    # under the mesh verifier alone, inside `dispatch` and `sync`
    "shard_layout", "shard_put", "shard_exec", "shard_check",
    # PR 48, a `disconnect_block`: the record held against the block, before
    # `undo` (a stream's rollback has that one too) moves the coins
    "undo_check",
)
# The spans whose seconds the served cell's readers take from
# `consensus_span_duration_seconds{span}` (`layers/_spans.py`).
SPANS = (
    "serving.take", "serving.idle", "batch.stream_begin",
    "batch.stream_finish", "verifier.sync",
)


def _block(seed: str, height: int, n: int = 6, corrupt=None, kind="p2wpkh"):
    """A raw block of one transaction that spends `n` fresh coins of `kind`
    (P2WPKH: at most 7 curve checks, the 8-lane rung), and the coins that
    fund it."""
    coins, funded = make_funded_view(n, kinds=(kind,), seed=seed)
    tx = build_spend_tx(funded, fee=1000, corrupt_input=corrupt)
    return build_block([tx], height, fees=1000).serialize(), coins


@pytest.fixture(scope="module")
def workload():
    verifier = TpuSecpVerifier()
    connect = dict(pow_limit=REGTEST_POW_LIMIT, verifier=verifier)

    # a native connect on fresh caches, and the same block on warm ones
    raw, coins = _block("contract/tip", HEIGHT)
    sig, script = SigCache(), ScriptExecutionCache()
    for _ in range(2):
        res = connect_block(raw, to_native_view(coins), HEIGHT,
                            sig_cache=sig, script_cache=script, **connect)
        assert res.ok and len(res.input_results) == 6
    # and on a warm signature cache alone: interpreted again, every check
    # found by the probe (`sig_probe`), nothing launched
    res = connect_block(raw, to_native_view(coins), HEIGHT, sig_cache=sig,
                        script_cache=ScriptExecutionCache(), **connect)
    assert res.ok and len(res.input_results) == 6

    # the block connected with its record and taken off the tip again
    view = to_native_view(coins)
    res = connect_block(raw, view, HEIGHT, sig_cache=SigCache(),
                        script_cache=ScriptExecutionCache(), want_undo=True, **connect)
    assert disconnect_block(raw, view, res.undo, HEIGHT, verifier=verifier).ok

    # a verifier that will not answer for any lane: the driver resolves
    # every one on the exact host oracle (`host_fixup`)
    unsure = TpuSecpVerifier()
    unsure.phases, settle = verifier.phases, unsure.sync_lanes

    def no_answer(pending, n):
        settle(pending, n)
        return np.zeros(n, dtype=bool), np.ones(n, dtype=bool)

    unsure.sync_lanes = no_answer
    res = connect_block(raw, to_native_view(coins), HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                        verifier=unsure, sig_cache=SigCache(),
                        script_cache=ScriptExecutionCache())
    assert res.ok and len(res.input_results) == 6

    # a stream whose second block has one flipped signature: it is begun
    # on a speculative view, rejected where it is finished, and undone
    good, coins_a = _block("contract/a", HEIGHT)
    bad, coins_b = _block("contract/b", HEIGHT + 1, corrupt=2)
    coins_a._map.update(coins_b._map)
    results = list(connect_block_stream(
        [good, bad], to_native_view(coins_a), HEIGHT, depth=2,
        sig_cache=SigCache(), script_cache=ScriptExecutionCache(), **connect))
    assert [r.ok for r in results] == [True, False]

    # three 2-of-3 spends, 12 pre-recorded pairings in two chunks of the
    # 8-lane rung against a queue one deep: the second chunk waits for the
    # first (`backpressure`), and every input's first guess is wrong, so a
    # second round interprets all three again and launches nothing
    small = TpuSecpVerifier(chunk=8)
    small.phases, small._inflight.max_depth = verifier.phases, 1
    raw, coins = _block("contract/multisig", HEIGHT, n=3, kind="p2wsh_multisig")
    res = connect_block(raw, to_native_view(coins), HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                        verifier=small, sig_cache=SigCache(),
                        script_cache=ScriptExecutionCache())
    assert res.ok and len(res.input_results) == 3

    # the same block through the mesh verifier, four of the CPU devices wide:
    # 12 lanes laid out three a shard beside a sentinel each, settled shard
    # by shard. The sharded step is stood in for by the verifier's own
    # one-device kernel (the 16-lane rung `warm_kernel` made) over the whole
    # buffer (the program's compile belongs to `tests/mesh_checks.py`'s
    # children); what the benchmark reads of the mesh is all on the host
    # side of it
    sharded = ShardedSecpVerifier(mesh=make_mesh(4), min_batch=16, chunk=16)
    sharded.phases, sharded._step = verifier.phases, host_step(sharded, xla_lane_verdicts)
    res = connect_block(raw, to_native_view(coins), HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                        verifier=sharded, sig_cache=SigCache(),
                        script_cache=ScriptExecutionCache())
    assert res.ok and len(res.input_results) == 3

    # a script-path taproot spend on the index path: a Schnorr lane and a
    # tweak lane, a BIP 341 digest and the commitment's tagged hashes
    txb, spk, amount = make_p2tr_scriptpath_spend("contract/leaf")
    leaf = BatchItem(txb, 0, VERIFY_ALL_EXTENDED, spent_outputs=[(amount, spk)])
    assert verify_batch([leaf], verifier, SigCache(), ScriptExecutionCache())[0].ok

    # the wire driver's lane prep, on the interpreter that has no native
    # `prep_pack`: the one place `pack` is a phase of its own
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(verifier, "_native", None)
        assert verifier.verify_checks(ge._example_checks(7)).all()

    # one request through a server, and one through the socket in front of it
    txb, spk, amount = make_p2wpkh_spend("contract/serve")
    item = BatchItem(txb, 0, VERIFY_ALL_LIBCONSENSUS,
                     spent_output_script=spk, amount=amount)
    with VerifyServer(verifier=verifier, max_batch=4, flush_s=0.005,
                      tenant_depth=8) as srv:
        assert srv.verify(item, tenant="contract", timeout=120).ok
        with IngressServer(srv, idle_s=10.0) as ing:
            with IngressClient(port=ing.port) as cli:
                assert cli.verify(item, tenant="contract").ok

    return verifier.phases.report(), get_registry().snapshot()


@pytest.mark.parametrize(
    "name", READ + ZERO + MESH_STILL + CHIP_ONLY + NO_READER_YET + PERF_MD_ONLY + PHASES + SPANS)
def test_the_benchmark_finds(workload, name):
    phases, snapshot = workload
    if name in PHASES:
        assert phases.get(name, {}).get("calls", 0) > 0, sorted(phases)
        assert 0.0 <= phases[name]["outer_secs"] <= phases[name]["secs"]
        return
    if name in SPANS:
        timed = {s["labels"]["span"]: s["count"]
                 for s in snapshot["consensus_span_duration_seconds"]["samples"]}
        assert timed.get(name, 0) > 0, sorted(timed)
        return
    assert name in snapshot, f"{name} is not registered"
    if name not in NO_SAMPLE_NEEDED + PERF_MD_ONLY:
        assert snapshot[name]["samples"], f"{name} took no sample"


def test_ingress_stages_and_compile_stages_are_the_ones_read(workload):
    """The label values the readers ask for (`layers/ingress_ms.serve.py`,
    `layers/_setup.py`): a renamed stage reads None on the chip, not here."""
    _, snapshot = workload
    stages = {s["labels"]["stage"]: s["count"]
              for s in snapshot["consensus_ingress_seconds"]["samples"]}
    assert stages.get("decode", 0) > 0 and stages.get("respond", 0) > 0, stages
    compiled = {s["labels"]["stage"]: s["value"]
                for s in snapshot["consensus_compile_seconds_total"]["samples"]}
    # `warm_kernel`'s first calls traced, lowered and compiled or loaded
    assert all(compiled.get(k, 0.0) > 0 for k in ("trace", "lower", "backend")), compiled


def test_sighash_results_are_the_ones_read(workload):
    """`layers/sighashes_per_input.connect.py` asks for `result="computed"`.
    The workload's 2-of-3 connects make both: a digest a signature hashed
    once a round, and read again by every pairing of the key walk."""
    _, snapshot = workload
    results = {s["labels"]["result"]: s["value"]
               for s in snapshot["consensus_sighash_total"]["samples"]}
    assert results.get("computed", 0) > 0 and results.get("reused", 0) > 0, results


def test_sighash_work_is_counted_by_kind(workload):
    """`layers/sighash_kb_per_input.connect.py` and
    `layers/sighash_mb_per_s.connect.py` sum
    `consensus_sighash_bytes_total` and `consensus_sighash_seconds_total`
    over their `kind` label. Every fixpoint raises both kinds; the
    workload's P2WPKH connects hash BIP 143 digests, and time them."""
    _, snapshot = workload
    for name in ("consensus_sighash_bytes_total", "consensus_sighash_seconds_total"):
        kinds = {s["labels"]["kind"]: s["value"] for s in snapshot[name]["samples"]}
        assert set(kinds) == {"legacy", "bip143"} and kinds["bip143"] > 0, (name, kinds)


def test_sighash_template_events_are_the_ones_named(workload):
    """`PERF.md` section 3 reads `consensus_sighash_template_total` by its
    `event` label. Every fixpoint raises the three events, by nothing where,
    as in the workload, every digest is BIP 143's or BIP 341's; a template is
    laid down to serve a digest, so `built` never passes `served`, and a
    digest resumes from a template that serves it."""
    _, snapshot = workload
    events = {s["labels"]["event"]: s["value"]
              for s in snapshot["consensus_sighash_template_total"]["samples"]}
    assert set(events) == {"built", "served", "resumed"}, events
    assert 0 <= events["built"] <= events["served"], events
    assert 0 <= events["resumed"] <= events["served"], events


# The label pairs of the native stage clock's two families (`layers/_stages.py`
# asks for them by `call` and by `stage` or `stat`; `README.md` "Observability").
STAGE_PAIRS = {
    ("interpret", "setup"), ("interpret", "workers"), ("interpret", "merge"),
    ("lanes", "order"), ("lanes", "shards"), ("digests", "shards"),
    ("accounting", "decide"), ("accounting", "fill"), ("accounting", "copy"),
}
FAN_CALLS = ("interpret", "lanes", "digests")
FAN_STATS = ("wall", "held", "sum", "max", "start_lag", "tail")


def _by_pair(snapshot, name, second):
    return {(s["labels"]["call"], s["labels"][second]): s["value"]
            for s in snapshot[name]["samples"]}


@pytest.mark.parametrize("call", FAN_CALLS)
def test_fan_out_seconds_are_a_sum_its_largest_part_and_what_held_them(workload, call):
    """`layers/_stages.py` reads `consensus_fan_out_seconds_total` by `call`
    and `stat`: what the fan-outs of the interpreter, of lane prep and of
    the digests say of themselves. Every fixpoint raises all six of each; no
    worker is busy longer than all of them together, nor they than the
    thread time the call held, and a fan-out's start lag and tail lie inside
    its wall. The workload's calls are too small to make a thread: one
    worker each, which starts at once."""
    _, snapshot = workload
    stats = {stat: v for (c, stat), v in
             _by_pair(snapshot, "consensus_fan_out_seconds_total", "stat").items() if c == call}
    assert set(stats) == set(FAN_STATS), stats
    assert 0 < stats["max"] <= stats["sum"] <= stats["held"] + 1e-12, stats
    assert stats["max"] <= stats["wall"] <= stats["held"], stats
    assert 0 <= stats["start_lag"] and 0 <= stats["tail"], stats
    assert stats["start_lag"] + stats["tail"] <= stats["wall"] + 1e-12, stats


def test_a_connect_raises_exactly_the_stage_and_fan_out_labels_named():
    """One native connect raises the nine (`call`, `stage`) pairs of
    `consensus_native_stage_seconds_total` and the eighteen (`call`, `stat`)
    pairs of `consensus_fan_out_seconds_total`, and no other: the session's
    at the fixpoint's end, the accounting's after the apply. The stages of a
    call lie inside the phase that holds the call."""
    verifier = TpuSecpVerifier()
    raw, coins = _block("contract/stages", HEIGHT)
    names = {"consensus_native_stage_seconds_total": "stage",
             "consensus_fan_out_seconds_total": "stat"}
    before = get_registry().snapshot()
    res = connect_block(raw, to_native_view(coins), HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                        verifier=verifier, sig_cache=SigCache(),
                        script_cache=ScriptExecutionCache())
    assert res.ok
    after = get_registry().snapshot()
    rose = {}
    for name, second in names.items():
        was = _by_pair(before, name, second) if name in before else {}
        rose[name] = {k: v - was.get(k, 0.0) for k, v in _by_pair(after, name, second).items()}
    stages, fans = rose["consensus_native_stage_seconds_total"], rose["consensus_fan_out_seconds_total"]
    assert set(stages) == STAGE_PAIRS
    assert set(fans) == {(c, s) for c in FAN_CALLS for s in FAN_STATS}
    assert all(v > 0 for k, v in stages.items()), stages
    phases = verifier.phases.report()
    of = lambda call: sum(v for (c, _), v in stages.items() if c == call)
    assert of("interpret") <= phases["interpret"]["secs"]
    assert of("lanes") + of("digests") <= phases["host_prep"]["secs"]
    assert of("accounting") <= phases["accounting"]["secs"]
    for call in FAN_CALLS:  # a fan-out runs inside the stage around it
        around = stages[call, "workers" if call == "interpret" else "shards"]
        assert 0 < fans[call, "wall"] <= around


def test_coin_probe_tables_are_the_ones_counted(workload):
    """`layers/_probes.py` sums `consensus_coin_probes_total` over its
    `table` label: the view, and pass 1's table of the block's own coins.
    Every native connect of the workload raises both, once, after its
    apply. `drivers/reorg.py` reads `table="undo"` apart: the view's probes
    by a `disconnect_block`, one a coin it moved (the workload's: six spent
    coins put back, the transaction's output and the coinbase's two taken
    out)."""
    _, snapshot = workload
    tables = {s["labels"]["table"]: s["value"]
              for s in snapshot["consensus_coin_probes_total"]["samples"]}
    assert set(tables) == {"view", "block", "undo"}, tables
    assert tables["view"] > tables["block"] > 0 and tables["undo"] == 9, tables


def test_disconnect_outcomes_and_moved_coins_are_the_ones_read(workload):
    """`drivers/reorg.py` differences `consensus_blocks_disconnected_total`
    by `result` and `consensus_undo_coins_total` by `what` around every timed
    reorganisation, and holds them to the generator's counts."""
    _, snapshot = workload
    ended = {s["labels"]["result"]: s["value"]
             for s in snapshot["consensus_blocks_disconnected_total"]["samples"]}
    assert ended == {"ok": 1}, ended
    moved = {s["labels"]["what"]: s["value"]
             for s in snapshot["consensus_undo_coins_total"]["samples"]}
    assert moved == {"restored": 6, "removed": 3}, moved
    timed = {s["labels"]["span"]: s["count"]
             for s in snapshot["consensus_span_duration_seconds"]["samples"]}
    assert timed.get("block.disconnect") == 1, sorted(timed)


def test_lane_kinds_and_taproot_hashes_are_the_ones_read(workload):
    """`layers/_lanes.py` asks `consensus_checks_total` for `kind="schnorr"`
    and `"tweak"` over all kinds, and `drivers/connect_taproot.py` reports
    every kind; `layers/taphashes_per_input.connect.py` sums every `what`.
    The workload's connects and its script-path spend feed both from the
    index path (`IdxFixpoint.finish`)."""
    _, snapshot = workload
    kinds = {s["labels"]["kind"]: s["value"]
             for s in snapshot["consensus_checks_total"]["samples"]}
    assert all(kinds.get(k, 0) > 0 for k in ("ecdsa", "schnorr", "tweak")), kinds
    hashes = {s["labels"]["what"]: s["value"]
              for s in snapshot["consensus_taproot_hash_total"]["samples"]}
    assert set(hashes) == {"sighash", "leaf", "branch", "tweak"}, hashes
    # a lone leaf under the internal key: no sibling, so no branch hash
    assert hashes["sighash"] > 0 and hashes["leaf"] > 0 and hashes["tweak"] > 0, hashes


def test_tile_rows_are_the_ones_read(monkeypatch):
    """`layers/full_tile_share.connect.py` asks `consensus_dispatch_tiles_total`
    for `rows="8"` over every `rows`: a grid step of the Pallas program a
    dispatch launches, by the sublane rows its tile fills. The 512-lane
    shape is one half-filled tile, a multiple of 1,024 lanes runs dense
    ones, a mesh dispatch counts every shard's steps, and an XLA rung
    counts nothing."""
    from types import SimpleNamespace

    import jax.numpy as jnp

    from bitcoinconsensus_tpu.crypto import jax_backend
    from bitcoinconsensus_tpu.crypto.lane_wire import ROW_BYTES

    def rows():
        samples = get_registry().snapshot()["consensus_dispatch_tiles_total"]["samples"]
        return {s["labels"]["rows"]: s["value"] for s in samples}

    def rose(before):
        return {k: v - before.get(k, 0) for k, v in rows().items() if v != before.get(k, 0)}

    def launch(verifier, lanes):
        verifier._run_packed(np.zeros((lanes, ROW_BYTES), np.uint8), lanes - 1)

    monkeypatch.setattr(jax_backend, "_packed_program", lambda backend: (
        lambda packed: jnp.zeros(len(packed) + 2, jnp.int32)))
    verifier = TpuSecpVerifier()
    before = rows()
    launch(verifier, 512)  # a CPU verifier: the XLA rung, whatever the shape
    assert rose(before) == {}
    verifier._use_pallas = True
    launch(verifier, 8)    # under the Pallas tile: the XLA rung
    assert rose(before) == {}
    launch(verifier, 512)
    assert rose(before) == {"4": 1}
    launch(verifier, 8192)
    launch(verifier, 1024)
    assert rose(before) == {"4": 1, "8": 9}
    sharded = ShardedSecpVerifier(mesh=make_mesh(4), min_batch=16, chunk=16)
    layout = SimpleNamespace(padded=8192, n=8188, n_shards=4, shard_size=2048)
    sharded._note_mesh_dispatch(layout)  # a CPU mesh: its shards run the XLA kernel
    assert rose(before) == {"4": 1, "8": 9}
    sharded._mesh_pallas = True
    sharded._note_mesh_dispatch(layout)  # four shards of two dense steps
    assert rose(before) == {"4": 1, "8": 17}


def test_mesh_phases_nest_and_outer_secs_do_not_count_them_twice(workload):
    """`shard_*` run inside `dispatch` and `sync` (or `backpressure`): their
    seconds are in `secs` twice over and in `outer_secs` once, so the sum of
    `outer_secs` stays under the sum of `secs` by at least the nested ones."""
    phases, _ = workload
    nested = ("shard_layout", "shard_put", "shard_exec", "shard_check")
    assert all(phases[n]["outer_secs"] == 0.0 < phases[n]["secs"] for n in nested)
    outer = sum(e["outer_secs"] for e in phases.values())
    secs = sum(e["secs"] for e in phases.values())
    assert outer <= secs - sum(phases[n]["secs"] for n in nested) + 1e-6 * len(phases)


def test_lists_are_what_benchmarks_names():
    """Every `consensus_*` name in the benchmark's Python (its own tests
    aside) is in a list above, and the lists hold nothing else."""
    named = set()
    for root, dirs, files in os.walk(os.path.join(REPO, "benchmarks")):
        dirs[:] = [d for d in dirs if d not in ("tests", "__pycache__")]
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    named |= set(re.findall(r"\bconsensus_[a-z_]+", fh.read()))
    assert named == set(READ + ZERO + MESH_STILL + CHIP_ONLY)


# -- where the time of a connect goes, by name -------------------------------


def _open_phase():
    """The innermost span open on this thread, as its name."""
    stack = S._stack()
    return stack[-1].name if stack else None


@pytest.fixture
def seams(monkeypatch):
    """Every call of the three seams that used to run outside any phase,
    each with the span that was open around it."""
    seen = []

    def recorded(owner, name, label):
        real = getattr(owner, name)

        def wrapper(self, *a, **k):
            if isinstance(self, owner):
                seen.append((label, _open_phase()))
            return real(self, *a, **k)

        monkeypatch.setattr(owner, name, wrapper)

    recorded(SigCache, "add_keys", "add_keys")
    recorded(SigCache, "contains_keys", "contains_keys")
    recorded(native_bridge.NativeBlock, "__del__", "block_free")
    return seen


WHERE = {"add_keys": "verifier.sig_insert", "contains_keys": "verifier.sig_probe",
         "block_free": "verifier.block_free"}


def test_a_connect_runs_its_seams_inside_named_phases(seams):
    verifier = TpuSecpVerifier()
    raw, coins = _block("contract/tiling", HEIGHT)
    sig = SigCache()
    for script in (ScriptExecutionCache(), ScriptExecutionCache()):
        res = connect_block(raw, to_native_view(coins), HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                            verifier=verifier, sig_cache=sig, script_cache=script)
        assert res.ok
    # cold: one insert, no probe (an empty cache is not probed), the block
    # freed; on the warm signature cache: one probe, an insert of nothing
    assert [label for label, _ in seams] == [
        "add_keys", "block_free", "contains_keys", "block_free"]
    assert all(span == WHERE[label] for label, span in seams), seams
    report = verifier.phases.report()
    assert report["block_free"]["calls"] == report["session_setup"]["calls"] == 2
    assert report["gc_sweep"]["calls"] == 2 and report["accept"]["calls"] == 2


def test_a_connect_walks_its_cache_keys_in_the_native_set():
    """The counter that says the native set engaged: a connect's bulk probes
    and inserts raise `consensus_cache_bulk_keys_total{store="native"}` by
    the block's keys (six inputs, one check each) and `{store="python"}` by
    nothing."""
    tag = os.urandom(4).hex()
    bulk = get_registry().get("consensus_cache_bulk_keys_total")
    verifier = TpuSecpVerifier()
    raw, coins = _block("contract/bulk", HEIGHT)
    sig = SigCache(cache_label=f"sig-{tag}")
    walked = {}
    for turn in ("cold", "warm-sig"):
        script = ScriptExecutionCache(cache_label=f"script-{turn}-{tag}")
        res = connect_block(raw, to_native_view(coins), HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                            verifier=verifier, sig_cache=sig, script_cache=script)
        assert res.ok and len(res.input_results) == 6
        for cache in (sig, script):
            label = cache._poison_site[len("sigcache."):]
            walked[turn, label[:3]] = bulk.value(cache=label, store="native")
            assert bulk.value(cache=label, store="python") == 0
    # cold: six successes inserted into each cache, no probe of an empty one;
    # on the warm signature cache: six probes more, all hits, nothing to
    # insert; the fresh script cache takes its six results
    assert walked == {("cold", "sig"): 6, ("cold", "scr"): 6,
                      ("warm-sig", "sig"): 12, ("warm-sig", "scr"): 6}


def test_a_stream_runs_its_seams_inside_named_phases(seams):
    verifier = TpuSecpVerifier()
    first, coins_a = _block("contract/tiling-a", HEIGHT)
    second, coins_b = _block("contract/tiling-b", HEIGHT + 1)
    coins_a._map.update(coins_b._map)
    results = list(connect_block_stream(
        [first, second], to_native_view(coins_a), HEIGHT, depth=2, verifier=verifier,
        pow_limit=REGTEST_POW_LIMIT, sig_cache=SigCache(),
        script_cache=ScriptExecutionCache()))
    assert [r.ok for r in results] == [True, True]
    # block 2 is begun before block 1's inserts, so it probes an empty
    # cache (no call); each block inserts once and is freed at its commit
    assert sorted(label for label, _ in seams) == ["add_keys"] * 2 + ["block_free"] * 2
    assert all(span == WHERE[label] for label, span in seams), seams
    report = verifier.phases.report()
    # a begin and a finish a block, each a paused section of its own
    assert report["block_free"]["calls"] == 2 and report["gc_sweep"]["calls"] == 4


def test_a_connect_under_the_profiler_shares_its_clock():
    """One mechanism, `obs.spans.span`, puts every span on the profiler's
    clock: a connect traced on the CPU backend holds `block.connect` and the
    `verifier.*` phases on the caller's host line, each inside its parent."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    verifier = TpuSecpVerifier()
    raw, coins = _block("contract/clock", HEIGHT)
    view = to_native_view(coins)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as directory:
        jax.profiler.start_trace(directory, profiler_options=options)
        try:
            res = connect_block(raw, view, HEIGHT, pow_limit=REGTEST_POW_LIMIT,
                                verifier=verifier, sig_cache=SigCache(),
                                script_cache=ScriptExecutionCache())
        finally:
            jax.profiler.stop_trace()
        assert res.ok
        (path,) = glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb"))
        data = ProfileData.from_file(path)
    lines = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.start_ns) + int(e.duration_ns))
                          for e in line.events
                          if e.name.startswith(("block.", "verifier."))]
                if events:
                    lines[line.name] = events
    assert len(lines) == 1, sorted(lines)  # the caller's thread, no other
    (events,) = lines.values()
    by_name = {}
    for name, start, end in events:
        by_name.setdefault(name, []).append((start, end))
    (c0, c1), = by_name["block.connect"]
    want = {"verifier." + n for n in (
        "parse", "block_check", "accounting", "probe", "session_setup", "interpret",
        "host_prep", "dispatch", "sync", "sig_insert", "publish", "accept", "release",
        "results", "apply", "block_free")}
    assert want <= set(by_name), sorted(want - set(by_name))
    for name in want:
        assert all(c0 <= a <= b <= c1 for a, b in by_name[name]), name
    # `gc_sweep` follows the span it closes: a sibling of `block.connect`
    assert all(a >= c1 for a, _ in by_name["verifier.gc_sweep"])
    # the phases of one thread do not overlap: they tile the connect
    inner = sorted(iv for n in want for iv in by_name[n])
    assert all(b0 <= a1 for (_, b0), (a1, _) in zip(inner, inner[1:]))
