#!/usr/bin/env python
"""Run a deterministic verify workload and expose the metrics snapshot.

The observability counterpart of `scripts/consensus_lint.py`: where the
lint proves static properties of the kernels, this proves the telemetry
layer end to end — every pipeline layer (api, batch driver, sig/script
caches, device dispatch, mesh, block connect) must light up its metrics
on a small deterministic workload, or CI's `obs-smoke` job fails.

Usage:
    python scripts/consensus_stats.py                       # mini workload, JSON to stdout
    python scripts/consensus_stats.py --format prom         # Prometheus text
    python scripts/consensus_stats.py --out snap.json       # also write the doc
    python scripts/consensus_stats.py --check               # exit 1 on missing/NaN metrics
    python scripts/consensus_stats.py --diff old.json       # delta vs an earlier snapshot
    python scripts/consensus_stats.py --jsonl-sink spans.jsonl   # stream span records

`--workload none` skips the workload and snapshots whatever the process
already accumulated (useful under `python -i` or after importing from a
driver). The mini workload is seeded/deterministic: same inputs, same
counter values, modulo timing histograms.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The mesh leg of the workload wants >1 CPU device; must be set before
# jax initializes. 8 matches tests/conftest.py so this script shares the
# suite's persistent XLA compile cache (topology is part of the cache
# key — a different device count means minutes of recompiles).
if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

# Every metric name the mini workload must light up, by layer. This list
# is the CI contract: a refactor that silently drops an instrumentation
# point fails `--check` before it ships.
REQUIRED_METRICS = [
    # api layer
    "consensus_verify_calls_total",
    "consensus_verify_reject_total",
    "consensus_script_reject_total",
    # batch driver
    "consensus_batch_size",
    "consensus_batch_items_total",
    "consensus_batch_results_total",
    "consensus_fixpoint_rounds",
    "consensus_uniq_checks_total",
    "consensus_prep_lanes_total",
    "consensus_sighash_total",
    # what those digests cost: preimage bytes hashed and thread seconds, by kind
    "consensus_sighash_bytes_total",
    "consensus_sighash_seconds_total",
    # the blanked template a transaction's legacy digests are hashed from
    "consensus_sighash_template_total",
    # the native stage clock: the serial stages that tile a native call, and
    # what its thread fan-outs say of themselves (wall, held, sum, max, ...)
    "consensus_native_stage_seconds_total",
    "consensus_fan_out_seconds_total",
    "consensus_taproot_hash_total",
    # CHECKMULTISIG on the index path: the pairings pre-recorded ahead of
    # the key walk, and those the walk behind a returned verdict tried
    "consensus_multisig_spec_pairings_total",
    "consensus_multisig_walk_pairings_total",
    # caches
    "consensus_cache_lookups_total",
    "consensus_cache_hits_total",
    "consensus_cache_misses_total",
    "consensus_cache_insertions_total",
    "consensus_cache_entries",
    # the keys bulk probes and inserts walked, by where the set lives
    "consensus_cache_bulk_keys_total",
    # device dispatch
    "consensus_checks_total",
    "consensus_dispatch_total",
    "consensus_dispatch_lanes_total",
    "consensus_dispatch_padded_lanes_total",
    "consensus_dispatch_fill_ratio",
    "consensus_dispatch_new_shapes_total",
    # a one-device dispatch travels packed: one piece in, one out
    "consensus_dispatch_transfers_total",
    # mesh (fault-domain counters light up via the workload's eviction
    # leg; consensus_mesh_repromotions_total is chaos-sweep-only)
    "consensus_mesh_devices",
    "consensus_mesh_dispatch_total",
    "consensus_mesh_shard_lanes",
    "consensus_mesh_healthy_devices",
    "consensus_mesh_shard_failures_total",
    "consensus_mesh_evictions_total",
    "consensus_mesh_redispatch_lanes_total",
    # block connect
    "consensus_blocks_total",
    "consensus_block_reject_total",
    # block stream (native core: connect_block_stream's speculative view)
    "consensus_stream_blocks_total",
    "consensus_stream_rollbacks_total",
    "consensus_stream_blocks_in_flight",
    # native coin tables (the stream leg's native connects: both tables;
    # its disconnect: `table="undo"`)
    "consensus_coin_probes_total",
    # disconnect_block: how each call ended, and the coins a clean one moved
    "consensus_blocks_disconnected_total",
    "consensus_undo_coins_total",
    # resilience (clean-path samples: ladder gauge set at verifier
    # construction, sentinel lanes ride every padded dispatch; the fault
    # counters only light up under scripts/consensus_chaos.py)
    "consensus_resilience_level",
    "consensus_resilience_sentinel_lanes_total",
    # in-flight dispatch queue (every guarded dispatch rides a ticket;
    # the deadline/redispatch/backpressure counters only light up under
    # scripts/consensus_chaos.py or a saturated pipeline)
    "consensus_inflight_depth",
    "consensus_inflight_tickets_total",
    "consensus_inflight_settle_seconds",
    # the stream-window gauge sets on the serving leg's
    # verify_batch_stream bursts
    "consensus_pipeline_stream_window",
    # serving front end (admission + coalescing + SLO shedding; the
    # workload's serving leg admits a small fan-in and forces one
    # explicit shed so both sides of the admission decision sample)
    "consensus_serving_admitted_total",
    "consensus_serving_shed_total",
    "consensus_serving_queue_depth",
    "consensus_serving_queue_wait_seconds",
    "consensus_serving_batch_fill",
    "consensus_serving_batch_seconds",
    "consensus_serving_slo_seconds",
    "consensus_serving_slo_p50_seconds",
    "consensus_serving_slo_p99_seconds",
    "consensus_serving_batches_total",
    # network ingress (the workload's socket leg: one verified round
    # trip, one garbage frame, one reaped slow-loris; the write-error
    # path only lights up under scripts/consensus_chaos.py --ingress)
    "consensus_ingress_sessions_total",
    "consensus_ingress_frames_total",
    "consensus_ingress_bytes_total",
    "consensus_ingress_deadline_reaps_total",
    "consensus_ingress_protocol_errors_total",
    # persistent sigstore (populate, crash-free reopen, warm replay;
    # the skip/append-error counters are chaos-sweep-only)
    "consensus_sigstore_hits_total",
    "consensus_sigstore_misses_total",
    "consensus_sigstore_tier_entries",
    "consensus_sigstore_warmup_seconds",
    "consensus_sigstore_replay_records_total",
    "consensus_sigstore_appends_total",
    # serving cell (cell/: tenant-hash router + supervised replicas +
    # sigstore tier; the workload's cell leg runs two in-process
    # replicas, kills one, and drives the evict -> handoff -> reroute ->
    # re-promote loop for real. A retried frame needs a frame in flight
    # at the instant an upstream dies — inherently racy — so that
    # counter reports an explicit zero sample)
    "consensus_cell_replicas_healthy",
    "consensus_cell_evictions_total",
    "consensus_cell_repromotions_total",
    "consensus_cell_reroutes_total",
    "consensus_cell_retried_frames_total",
    "consensus_cell_handoffs_total",
    "consensus_cell_handoff_records_total",
    # sigstore shard ownership moved away mid-append (cell handoff):
    # the workload rips a store's directory out from under it and the
    # next append must restart the shard cold, counted, never raising
    "consensus_sigstore_shard_moved_total",
    # adversarial gauntlet (workloads/: corpus pins, replay stream,
    # differential fuzz; the divergence counter reports explicit zero
    # samples per leg — "ran and agreed", not merely "absent")
    "consensus_gauntlet_corpus_cases_total",
    "consensus_gauntlet_divergence_total",
    "consensus_gauntlet_replay_blocks_total",
    "consensus_gauntlet_fuzz_cases_total",
    "consensus_gauntlet_shape_seconds",
    # scalar-schedule prover (analysis/scalar_check.py: the fast
    # certificate set re-proves per run and reports per-target status —
    # a VACUOUS or FAIL sample here is a gate failure, not telemetry)
    "consensus_scalar_certificates",
    # GLV runtime range guard (crypto/glv.py SplitRangeError path;
    # registered at import, zero in any healthy run)
    "consensus_glv_split_range_total",
    # flight recorder (armed for one leg with one explicit
    # trigger; conviction-path triggers light up under
    # scripts/consensus_chaos.py)
    "consensus_flight_armed",
    "consensus_flight_events_total",
    "consensus_flight_dumps_total",
    # spans
    "consensus_span_duration_seconds",
]


def run_mini_workload() -> None:
    """Deterministic workload touching every instrumented layer.

    Success and failure paths both: the reject-reason counters keyed by
    `Error` / `ScriptError` code are part of the CI contract.
    """
    from bitcoinconsensus_tpu import api
    from bitcoinconsensus_tpu.core.flags import VERIFY_ALL_EXTENDED
    from bitcoinconsensus_tpu.crypto.jax_backend import SigCheck
    from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
    from bitcoinconsensus_tpu.models.validate import connect_block
    from bitcoinconsensus_tpu.parallel.mesh import (
        ShardedSecpVerifier,
        make_mesh,
    )
    from bitcoinconsensus_tpu.utils import blockgen

    def expect(code, fn, *args, **kw):
        try:
            fn(*args, **kw)
        except api.ConsensusError as e:
            assert e.code == code, f"expected {code.name}, got {e.code.name}"
        else:
            raise AssertionError(f"expected {code.name}, got success")

    # --- api layer: one success per entry point + one of each reject ---
    view, funded = blockgen.make_funded_view(8, seed="stats")
    tx = blockgen.build_spend_tx(funded[:4])
    raw = tx.serialize()
    outs = [(f.amount, f.wallet.spk) for f in funded[:4]]
    api.verify_with_spent_outputs(raw, 0, outs)
    pk_fund = [f for f in funded if f.wallet.kind == "p2pkh"][0]
    pk_tx = blockgen.build_spend_tx([pk_fund])
    api.verify(pk_fund.wallet.spk, pk_fund.amount, pk_tx.serialize(), 0)
    api.verify_with_flags(
        pk_fund.wallet.spk, pk_fund.amount, pk_tx.serialize(), 0, 0
    )
    expect(api.Error.ERR_TX_DESERIALIZE, api.verify, b"\x51", 0, b"junk", 0)
    expect(
        api.Error.ERR_INVALID_FLAGS,
        api.verify_with_flags, b"\x51", 0, raw, 0, 1 << 30,
    )
    expect(api.Error.ERR_TX_INDEX, api.verify_with_spent_outputs, raw, 99, outs)
    bad_tx = blockgen.build_spend_tx(funded[:4], corrupt_input=1)
    expect(
        api.Error.ERR_SCRIPT,
        api.verify_with_spent_outputs, bad_tx.serialize(), 1,
        outs,
    )

    # --- batch driver + caches + device dispatch: mixed batch, one bad
    # input, then an identical replay for the cache-hit counters ---
    items = [
        BatchItem(raw, i, VERIFY_ALL_EXTENDED, spent_outputs=outs)
        for i in range(4)
    ]
    bad_raw = bad_tx.serialize()
    items.append(
        BatchItem(bad_raw, 1, VERIFY_ALL_EXTENDED, spent_outputs=outs)
    )
    for _pass in range(2):
        res = verify_batch(items)
        assert [r.ok for r in res] == [True] * 4 + [False]

    # --- block connect: one valid block, one failing replay (this leg and
    # the stream's run before the serving legs, so that a replica lost in
    # those cannot hide what the disconnect's own asserts hold) ---
    bview, bfunded = blockgen.make_funded_view(4, height=1, seed="stats-blk")
    good = blockgen.build_spend_tx(bfunded, fee=2000)
    blk = blockgen.build_block([good], height=200, fees=2000)
    r = connect_block(blk, bview, 200, check_pow=False)
    assert r.ok, r.reason
    r2 = connect_block(blk, bview, 200, check_pow=False)  # inputs now spent
    assert not r2.ok

    # --- block stream: a block that connects, then one whose bad signature
    # shows in its finish, after its speculative apply (one rollback) ---
    from bitcoinconsensus_tpu import native_bridge

    if native_bridge.available():
        from bitcoinconsensus_tpu.models.validate import connect_block_stream

        sview, sfunded = blockgen.make_funded_view(8, height=1, seed="stats-stream")
        nview = native_bridge.NativeCoinsView()
        nview.add_coins_batch([
            (op_txid, n, c.out.value, c.height, c.coinbase, c.out.script_pubkey)
            for (op_txid, n), c in sview._map.items()
        ])
        chain = [
            blockgen.build_block(
                [blockgen.build_spend_tx(sfunded[:4], fee=2000)], height=200, fees=2000),
            blockgen.build_block(
                [blockgen.build_spend_tx(sfunded[4:], fee=2000, corrupt_input=0)],
                height=201, fees=2000),
        ]
        streamed = list(connect_block_stream(
            chain, nview, 200, check_pow=False, want_undo=True))
        assert [r.ok for r in streamed] == [True, False]
        # the block that stood is taken off the tip by the record the stream
        # handed out (its second block's rollback left it sound), and the same
        # record offered again is refused: the block's outputs are gone
        from bitcoinconsensus_tpu.models.validate import (
            _COIN_PROBES,
            _DISCONNECTED,
            _UNDO_COINS,
            disconnect_block,
        )

        before = (len(nview), nview.digest())
        assert streamed[1].undo is None
        gone = disconnect_block(chain[0].serialize(), nview, streamed[0].undo, 200)
        assert gone.ok and (gone.restored, gone.removed) == (4, 3)
        assert (len(nview), nview.digest()) != before
        again = disconnect_block(chain[0].serialize(), nview, streamed[0].undo, 200)
        assert again.reason == "unclean"
        # both began, so both were accounted and applied: the counter holds
        # the label values its readers sum, and the disconnect's own
        assert all(_COIN_PROBES.value(table=t) > 0 for t in ("view", "block", "undo"))
        assert _DISCONNECTED.value(result="ok") == _DISCONNECTED.value(result="unclean") == 1
        assert (_UNDO_COINS.value(what="restored"), _UNDO_COINS.value(what="removed")) == (4, 3)

    # --- serving front end: coalesced fan-in from two tenants, then a
    # deliberate overload (tenant_depth=1, no time flush) so the shed
    # counter and both admission outcomes sample ---
    from bitcoinconsensus_tpu.serving import OverloadError, VerifyServer

    with VerifyServer(max_batch=8, flush_s=0.005, tenant_depth=8) as srv:
        pend = [
            srv.submit(it, tenant=f"tenant{i % 2}")
            for i, it in enumerate(items[:4])
        ]
        assert [p.result(timeout=60).ok for p in pend] == [True] * 4
    srv2 = VerifyServer(max_batch=64, flush_s=30.0, tenant_depth=1).start()
    queued = srv2.submit(items[0])
    expect(api.Error.ERR_OVERLOADED, srv2.submit, items[1])
    srv2.close(drain=True)  # graceful drain settles the queued request
    assert queued.result(timeout=60).ok and srv2.pending == 0

    # --- network ingress: one verified socket round trip, a garbage
    # frame (protocol-error counter), and a reaped slow-loris (deadline
    # counter) against a short-idle listener ---
    import socket as socketlib

    from bitcoinconsensus_tpu.serving import IngressClient, IngressServer
    from bitcoinconsensus_tpu.serving.ingress import encode_frame

    with VerifyServer(max_batch=8, flush_s=0.005, tenant_depth=8) as srv3:
        ing = IngressServer(srv3, idle_s=0.2).start()
        try:
            cli = IngressClient(port=ing.port, timeout_s=60)
            assert cli.verify(items[0]).ok
            cli.close()
            s = socketlib.create_connection(
                ("127.0.0.1", ing.port), timeout=30
            )
            s.sendall(encode_frame(0x7D, b"junk"))  # unknown frame type
            s.settimeout(30)
            s.recv(64)  # typed ERR frame comes back, then EOF
            s.close()
            s = socketlib.create_connection(
                ("127.0.0.1", ing.port), timeout=30
            )
            s.sendall(b"\x01\x00\x00\x00\x40")  # header only, then stall
            s.settimeout(30)
            while s.recv(64):  # blocks until the deadline reap closes us
                pass
            s.close()
        finally:
            ing.close(drain=True)

    # --- persistent sigstore: populate through the driver, reopen (warm
    # replay), and replay the same workload so the hit/warm-up side of
    # the two-tier store samples alongside the cold-pass misses ---
    import tempfile

    from bitcoinconsensus_tpu.models.sigcache import ScriptExecutionCache
    from bitcoinconsensus_tpu.models.sigstore import PersistentSigCache

    sdir = tempfile.mkdtemp(prefix="stats-sigstore-")
    good = items[:4]
    with PersistentSigCache(sdir, hot_entries=64, shards=2,
                            warmup_min_probes=2) as store:
        verify_batch(good, sig_cache=store,
                     script_cache=ScriptExecutionCache(cache_label="ss1"))
    with PersistentSigCache(sdir, hot_entries=64, shards=2,
                            warmup_min_probes=2) as store2:
        assert len(store2) > 0  # replay warmed the cold tier
        verify_batch(good, sig_cache=store2,
                     script_cache=ScriptExecutionCache(cache_label="ss2"))
        assert store2.warmup_s is not None  # >=90% hits on the repeat

    # --- serving cell: two in-process replicas behind the tenant-hash
    # router; kill one and drive the full failure loop for real —
    # dead-replica eviction, sigstore shard handoff to the survivor,
    # tenant re-route, then restart + known-answer re-promotion. A
    # retried frame needs a frame in flight at the instant an upstream
    # dies (inherently racy), so that counter samples an explicit zero ---
    import shutil
    import time as timelib

    from bitcoinconsensus_tpu.cell import ServingCell
    from bitcoinconsensus_tpu.cell.router import _C_RETRIED

    with ServingCell(
        n_replicas=2, stub=True,
        server_kw=dict(max_batch=8, flush_s=0.005),
        evict_after=1, backoff_s=0.02, max_backoff_s=0.05,
    ) as cell:
        cellcli = IngressClient(port=cell.port, timeout_s=60)
        try:
            assert cellcli.verify(items[0], tenant="cell-t0").ok
            victim = cell.router._home.lookup("cell-t0")
            cell.replicas[victim].kill()
            cell.tick()  # dead -> evict -> shard handoff to the survivor
            assert victim not in cell.healthy_names()
            # The victim's tenant must verify again via the survivor
            # (lights the reroute counter on its real code path).
            assert cellcli.verify(items[0], tenant="cell-t0").ok
            deadline = timelib.monotonic() + 60
            while (victim not in cell.healthy_names()
                   and timelib.monotonic() < deadline):
                timelib.sleep(0.06)
                cell.tick()  # restart + passing known-answer probe
            assert victim in cell.healthy_names()
        finally:
            cellcli.close()
    _C_RETRIED.inc(0)  # explicit zero: no frame in flight at link death

    # A store whose directory vanishes mid-append (shard ownership moved
    # away under a cell handoff) must restart the shard cold — counted,
    # never raised into the verify path.
    sdir2 = tempfile.mkdtemp(prefix="stats-shard-moved-")
    store3 = PersistentSigCache(sdir2, hot_entries=16, shards=2)
    shutil.rmtree(sdir2)
    store3.add_key(b"\x07" * 32)  # lazy shard open hits the gone dir
    # The moved shard restarts cold: it must NOT keep answering for
    # keys whose records now live elsewhere.
    assert not store3.peek_key(b"\x07" * 32) and len(store3) == 0
    store3.close()

    # --- mesh: a sharded dispatch over the (virtual) device mesh ---
    sv = ShardedSecpVerifier(mesh=make_mesh())
    w = blockgen.Wallet("stats-mesh", "p2wpkh")
    import hashlib

    msg = hashlib.sha256(b"stats-mesh-msg").digest()
    from bitcoinconsensus_tpu.crypto import secp_host as H

    sig = H.sign_ecdsa(w.sk, msg)
    checks = [SigCheck("ecdsa", (w.pub, sig, msg))] * 4
    res, verdict = sv.verify_checks_with_verdict(checks)
    assert verdict and res.all()

    # --- mesh fault domains: one injected device loss evicts a device
    # and re-answers its lanes, lighting the shard-failure / eviction /
    # re-dispatch counters on their real code paths ---
    from bitcoinconsensus_tpu.resilience import FaultPlan, FaultSpec, inject

    sv2 = ShardedSecpVerifier(mesh=make_mesh(), evict_after=1)
    with inject(
        FaultPlan([FaultSpec("mesh.shard.1", "device-loss")]), seed=0
    ):
        res2, verdict2 = sv2.verify_checks_with_verdict(checks)
    assert verdict2 and res2.all()
    assert int(sv2.mesh.devices.size) == 7  # survivor mesh kept flowing

    # --- adversarial gauntlet: a tiny replay stream, the pinned corpus
    # sweep (per-shape latency histogram) and a handful of fuzz mutants
    # light the consensus_gauntlet_* family with its zero-divergence
    # samples ---
    from bitcoinconsensus_tpu.workloads import (
        ReplayConfig,
        run_diff_fuzz,
        run_replay,
    )
    from bitcoinconsensus_tpu.workloads.corpus import run_corpus_check

    grep = run_replay(ReplayConfig(seed=5, n_blocks=2, txs_per_block=2))
    assert grep["bit_identical"], grep["divergences"]
    crep = run_corpus_check()
    assert crep["pinned"], crep["mismatches"]
    frep = run_diff_fuzz(seed=1, n_cases=8)
    assert frep["bit_identical"], frep["divergences"]

    # --- scalar-schedule prover: re-prove the fast certificate set
    # (digit recoders, byte packers, GLV lattice constants) so the
    # consensus_scalar_certificates{target,status} family carries a
    # THEOREM sample per target — a FAIL/VACUOUS status here is a gate
    # failure. The GLV range guard records explicit zero samples: the
    # split ran and stayed inside the proven |k_i| < 2^128 bound. ---
    from bitcoinconsensus_tpu.analysis import scalar_check
    from bitcoinconsensus_tpu.crypto import glv

    certs = scalar_check.certify_all(quick=True, include_heavy=False)
    bad = [(c.name, c.status, c.failures) for c in certs if not c.ok]
    assert not bad, bad
    for k in (1, glv.LAMBDA, (1 << 128) - 1):
        glv.split_lambda(k)
    glv._SPLIT_RANGE.inc(amount=0, half="k1")
    glv._SPLIT_RANGE.inc(amount=0, half="k2")

    # --- flight recorder: the armed recorder subscribes to spans and
    # one explicit trigger dumps the ring to a throwaway dir, sampling
    # the flight counters end to end ---
    from bitcoinconsensus_tpu.obs import flight, spans

    flight.set_enabled(True)
    try:
        with spans.span("stats.flight_leg"):
            pass  # one span through the armed sink -> ring event
        fdir = tempfile.mkdtemp(prefix="stats-flight-")
        dump = flight.trigger("stats", out_dir=fdir)
        assert dump is not None and os.path.exists(dump), dump
    finally:
        flight.set_enabled(False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--workload", choices=("mini", "none"), default="mini",
        help="workload to run before snapshotting (default: mini)",
    )
    ap.add_argument(
        "--format", choices=("json", "prom"), default="json",
        help="stdout exposition format (default: json)",
    )
    ap.add_argument("--out", help="also write the JSON document to this path")
    ap.add_argument(
        "--check", action="store_true",
        help="validate the snapshot (required metrics present with "
        "samples, no NaN/inf); exit 1 on problems",
    )
    ap.add_argument(
        "--diff", metavar="OLD_JSON",
        help="print per-metric deltas against an earlier --out document",
    )
    ap.add_argument(
        "--jsonl-sink", metavar="PATH",
        help="stream span records (JSON lines) to this file during the run",
    )
    args = ap.parse_args(argv)

    from bitcoinconsensus_tpu.obs import (
        JsonlSink,
        add_sink,
        get_registry,
        remove_sink,
    )
    from bitcoinconsensus_tpu.obs.exposition import (
        diff_snapshots,
        snapshot_to_json,
        to_prometheus_text,
        validate_snapshot,
    )

    sink = None
    if args.jsonl_sink:
        sink = JsonlSink(args.jsonl_sink)
        add_sink(sink)
    try:
        if args.workload == "mini":
            run_mini_workload()
    finally:
        if sink is not None:
            remove_sink(sink)
            sink.close()

    snap = get_registry().snapshot()
    doc = snapshot_to_json(snap, workload=args.workload)

    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            fh.write(doc + "\n")

    if args.diff:
        with open(args.diff, encoding="utf-8") as fh:
            old = json.load(fh)["metrics"]
        lines = diff_snapshots(old, snap)
        print("\n".join(lines) if lines else "(no differences)")
    elif args.format == "prom":
        sys.stdout.write(to_prometheus_text(snap))
    else:
        print(doc)

    if args.check:
        required = REQUIRED_METRICS if args.workload == "mini" else ()
        problems = validate_snapshot(snap, required)
        with_samples = [n for n in snap if snap[n]["samples"]]
        print(
            f"# {len(with_samples)} metrics with samples, "
            f"{len(problems)} problems",
            file=sys.stderr,
        )
        if problems:
            for p in problems:
                print(f"PROBLEM: {p}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
