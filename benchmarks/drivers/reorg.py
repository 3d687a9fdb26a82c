"""Closed loop, one caller: a node at the tip goes over to a competing
branch, again and again.

One reorganisation, timed from the first call to the third `ConnectResult`
inside one `bench.reorg` annotation: `disconnect_block` of `A2` and of `A1`
with the records their connects returned, then `B1`, `B2`, `B3` as raw
bytes through `connect_block_stream(depth, want_undo=True)` on the caches
that connecting `A1`, `A2` left warm. Before the next, untimed:
`disconnect_block` of `B3`, `B2`, `B1` with the records the timed call
returned, fresh caches, `A1` and `A2` through the same stream call, and the
view's `len` and `digest()` as set-up found them at the tip of `A`.

Set-up also offers the operator four things it must refuse or survive: a
record with another block, a block disconnected before the one on top of
it, a record offered a second time, and the branch with one signature of
`B2` flipped where `B3` spends that transaction's output: `B1` ok, `B2`
rejected for exactly its victim, the end, then back to `A` by the record
that stream handed out. The plain reference (`harness/reorgref.py`) goes
through every step beside the program.

How the cell came in (it edited no file the benchmark had):
`configs/tip-reorg.json` states the block, the fork, the view and the
guarantees; `traffic/reorg-depth2.json` names the `fork` generator and this
driver; `generators/fork.py` builds the two branches; thirteen readers
`layers/*.reorg.py` share `layers/_reorg.py`.
"""

from __future__ import annotations

import bisect
import time
from typing import Dict, List, Optional

# A program that cannot take a block off its tip fails here, as the driver
# is imported: before a verifier is made or a shape compiled.
from bitcoinconsensus_tpu.models.validate import connect_block_stream, disconnect_block

from ..harness import cell, counters, oracle, reorgref, stats
from ..harness.tracer import annotate
from .stream import ROLLBACKS, background_coins

BRANCH_A, BRANCH_B = ("A1", "A2"), ("B1", "B2", "B3")
PROBES = "consensus_coin_probes_total"
DISCONNECTED = "consensus_blocks_disconnected_total"
UNDO_COINS = "consensus_undo_coins_total"
# What the readers and the turn's own check difference, read around every
# timed reorganisation.
_PER_REORG = (
    "consensus_cache_hits_total", "consensus_cache_lookups_total",
    "consensus_dispatch_lanes_total", "consensus_dispatch_padded_lanes_total",
    "consensus_dispatch_total", "consensus_dispatch_transfers_total",
    PROBES, DISCONNECTED, UNDO_COINS,
)
_HELD_AT_ZERO = ("consensus_exact_fallback_total", "consensus_host_fixup_total", ROLLBACKS)


class Driver:
    def __init__(self, config: dict, traffic: dict, data: dict, seed: int,
                 control: Optional[str] = None, schedule_path: Optional[str] = None):
        self.config, self.traffic, self.data, self.seed = config, traffic, data, seed
        self.control = control
        self.depth = int(config["depth"])
        self.n_inputs = int(data["n_inputs"])
        self.walls: List[float] = []  # a sound reorganisation, first call to last result
        self.disconnects: List[float] = []  # every timed disconnect_block of a sound one
        self.gaps: List[List[float]] = []  # first connect call -> B1 -> B2 -> B3
        self.phases: List[dict] = []
        self.deltas: List[dict] = []
        self.resets: List[float] = []
        self.failed = 0
        self.notes: List[str] = []
        self.last_results = None
        self.window_s = 0.0

    def _note(self, text: str) -> None:
        if len(self.notes) < 8:
            self.notes.append(text)

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from bitcoinconsensus_tpu import native_bridge
        from bitcoinconsensus_tpu.core.flags import height_to_flags

        d = self.data
        h0 = int(d["fork_height"])
        self.heights = {"A1": h0 + 1, "A2": h0 + 2, "B1": h0 + 1, "B2": h0 + 2, "B3": h0 + 3}
        self.flags = {k: height_to_flags(h, extended=True) for k, h in self.heights.items()}
        self.pow_limit = int(self.config["block"]["pow_limit"], 16)
        self.verifier = cell.make_verifier(self.config)
        self.watch = cell.PathWatch(self.verifier, self.config["backend"])
        self.n_background = int(self.config["utxo_set"]["background_coins"])
        self.view = native_bridge.NativeCoinsView()
        self.view.add_coins_arrays(**background_coins(self.n_background, self.seed))
        self.view.add_coins_batch(d["coins"])
        if len(self.view) != self.n_background + len(d["coins"]):
            self._note("a background outpoint collides with a funded one")
        self.at_fork = self._state()
        self.ref = reorgref.ReorgRef(d["coins"])
        self.ref_undo: Dict[str, list] = {}
        self.undo: Dict[str, object] = {}
        # The node's own branch first: it compiles a full block's shape.
        self.at_tip_a = None
        self._to_tip_a()
        self.at_tip_a = self._state()
        self._ref_connect(BRANCH_A)
        self._against_ref("the view at the tip of A")
        self.refused = self._refusals()
        self.bad_branch = self._corrupted_branch()
        # Then one whole turn as the window runs it, untimed.
        self._reorganise(record=False)
        self._reset()

    def _state(self):
        return len(self.view), self.view.digest()

    def _stream(self, blocks, start_label: str, caches):
        return connect_block_stream(
            blocks, self.view, self.heights[start_label], depth=self.depth,
            verifier=self.verifier, pow_limit=self.pow_limit, sig_cache=caches[0],
            script_cache=caches[1], want_undo=True,
        )

    def _disconnect(self, label: str, undo=None):
        return disconnect_block(
            self.data["blocks"][label], self.view, self.undo[label] if undo is None else undo,
            self.heights[label], verifier=self.verifier,
        )

    def _sound(self, res) -> bool:
        return bool(res.ok and res.undo is not None and res.input_results is not None
                    and len(res.input_results) == self.n_inputs
                    and all(r.ok for r in res.input_results))

    def _to_tip_a(self) -> bool:
        """From the fork point: fresh caches, `A1` and `A2` through the
        stream, their records kept, the caches left as they leave them. One
        more try where a block was refused (a control's one flipped chunk),
        which the run is marked for."""
        for _ in range(2):
            self.caches = cell.fresh_caches(self.config)
            results = list(self._stream([self.data["blocks"][k] for k in BRANCH_A], "A1",
                                        self.caches))
            if len(results) == len(BRANCH_A) and all(self._sound(r) for r in results):
                self.undo.update({k: r.undo for k, r in zip(BRANCH_A, results)})
                if self.at_tip_a is not None and self._state() != self.at_tip_a:
                    self.failed += 1
                    self._note("the view back at the tip of A is not the view set-up saw there")
                return True
            self.failed += 1
            self._note(f"connecting A: ok={[r.ok for r in results]} "
                       f"reason={results[-1].reason!r} failures={results[-1].script_failures[:5]}")
            for k, r in reversed(list(zip(BRANCH_A, results))):
                if r.ok:
                    self._disconnect(k, r.undo)
        return False

    def _ref_connect(self, labels) -> None:
        for k in labels:
            self.ref_undo[k] = self.ref.connect(self.data["blocks"][k], self.heights[k])

    def _ref_disconnect(self, labels, problems: Optional[List[str]] = None) -> None:
        for k in labels:
            got = self.ref.disconnect(self.data["blocks"][k], self.ref_undo[k], self.heights[k])
            if got != "ok":
                (self.notes if problems is None else problems).append(
                    f"the reference's disconnect of {k} is {got}")

    def _against_ref(self, where: str, problems: Optional[List[str]] = None) -> None:
        differences = self.ref.differences(self.view, self.n_background)
        if differences:
            (self.notes if problems is None else problems).append(f"{where}: {differences}")

    def _refusals(self) -> dict:
        """From the tip of A to the fork point, by way of three offers the
        operator must refuse with the view untouched; the reference is
        offered the same and has to say the same."""
        blocks, h = self.data["blocks"], self.heights
        out = {}

        def offer(name: str, label: str, record: str, want: str) -> None:
            before = self._state()
            got = self._disconnect(label, self.undo[record]).reason
            ref = self.ref.disconnect(blocks[label], self.ref_undo[record], h[label])
            out[name] = {"program": got, "reference": ref, "want": want,
                         "view_untouched": self._state() == before}
            if not (got == ref == want and out[name]["view_untouched"]):
                self._note(f"refusal {name}: {out[name]}")

        offer("another_blocks_record", "A2", "A1", "failed")
        offer("out_of_order", "A1", "A1", "unclean")
        for label in ("A2", "A1"):
            if not self._disconnect(label).ok:
                self._note(f"set-up could not disconnect {label}")
            self._ref_disconnect([label])
            if label == "A2":
                offer("a_second_time", "A2", "A2", "unclean")
        if self._state() != self.at_fork:
            self._note("A2 and A1 disconnected, and the view is not the fork point's")
        return out

    def _corrupted_branch(self) -> dict:
        """From the fork point, on the caches A left warm: `B1`, then `B2`
        with one signature of one of its new transactions flipped, then a
        `B3` that spends that transaction's output. `B1` ok, `B2` rejected
        for exactly its victim, the end, the view at fork + `B1`; then back
        to A as Core goes back to its best valid chain."""
        d, bad = self.data, self.data["bad"]
        victim = bad["victim"] + (1 if self.control == "truth-shift" else 0)
        rolled = counters.total(counters.snapshot([ROLLBACKS]), ROLLBACKS)
        results = list(self._stream([d["blocks"]["B1"], bad["B2"], bad["B3"]], "B1", self.caches))
        rolled = counters.total(counters.snapshot([ROLLBACKS]), ROLLBACKS) - rolled
        last = results[-1]
        want = oracle.oracle_verdict(
            bad["tx"]["raw"], bad["victim"] - d["tx_start"]["B2"][bad["tx"]["index"]],
            bad["tx"]["outs"], self.flags["B2"],
        )
        got = (oracle.as_triple(last.input_results[bad["victim"]])
               if last.input_results else None)
        self._ref_connect(["B1"])
        differences = self.ref.differences(self.view, self.n_background)
        back = self._disconnect("B1", results[0].undo).reason if results[0].ok else None
        self._ref_disconnect(["B1"])
        at_fork = self._state() == self.at_fork
        at_tip = self._to_tip_a() and self._state() == self.at_tip_a
        self._ref_connect(BRANCH_A)
        out = {
            "results": [r.ok for r in results], "records": [r.undo is not None for r in results],
            "reason": last.reason, "script_failures": last.script_failures, "victim": victim,
            "victim_verdict": got, "oracle_verdict": want, "view_differences": differences,
            "rollbacks": rolled, "in_flight_after": self.verifier._inflight.depth,
            "disconnect_b1": back, "at_fork_after": at_fork, "at_tip_a_after": at_tip,
        }
        # B2's own apply, and that of the block begun behind it.
        if (out["results"] != [True, False] or out["records"] != [True, False]
                or last.reason != "block-validation-failed"
                or last.script_failures != [victim] or got != want or differences
                or rolled != min(self.depth, 2) or self.verifier._inflight.depth
                or back != "ok" or not at_fork or not at_tip):
            self._note(f"corrupted branch: {out}")
        return out

    # -- the loop ---------------------------------------------------------

    def _reorganise(self, record: bool = True) -> None:
        """The timed call. Leaves the view at the tip of B and the records
        of B's blocks in `self.undo`."""
        d = self.data
        phases = self.verifier.phases
        phases.reset()
        before = counters.snapshot(_PER_REORG)
        took, stamps, results = [], [], []
        with annotate("reorg"):
            t0 = time.perf_counter()
            outcomes = []
            for label in reversed(BRANCH_A):
                t = time.perf_counter()
                outcomes.append(self._disconnect(label))
                took.append(time.perf_counter() - t)
            stamps.append(time.perf_counter())
            if all(o.ok for o in outcomes):
                for res in self._stream([d["blocks"][k] for k in BRANCH_B], "B1", self.caches):
                    stamps.append(time.perf_counter())
                    results.append(res)
        after = counters.snapshot(_PER_REORG)
        self.connected_b = [k for k, r in zip(BRANCH_B, results) if r.ok]
        self.undo.update({k: r.undo for k, r in zip(BRANCH_B, results)})
        self.left_a = [k for k, o in zip(reversed(BRANCH_A), outcomes) if o.ok]
        if record:
            with annotate("account"):
                self._account(t0, took, stamps, outcomes, results, before, after, phases.report())

    def _account(self, t0, took, stamps, outcomes, results, before, after, report) -> None:
        c = self.data["counts"]
        delta = {n: counters.rose(before, after, n) for n in _PER_REORG}
        probes = counters.rose_by_label(before, after, PROBES, "table")
        delta["undo_probes"] = probes.get("undo", 0.0)
        delta["connect_probes"] = probes.get("view", 0.0) + probes.get("block", 0.0)
        moved = counters.rose_by_label(before, after, UNDO_COINS, "what")
        ended = counters.rose_by_label(before, after, DISCONNECTED, "result")
        want_moved = {"restored": c["inputs"]["A1"] + c["inputs"]["A2"],
                      "removed": c["outputs"]["A1"] + c["outputs"]["A2"]}
        ok = (all(o.ok for o in outcomes) and len(results) == len(BRANCH_B)
              and all(self._sound(r) for r in results)
              and moved == want_moved and ended == {"ok": len(BRANCH_A)})
        if not ok:
            self.failed += 1
            self._note(
                f"reorganisation {len(self.walls) + self.failed}: "
                f"disconnects={[o.reason for o in outcomes]} ok={[r.ok for r in results]} "
                f"reason={results[-1].reason if results else None!r} "
                f"failures={results[-1].script_failures[:5] if results else None} "
                f"moved={moved} ended={ended}")
            return
        self.walls.append(stamps[-1] - t0)
        self.disconnects.extend(took)
        self.gaps.append([b - a for a, b in zip(stamps, stamps[1:])])
        self.phases.append(report)
        self.deltas.append(delta)
        self.last_results = results

    def _reset(self) -> None:
        """Untimed: from wherever the timed call left the view back to the
        tip of A, by the records it returned."""
        t0 = time.perf_counter()
        with annotate("reset"):
            for label in reversed(self.connected_b):
                if not self._disconnect(label).ok:
                    self.failed += 1
                    self._note(f"the reset could not disconnect {label}")
            if len(self.left_a) == len(BRANCH_A):
                self._to_tip_a()
            elif self.left_a:  # A2 went and A1 would not: A2 comes back alone
                raise RuntimeError("a reorganisation stopped between its two disconnects")
        self.resets.append(time.perf_counter() - t0)

    def run_window(self, seconds: float, tracer) -> None:
        self.watch.open()
        self.window_start = time.monotonic()
        with cell.armed(self.control, self.verifier, self.seed):
            while True:
                tracer.poll(time.monotonic() - self.window_start)
                self._reorganise()
                # The last one stays where it ended: `verify` looks at the
                # view there, and then makes the reset itself.
                if time.monotonic() - self.window_start >= seconds:
                    break
                self._reset()
        tracer.stop()
        self.window_s = time.monotonic() - self.window_start
        self.watch.close()

    # -- results ----------------------------------------------------------

    def verify(self) -> dict:
        d = self.data
        n, total = self.n_inputs, self.n_inputs * len(BRANCH_B)
        got, items = {}, {}
        for k, res in enumerate(self.last_results or []):
            for i, r in enumerate(res.input_results):
                got[k * n + i] = oracle.as_triple(r)
        for j in oracle.sample_indices(total, [], int(self.config["oracle_sample"]), self.seed):
            k, i = divmod(j, n)
            label = BRANCH_B[k]
            t = bisect.bisect_right(d["tx_start"][label], i) - 1
            tx = d["txs"][label][t]
            items[j] = (tx["raw"], i - d["tx_start"][label][t], tx["outs"], self.flags[label])
        compared = oracle.compare(got, items, {j: True for j in range(total)})
        problems = list(self.notes) + self.watch.problems()
        for name in _HELD_AT_ZERO:
            rose = counters.rose(self.watch.before, self.watch.after, name)
            if rose:
                problems.append(f"{name} +{rose:g} inside the window")
        # The view after the last timed call, and after the last reset,
        # against the plain reference taken the same way.
        if self.connected_b == list(BRANCH_B):
            self._ref_disconnect(reversed(BRANCH_A), problems)
            self._ref_connect(BRANCH_B)
            self._against_ref("the view after the last reorganisation", problems)
            self._reset()
            self._ref_disconnect(reversed(BRANCH_B), problems)
            self._ref_connect(BRANCH_A)
            self._against_ref("the view after the last reset", problems)
            if self._state() != self.at_tip_a:
                problems.append("the view after the last reset is not the tip of A set-up saw")
        else:
            problems.append("the last reorganisation did not reach the tip of B")
        problems.extend(x for x in self.notes if x not in problems)
        return {
            "attempted": len(self.walls) + self.failed,
            "failed": self.failed,
            "compared": compared,
            "corrupted_block": {"refused": self.refused, "branch": self.bad_branch},
            "problems": problems,
            "correct": bool(self.walls) and not self.failed
            and not compared["mismatches"] and not problems,
        }

    def end_to_end(self) -> Dict[str, float]:
        timed = sum(self.walls)
        done = self.n_inputs * len(BRANCH_B) * len(self.walls)
        return {"inputs_per_s": done / timed if timed else 0.0}

    def layer_context(self) -> dict:
        c = self.data["counts"]
        return {
            "kind": "reorg", "walls_s": self.walls, "disconnect_s": self.disconnects,
            "gaps_s": self.gaps, "phases": self.phases, "deltas": self.deltas,
            "counters_before": self.watch.before, "counters_after": self.watch.after,
            "n_inputs": self.n_inputs, "verdicts": self.n_inputs * len(BRANCH_B),
            "disconnected_inputs": sum(c["inputs"][k] for k in BRANCH_A),
            "disconnected_outputs": sum(c["outputs"][k] for k in BRANCH_A),
        }

    def detail(self) -> dict:
        """Beside the metrics, for a reader of the line: the median
        milliseconds of every phase `verifier.phases` timed in a
        reorganisation (`undo_check` and `undo` among them), the walls of
        every reorganisation, the slowest one's phases, and what a
        reorganisation sent to the device."""
        base = {"reorganisations": len(self.walls), "depth": self.depth,
                "window_s": self.window_s, "counts": self.data["counts"]}
        if not self.walls:
            return base
        names = sorted({n for rep in self.phases for n in rep})
        ms = [w * 1000.0 for w in self.walls]
        slowest = max(range(len(ms)), key=ms.__getitem__)

        def phase_ms(rep):
            return {n: rep[n]["secs"] * 1000.0 for n in sorted(rep)}

        return {**base, "phase_ms_p50": {
            n: stats.median([rep.get(n, {}).get("secs", 0.0) for rep in self.phases]) * 1000.0
            for n in names
        }, "reorg_ms": {
            "min": min(ms), "p50": stats.median(ms), "max": max(ms), "mean": sum(ms) / len(ms),
        }, "reorg_walls_ms": [round(w, 3) for w in ms],
            "slowest": {"index": slowest, "ms": ms[slowest], "phase_ms": phase_ms(self.phases[slowest]),
                        "gaps_ms": [g * 1000.0 for g in self.gaps[slowest]]},
            "reset_ms_p50": stats.median(self.resets) * 1000.0 if self.resets else None,
            "a_reorganisation": {n: stats.median([x[n] for x in self.deltas])
                                 for n in sorted(self.deltas[0])}}

    def close(self) -> None:
        pass
