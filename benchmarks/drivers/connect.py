"""Closed loop, one caller: connect the same block again and again.

Each iteration, untimed: clone the funded view, make fresh caches and,
where the traffic file gives a `precharge_share`, verify the inputs of the
transactions the mempool saw through `verify_batch` into those caches.
Timed: `connect_block` from the raw block to its `ConnectResult`.
"""

from __future__ import annotations

import bisect
import time
from typing import Dict, List, Optional

from ..harness import cell, counters, oracle, stats
from ..harness.tracer import annotate

# What the per-connect readers difference, read around every timed connect.
_PER_CONNECT = (
    "consensus_cache_hits_total", "consensus_cache_lookups_total",
    "consensus_dispatch_lanes_total", "consensus_dispatch_padded_lanes_total",
    "consensus_dispatch_total",
)


class Driver:
    def __init__(self, config: dict, traffic: dict, data: dict, seed: int,
                 control: Optional[str] = None, schedule_path: Optional[str] = None):
        self.config, self.traffic, self.data, self.seed = config, traffic, data, seed
        self.control = control
        self.walls: List[float] = []
        self.phases: List[dict] = []
        self.deltas: List[dict] = []
        self.failed = 0
        self.notes: List[str] = []
        self.last_results = None

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from bitcoinconsensus_tpu import native_bridge
        from bitcoinconsensus_tpu.core.flags import height_to_flags
        from bitcoinconsensus_tpu.models.batch import BatchItem

        d = self.data
        self.height = int(d["height"])
        self.flags = height_to_flags(self.height, extended=True)
        self.verifier = cell.make_verifier(self.config)
        self.watch = cell.PathWatch(self.verifier, self.config["backend"])
        self.funded = native_bridge.NativeCoinsView()
        self.funded.add_coins_batch(d["coins"])
        unseen = set(d["unseen_txs"])
        self.precharge = None
        if self.traffic.get("precharge_share"):
            self.precharge = [
                BatchItem(tx["raw"], i, self.flags, spent_outputs=tx["outs"])
                for t, tx in enumerate(d["txs"]) if t not in unseen
                for i in range(len(tx["outs"]))
            ]
        # The corrupted block first: it compiles the block's shape, and it
        # must be rejected for exactly its victim with the view untouched.
        victim = d["victim"] + (1 if self.control == "truth-shift" else 0)
        view = self.funded.clone()
        res = self._connect(d["bad_block"], view, *cell.fresh_caches(self.config))
        bad = d["bad_tx"]
        want = oracle.oracle_verdict(
            bad["raw"], d["victim"] - d["tx_start"][bad["index"]], bad["outs"], self.flags
        )
        got = oracle.as_triple(res.input_results[d["victim"]]) if res.input_results else None
        self.bad_block = {
            "rejected": not res.ok, "reason": res.reason,
            "script_failures": res.script_failures, "victim": victim,
            "view_untouched": len(view) == len(self.funded),
            "victim_verdict": got, "oracle_verdict": want,
        }
        if (res.ok or res.reason != "block-validation-failed"
                or res.script_failures != [victim] or len(view) != len(self.funded)
                or got != want):
            self.notes.append(f"corrupted block: {self.bad_block}")
        # Then one whole iteration as the window runs it, untimed.
        self._iteration(record=False)

    def _connect(self, raw: bytes, view, sig_cache, script_cache):
        from bitcoinconsensus_tpu.models.validate import connect_block

        return connect_block(
            raw, view, self.height, pow_limit=int(self.config["block"]["pow_limit"], 16),
            verifier=self.verifier, sig_cache=sig_cache, script_cache=script_cache,
        )

    # -- the loop ---------------------------------------------------------

    def _iteration(self, record: bool = True) -> None:
        from bitcoinconsensus_tpu.models.batch import verify_batch

        with annotate("reset"):
            view = self.funded.clone()
            sig_cache, script_cache = cell.fresh_caches(self.config)
        if self.precharge is not None:
            with annotate("precharge"):
                seen = verify_batch(self.precharge, self.verifier, sig_cache, script_cache)
            if not all(r.ok for r in seen):
                self.notes.append("precharge: a valid input was refused")
        self.verifier.phases.reset()
        before = counters.snapshot(_PER_CONNECT)
        t0 = time.perf_counter()
        with annotate("connect"):
            res = self._connect(self.data["block"], view, sig_cache, script_cache)
        wall = time.perf_counter() - t0
        if not record:
            return
        with annotate("account"):
            self._account(wall, res, before)

    def _account(self, wall: float, res, before: dict) -> None:
        after = counters.snapshot(_PER_CONNECT)
        self.walls.append(wall)
        self.phases.append(self.verifier.phases.report())
        self.deltas.append({n: counters.rose(before, after, n) for n in _PER_CONNECT})
        ok = (res.ok and res.input_results is not None
              and len(res.input_results) == self.data["n_inputs"]
              and all(r.ok for r in res.input_results))
        if not ok:
            self.failed += 1
            if len(self.notes) < 5:
                self.notes.append(
                    f"connect {len(self.walls)}: ok={res.ok} reason={res.reason!r} "
                    f"failures={res.script_failures[:5]}"
                )
        self.last_results = res.input_results

    def run_window(self, seconds: float, tracer) -> None:
        self.watch.open()
        self.window_start = time.monotonic()
        with cell.armed(self.control, self.verifier, self.seed):
            while True:
                elapsed = time.monotonic() - self.window_start
                tracer.poll(elapsed)
                if elapsed >= seconds:
                    break
                self._iteration()
        tracer.stop()
        self.window_s = time.monotonic() - self.window_start
        self.watch.close()

    # -- results ----------------------------------------------------------

    def verify(self) -> dict:
        d = self.data
        n = d["n_inputs"]
        got, items = {}, {}
        results = self.last_results or []
        for i, r in enumerate(results):
            got[i] = oracle.as_triple(r)
        for i in oracle.sample_indices(n, [], int(self.config["oracle_sample"]), self.seed):
            t = bisect.bisect_right(d["tx_start"], i) - 1
            tx = d["txs"][t]
            items[i] = (tx["raw"], i - d["tx_start"][t], tx["outs"], self.flags)
        compared = oracle.compare(got, items, {i: True for i in range(n)})
        problems = list(self.notes) + self.watch.problems()
        return {
            "attempted": len(self.walls),
            "failed": self.failed,
            "compared": compared,
            "corrupted_block": self.bad_block,
            "problems": problems,
            "correct": bool(self.walls) and not self.failed
            and not compared["mismatches"] and not problems,
        }

    def end_to_end(self) -> Dict[str, float]:
        timed = sum(self.walls)
        return {
            "connect_ms_p50": stats.median(self.walls) * 1000.0,
            "inputs_per_s": self.data["n_inputs"] * len(self.walls) / timed,
        }

    def layer_context(self) -> dict:
        return {
            "kind": "connect", "walls_s": self.walls, "phases": self.phases,
            "deltas": self.deltas, "counters_before": self.watch.before,
            "counters_after": self.watch.after, "n_inputs": self.data["n_inputs"],
        }

    def detail(self) -> dict:
        """Beside the metrics, for a reader of the line: the median
        milliseconds of every phase `verifier.phases` timed in a connect."""
        names = sorted({n for rep in self.phases for n in rep})
        ms = [w * 1000.0 for w in self.walls]
        return {"phase_ms_p50": {
            n: stats.median([rep.get(n, {}).get("secs", 0.0) for rep in self.phases]) * 1000.0
            for n in names
        }, "connect_ms": {
            "min": min(ms), "p10": stats.percentile(ms, 10.0), "p90": stats.percentile(ms, 90.0),
            "max": max(ms), "mean": sum(ms) / len(ms),
        }, "connects": len(ms), "window_s": self.window_s}

    def close(self) -> None:
        pass
