"""Closed loop, one caller: a chain of blocks through one overlapped stream,
again and again.

One pass, timed: the chain's blocks as raw bytes, in height order, through
`connect_block_stream` at the configuration's depth, from the first call
to the last `ConnectResult`. Before every pass, untimed: fresh caches, and
the view put back by the blocks' undo records, newest first (the traffic
file says why not by a clone). Set-up also runs the chain with one
signature of one block flipped, which has to end there with the view
rolled back, and one whole pass as the window runs it.

How the cell came in, as a worked example of "a stream of blocks" (it
edited no file the benchmark had): `configs/ibd-stream.json` states the
chain (`chain`: blocks, heights, the counts of a block, how many inputs
spend what the block before created, which block the corrupted stream
breaks), the view (`utxo_set`, the one key under `reduced`), `depth` and
the five guarantees of a stream; `traffic/stream-cold.json` names the
`chain` generator and this driver and says how the view is reset between
passes; `generators/chain.py` builds the dependent pre-segwit blocks;
`harness/chainref.py` is the plain reference, a dict taken through the raw
blocks by a parser of its own; seven readers `layers/*.stream.py` (sharing
`layers/_stream.py`) read the result stamps, `verifier.phases` between two
results, the program's in-flight histogram and the `bench.stream`
annotations; `BENCHMARK.json` gained the configuration, the cell, the
seven metrics, and the cell's name under `inputs_per_s`. `rehearse.py`
runs the cell on a CPU at 3 blocks of 12 transactions.
"""

from __future__ import annotations

import bisect
import time
from typing import Dict, List, Optional

# A program that cannot stream blocks fails here, as the driver is
# imported: before a verifier is made or a shape compiled.
from bitcoinconsensus_tpu.models.validate import connect_block_stream

from ..harness import cell, chainref, counters, oracle, stats
from ..harness.tracer import annotate

ROLLBACKS = "consensus_stream_rollbacks_total"
_BACKGROUND_SPK = (b"\x76\xa9\x14", b"\x88\xac")  # P2PKH around 20 bytes of hash


def background_coins(n: int, seed: int):
    """Columns of `n` P2PKH coins the chain never touches, outpoints and
    key hashes from the seed, made in bulk."""
    import numpy as np

    rng = np.random.Generator(np.random.PCG64(seed))
    txids = np.frombuffer(rng.bytes(32 * n), dtype=np.uint8)
    head, tail = (np.frombuffer(b, dtype=np.uint8) for b in _BACKGROUND_SPK)
    spk = np.empty((n, len(head) + 20 + len(tail)), dtype=np.uint8)
    spk[:, : len(head)] = head
    spk[:, len(head) : len(head) + 20] = np.frombuffer(
        rng.bytes(20 * n), dtype=np.uint8).reshape(n, 20)
    spk[:, len(head) + 20 :] = tail
    return {
        "txids": txids, "ns": np.arange(n, dtype=np.int32) & 3,
        "values": rng.integers(100_000, 10_000_000, n, dtype=np.int64),
        "heights": np.ones(n, dtype=np.int32), "coinbases": np.zeros(n, dtype=np.int32),
        "spk_blob": spk.reshape(-1),
        "spk_offs": np.arange(n + 1, dtype=np.int64) * spk.shape[1],
    }


class Driver:
    def __init__(self, config: dict, traffic: dict, data: dict, seed: int,
                 control: Optional[str] = None, schedule_path: Optional[str] = None):
        self.config, self.traffic, self.data, self.seed = config, traffic, data, seed
        self.control = control
        self.depth = int(config["depth"])
        self.n_blocks, self.n_inputs = int(data["n_blocks"]), int(data["n_inputs"])
        self.pass_walls: List[float] = []
        self.first_result_s: List[float] = []  # first call to the first result, a pass
        self.block_gaps: List[float] = []  # between successive results inside a pass
        self.phases: List[dict] = []  # phase -> seconds, between successive results
        self.failed = 0
        self.notes: List[str] = []
        self.last_results = None
        self.applied = 0  # blocks the view holds beyond its funded state

    # -- set-up ---------------------------------------------------------

    def setup(self) -> None:
        from bitcoinconsensus_tpu import native_bridge
        from bitcoinconsensus_tpu.core.flags import height_to_flags

        d = self.data
        self.start = int(d["start_height"])
        self.flags = [height_to_flags(self.start + k, extended=True)
                      for k in range(self.n_blocks)]
        self.pow_limit = int(self.config["chain"]["pow_limit"], 16)
        self.verifier = cell.make_verifier(self.config)
        self.watch = cell.PathWatch(self.verifier, self.config["backend"])
        self.n_background = int(self.config["utxo_set"]["background_coins"])
        self.view = native_bridge.NativeCoinsView()
        self.view.add_coins_arrays(**background_coins(self.n_background, self.seed))
        self.view.add_coins_batch(d["coins"])
        self.funded_len = len(self.view)
        if self.funded_len != self.n_background + len(d["coins"]):
            self.notes.append("a background outpoint collides with a funded one")
        # The undo records the reset uses, made on the view itself: applied
        # block by block and taken back newest first, which has to leave the
        # view as it was, coin for coin.
        digest = self.view.digest()
        self.nblocks = [native_bridge.NativeBlock(raw) for raw in d["blocks"]]
        self.undos = [self.view.apply_block(nblk, self.start + k, undo=True)
                      for k, nblk in enumerate(self.nblocks)]
        self.applied = self.n_blocks
        self._reset_view()
        if (len(self.view), self.view.digest()) != (self.funded_len, digest):
            self.notes.append("apply and undo of the chain did not restore the view")
        # The corrupted stream first: it compiles the blocks' shape.
        self._corrupted_stream()
        # Then one whole pass as the window runs it, untimed.
        self._pass(record=False)

    def _stream(self, blocks, view, sig_cache, script_cache):
        return connect_block_stream(
            blocks, view, self.start, depth=self.depth, verifier=self.verifier,
            pow_limit=self.pow_limit, sig_cache=sig_cache, script_cache=script_cache,
        )

    def _reference(self, n_blocks: int) -> chainref.ChainRef:
        ref = chainref.ChainRef(self.data["coins"])
        for k in range(n_blocks):
            ref.apply(self.data["blocks"][k], self.start + k)
        return ref

    def _corrupted_stream(self) -> None:
        """The chain with one signature of block `bad.index` flipped, where
        the block behind it spends its outputs: every block before it ok,
        then that block rejected for exactly its victim, then the end, with
        the view as after the blocks before it and nothing in flight."""
        d, bad = self.data, self.data["bad"]
        at = bad["index"]
        blocks = list(d["blocks"])
        blocks[at] = bad["block"]
        victim = bad["victim"] + (1 if self.control == "truth-shift" else 0)
        rolled = counters.total(counters.snapshot([ROLLBACKS]), ROLLBACKS)
        results = list(self._stream(blocks, self.view, *cell.fresh_caches(self.config)))
        self.applied = sum(r.ok for r in results)
        rolled = counters.total(counters.snapshot([ROLLBACKS]), ROLLBACKS) - rolled
        last = results[-1]
        want = oracle.oracle_verdict(
            bad["tx"]["raw"], bad["victim"] - d["tx_start"][at][bad["tx"]["index"]],
            bad["tx"]["outs"], self.flags[at],
        )
        got = (oracle.as_triple(last.input_results[bad["victim"]])
               if last.input_results else None)
        differences = self._reference(at).differences(self.view, self.n_background)
        # The failed block's own apply, and that of each block begun behind it.
        undone = 1 + min(self.depth - 1, self.n_blocks - 1 - at)
        self.bad_stream = {
            "results": [r.ok for r in results], "reason": last.reason,
            "script_failures": last.script_failures, "victim": victim,
            "victim_verdict": got, "oracle_verdict": want,
            "view_differences": differences, "rollbacks": rolled,
            "in_flight_after": self.verifier._inflight.depth,
        }
        if ([r.ok for r in results] != [True] * at + [False]
                or last.reason != "block-validation-failed"
                or last.script_failures != [victim] or got != want or differences
                or rolled != undone or self.verifier._inflight.depth):
            self.notes.append(f"corrupted stream: {self.bad_stream}")

    # -- the loop ---------------------------------------------------------

    def _reset_view(self) -> None:
        for k in reversed(range(self.applied)):
            self.view.undo_block(self.nblocks[k], self.undos[k])
        self.applied = 0

    def _pass(self, record: bool = True) -> None:
        with annotate("reset"):
            self._reset_view()
            sig_cache, script_cache = cell.fresh_caches(self.config)
        phases = self.verifier.phases
        phases.reset()
        seen: Dict[str, float] = {}
        stamps: List[float] = []
        by_result: List[dict] = []
        results = []
        t0 = time.perf_counter()
        with annotate("stream"):
            for res in self._stream(self.data["blocks"], self.view, sig_cache, script_cache):
                stamps.append(time.perf_counter())
                results.append(res)
                now = {n: v["secs"] for n, v in phases.report().items()}
                by_result.append({n: s - seen.get(n, 0.0) for n, s in now.items()})
                seen = now
        self.applied = sum(r.ok for r in results)
        if record:
            with annotate("account"):
                self._account(t0, stamps, by_result, results)

    def _account(self, t0: float, stamps, by_result, results) -> None:
        ok = (len(results) == self.n_blocks and all(
            r.ok and r.input_results is not None
            and len(r.input_results) == self.n_inputs and all(x.ok for x in r.input_results)
            for r in results))
        if not ok:
            self.failed += 1
            if len(self.notes) < 5:
                self.notes.append(
                    f"pass {len(self.pass_walls) + self.failed}: {len(results)} results, "
                    f"ok={[r.ok for r in results]} reason={results[-1].reason!r} "
                    f"failures={results[-1].script_failures[:5]}"
                )
            return
        self.pass_walls.append(stamps[-1] - t0)
        self.first_result_s.append(stamps[0] - t0)
        self.block_gaps.extend(b - a for a, b in zip(stamps, stamps[1:]))
        self.phases.extend(by_result)
        self.last_results = results

    def run_window(self, seconds: float, tracer) -> None:
        self.watch.open()
        self.window_start = time.monotonic()
        with cell.armed(self.control, self.verifier, self.seed):
            while True:
                elapsed = time.monotonic() - self.window_start
                tracer.poll(elapsed)
                if elapsed >= seconds:
                    break
                self._pass()
        tracer.stop()
        self.window_s = time.monotonic() - self.window_start
        self.watch.close()

    # -- results ----------------------------------------------------------

    def verify(self) -> dict:
        d = self.data
        n, total = self.n_inputs, self.n_inputs * self.n_blocks
        got, items = {}, {}
        for k, res in enumerate(self.last_results or []):
            for i, r in enumerate(res.input_results):
                got[k * n + i] = oracle.as_triple(r)
        for j in oracle.sample_indices(total, [], int(self.config["oracle_sample"]), self.seed):
            k, i = divmod(j, n)
            t = bisect.bisect_right(d["tx_start"][k], i) - 1
            tx = d["txs"][k][t]
            items[j] = (tx["raw"], i - d["tx_start"][k][t], tx["outs"], self.flags[k])
        compared = oracle.compare(got, items, {j: True for j in range(total)})
        problems = list(self.notes) + self.watch.problems()
        rolled = counters.rose(self.watch.before, self.watch.after, ROLLBACKS)
        if rolled:
            problems.append(f"{rolled:g} speculative applies undone inside the window")
        # The view after the last pass against the plain chain reference.
        if self.applied == self.n_blocks:
            differences = self._reference(self.n_blocks).differences(self.view, self.n_background)
            if differences:
                problems.append(f"the view after the last pass: {differences}")
        passes = len(self.pass_walls) + self.failed
        return {
            "attempted": passes,
            "failed": self.failed,
            "compared": compared,
            "corrupted_block": self.bad_stream,
            "problems": problems,
            "correct": bool(self.pass_walls) and not self.failed
            and not compared["mismatches"] and not problems,
        }

    def end_to_end(self) -> Dict[str, float]:
        timed = sum(self.pass_walls)
        done = self.n_inputs * self.n_blocks * len(self.pass_walls)
        return {"inputs_per_s": done / timed if timed else 0.0}

    def layer_context(self) -> dict:
        return {
            "kind": "stream", "pass_walls_s": self.pass_walls, "block_gaps_s": self.block_gaps,
            "first_result_s": self.first_result_s, "phases": self.phases,
            "counters_before": self.watch.before, "counters_after": self.watch.after,
            "n_inputs": self.n_inputs, "n_blocks": self.n_blocks, "depth": self.depth,
        }

    def detail(self) -> dict:
        """Beside the metrics, for a reader of the line: the median
        milliseconds of every phase `verifier.phases` timed between two
        successive results, and the pass walls."""
        if not self.pass_walls:
            return {"passes": 0, "depth": self.depth, "window_s": self.window_s}
        names = sorted({n for rep in self.phases for n in rep})
        ms = [w * 1000.0 for w in self.pass_walls]
        return {"phase_ms_p50": {
            n: stats.median([rep.get(n, 0.0) for rep in self.phases]) * 1000.0
            for n in names
        }, "pass_ms": {
            "min": min(ms), "p50": stats.median(ms), "max": max(ms), "mean": sum(ms) / len(ms),
        }, "pass_walls_ms": [round(w, 3) for w in ms], "first_result_ms_p50": stats.median(self.first_result_s) * 1000.0,
            "block_ms_p50": stats.median(self.block_gaps) * 1000.0,
            "passes": len(ms), "depth": self.depth, "window_s": self.window_s}

    def close(self) -> None:
        pass
