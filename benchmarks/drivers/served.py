"""Open loop against the served path: `IngressServer` in front of
`VerifyServer`, in this process, which holds the chip; the load comes from
`served_client.py` in a child process that never touches JAX. The
client's warm-up stretch (the traffic file's `warmup_s` of the same
traffic) runs through the server before the window opens and counts as
set-up: a fresh process serves its first seconds slowly (PERF.md).
"""

from __future__ import annotations

import gc
import os
import pickle
import queue
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from ..harness import cell, counters, oracle, stats
from ..harness.tracer import annotate

_CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "served_client.py")


class Driver:
    def __init__(self, config: dict, traffic: dict, data: dict, seed: int,
                 control: Optional[str] = None, schedule_path: Optional[str] = None):
        self.config, self.traffic, self.data, self.seed = config, traffic, data, seed
        self.control = control
        self.schedule_path = schedule_path
        self.notes: List[str] = []
        self.client: Optional[dict] = None
        self.proc: Optional[subprocess.Popen] = None

    def setup(self) -> None:
        from bitcoinconsensus_tpu.models.batch import BatchItem, verify_batch
        from bitcoinconsensus_tpu.serving import IngressServer, VerifyServer

        d = self.data
        self.verifier = cell.make_verifier(self.config)
        self.watch = cell.PathWatch(self.verifier, self.config["backend"])
        # The cell's one shape, compiled by a direct call BEFORE any server
        # exists, so the compile never sits in a server's SLO window.
        warm = d["warm"]
        first = [
            BatchItem(tx["raw"], i, d["flags"], spent_outputs=tx["outs"])
            for tx in warm["txs"][: int(self.traffic["warm_call_txs"])]
            for i in range(len(tx["outs"]))
        ]
        got = verify_batch(first, self.verifier, *cell.fresh_caches(self.config))
        if len(got) != len(first):
            self.notes.append("warm-up call: results and items differ in number")
        sig_cache, script_cache = cell.fresh_caches(self.config)
        self.server = VerifyServer(
            self.verifier, sig_cache, script_cache, **self.config["server"]
        ).start()
        self.ingress = IngressServer(self.server, **self.config["ingress"]).start()
        self.out_path = f"{self.schedule_path}.{os.getpid()}.result"
        # Set-up (imports, the traffic this benchmark loaded, the compile)
        # leaves CPython a full collection owing, and over the JAX runtime's
        # heap that takes half a second (PR 24 saw 546 and 576 ms, once a
        # process, 12-14 s into serving). Pay it here, in set-up, not at a
        # random moment of the window where it decides the p95.
        gc.collect()
        self.proc = subprocess.Popen(
            [sys.executable, _CLIENT, "--port", str(self.ingress.port),
             "--schedule", self.schedule_path,
             "--sessions", str(self.traffic["sessions"]),
             "--drain-s", str(self.traffic["drain_s"]), "--out", self.out_path],
            stdout=subprocess.PIPE, text=True,
            env={k: v for k, v in os.environ.items() if k != "BENCH_RUN"},
        )
        self.lines: "queue.Queue[str]" = queue.Queue()
        self._pump = threading.Thread(target=self._read_lines, daemon=True)
        self._pump.start()
        # The client's warm-up stretch runs through the server; the window
        # opens when the client says so.
        self._await("WINDOW_START", float(self.traffic["warmup_s"]) + 120.0)

    def _read_lines(self) -> None:
        for line in self.proc.stdout:
            self.lines.put(line.strip())
        self.lines.put("EOF")

    def _await(self, word: str, timeout: float, poll=None) -> None:
        deadline = time.monotonic() + timeout
        while True:
            if poll:
                poll()
            try:
                # the program's own threads carry no annotation yet: an idle
                # gap of the device reads as this thread's wait
                with annotate("serve_wait"):
                    line = self.lines.get(timeout=0.02)
            except queue.Empty:
                if time.monotonic() > deadline:
                    raise RuntimeError(f"the client never said {word}")
                continue
            if line.startswith(word):
                return
            if line == "EOF":
                raise RuntimeError(
                    f"the client ended (code {self.proc.wait()}) before {word}"
                )

    def run_window(self, seconds: float, tracer) -> None:
        self.watch.open()
        self.window_start = time.monotonic()
        with cell.armed(self.control, self.verifier, self.seed):
            self._await(
                "WINDOW_END", seconds + 60.0,
                poll=lambda: tracer.poll(time.monotonic() - self.window_start),
            )
        tracer.stop()
        self.window_s = time.monotonic() - self.window_start
        self.watch.close()
        code = self.proc.wait(timeout=float(self.traffic["drain_s"]) + 60.0)
        self._pump.join(10)
        self.ingress.close(drain=True)
        self.server.close(drain=True)
        if code != 0:
            raise RuntimeError(f"the client exited with code {code}")
        with open(self.out_path, "rb") as f:
            self.client = pickle.load(f)
        os.remove(self.out_path)
        self.window_requests = [r for r in self.client["requests"] if r["in_window"]]

    def verify(self) -> dict:
        win = self.data["window"]
        truth = dict(win["truth"])
        if self.control == "truth-shift":
            rids = sorted(truth)
            truth = {r: truth[rids[(k + 1) % len(rids)]] for k, r in enumerate(rids)}
        from bitcoinconsensus_tpu.api import Error

        verdicts, errors = self.client["verdicts"], self.client["errors"]
        # An explicit ERR_OVERLOADED frame is the guarantee's other lawful
        # ending: the request failed, and no verdict was wrong. Any other
        # ERR frame, or no frame at all, is compared and found wanting.
        shed = {r for r, (code, _) in errors.items() if code == int(Error.ERR_OVERLOADED)}
        built_ok = {rid: not t["corrupted"] for rid, t in truth.items() if rid not in shed}
        got = {rid: verdicts.get(rid) for rid in built_ok}
        rids = sorted(truth)
        bad_rids = [k for k, r in enumerate(rids) if truth[r]["corrupted"]]
        items = {}
        for k in oracle.sample_indices(
            len(rids), bad_rids, int(self.config["oracle_sample"]), self.seed
        ):
            t = win["truth"][rids[k]]  # the oracle always gets the real input
            tx = win["txs"][t["tx"]]
            if rids[k] not in shed:
                items[rids[k]] = (tx["raw"], t["input"], tx["outs"], self.data["flags"])
        compared = oracle.compare(got, items, built_ok)
        wrong = set(shed)
        for rid, want in built_ok.items():
            if got[rid] is None or got[rid][0] != want:
                wrong.add(rid)
        self.failed_requests = [
            r["done"] is None or any(rid in wrong for rid in r["rids"])
            for r in self.window_requests
        ]
        problems = list(self.notes) + self.watch.problems()
        if self.server.pending:
            problems.append(f"server.pending == {self.server.pending} after close")
        return {
            "attempted": len(self.failed_requests),
            "failed": sum(self.failed_requests),
            "error_frames": len(errors), "shed_inputs": len(shed),
            "compared": compared,
            "problems": problems,
            "correct": bool(self.failed_requests) and not compared["mismatches"]
            and not problems,
        }

    def latencies_ms(self) -> List[float]:
        """Due time to last verdict frame of every request of the window; a
        failed, shed or unanswered one reads window + drain, over any limit."""
        reqs = self.window_requests
        fail_ms = (self.data["seconds"] + float(self.traffic["drain_s"])) * 1000.0
        return stats.request_latencies_ms(
            [r["due"] for r in reqs], [r["done"] for r in reqs],
            self.failed_requests, fail_ms,
        )

    def end_to_end(self) -> Dict[str, float]:
        reqs = self.window_requests
        lat = self.latencies_ms()
        answered = sum(
            len(r["rids"]) for r, bad in zip(reqs, self.failed_requests) if not bad
        )
        return {
            "request_ms_p50": stats.percentile(lat, 50.0),
            "request_ms_p95": stats.percentile(lat, 95.0),
            "inputs_per_s": answered / self.data["seconds"],
        }

    def layer_context(self) -> dict:
        reqs = self.window_requests
        return {
            "kind": "serve", "latency_ms": self.latencies_ms(),
            "lag_ms": [(r["sent"] - r["due"]) * 1000.0 for r in reqs if r["sent"] is not None],
            "counters_before": self.watch.before, "counters_after": self.watch.after,
            "requests": len(reqs),
        }

    def detail(self) -> dict:
        b, a = self.watch.before, self.watch.after
        return {
            "requests": len(self.window_requests),
            "batches": counters.rose(b, a, "consensus_serving_batches_total"),
            "dispatches": counters.rose(b, a, "consensus_dispatch_total"),
            "admitted_inputs": counters.rose(b, a, "consensus_serving_admitted_total"),
            "window_s": self.window_s,
        }

    def close(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        for part in ("ingress", "server"):
            obj = getattr(self, part, None)
            if obj is not None:
                obj.close(drain=False)
