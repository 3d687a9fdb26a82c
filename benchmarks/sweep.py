"""Find the knee of a served cell once: rising rates, one process.

    python3 benchmarks/sweep.py --workload <name> --seed <n> --seconds 10 --rates 150,300,...

Each rate runs the cell's own driver for `--seconds` against a fresh server
and fresh caches (one verifier's compiled shape serves all), with the
traffic file's `rate_tx_per_s` replaced. The knee is the highest rate with
no shed, completions within 1 % of what was offered and p95 under the
configuration's SLO. The cell's rate is then written into its traffic file
as a number; `run.py` never searches. PR 24's table is in PERF.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import run
from benchmarks.harness import counters
from benchmarks.harness.stats import percentile
from benchmarks.harness.tracer import Tracer


def one_rate(spec: dict, rate: float, seed: int, seconds: float) -> dict:
    config = spec["config"]
    traffic = dict(spec["traffic"], rate_tx_per_s=rate)
    driver, how = run.build_driver(config, traffic, seed, seconds)
    try:
        driver.setup()
        driver.run_window(seconds, Tracer(False, "", seconds))
        verdict = driver.verify()
        e2e = driver.end_to_end()
        ctx = driver.layer_context()
    finally:
        driver.close()
    b, a = ctx["counters_before"], ctx["counters_after"]
    reqs = driver.window_requests
    inputs = sum(len(r["rids"]) for r in reqs)
    done = sum(1 for r, bad in zip(reqs, driver.failed_requests) if not bad)
    slo_ms = float(config["server"]["slo_deadline_s"]) * 1000.0
    row = {
        "rate_tx_per_s": rate,
        "offered_tx": len(reqs), "offered_inputs_per_s": inputs / seconds,
        "completed_tx": done, "completed_share": done / len(reqs),
        "shed": counters.rose(b, a, "consensus_serving_shed_total"),
        "request_ms_p50": e2e["request_ms_p50"], "request_ms_p95": e2e["request_ms_p95"],
        "lag_ms_p95": percentile(ctx["lag_ms"], 95.0),
        "batches": counters.rose(b, a, "consensus_serving_batches_total"),
        "dispatches": counters.rose(b, a, "consensus_dispatch_total"),
        "correct": verdict["correct"], "problems": verdict["problems"][:2],
        "traffic_from_cache": how["from_cache"],
    }
    row["sustained"] = bool(
        not row["shed"] and row["completed_share"] >= 0.99
        and row["request_ms_p95"] < slo_ms and row["correct"]
    )
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated tx/s, rising")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    try:
        spec, dev = run.ready(args.workload)
    except run.Refused as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 2
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        rows.append(one_rate(spec, rate, args.seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    sustained = [r["rate_tx_per_s"] for r in rows if r["sustained"]]
    out = {"workload": args.workload, "seed": args.seed, "seconds": args.seconds,
           "device": dev, "rows": rows, "knee_tx_per_s": max(sustained, default=None)}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"knee_tx_per_s": out["knee_tx_per_s"], "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
