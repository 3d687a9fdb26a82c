"""Rehearse a cell end to end on the CPU at a tiny size: no chip time.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse.py --workload <name> [--seed n] [--seconds s] [--trace 0|1] [--control kind]

The same `run_cell` as a real run, with the `rehearsal` overrides of the
cell's configuration and traffic files laid on top (tens of inputs, the
XLA rung, 64-lane shapes). It proves paths, arguments and control flow. It
prints no metric: a time from a CPU says nothing about the chip, and this
line cannot be mistaken for a result.
"""

from __future__ import annotations

import argparse
import json
import sys

from run import Refused, load_spec, run_cell  # noqa: E402  (same directory)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    from benchmarks.harness import chipguard

    dev = chipguard.device_info()
    if dev["platform"] == "tpu":
        print("rehearse.py is for the CPU; on a TPU use run.py", file=sys.stderr)
        return 2
    dev["count"] = 1
    try:
        spec = load_spec(args.workload, rehearsal=True)
        line = run_cell(spec, args.seed, args.seconds, bool(args.trace), dev,
                        control=args.control)
    except Refused as e:
        print(f"rehearsal refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps({
        "rehearsal": True, "not_a_result": "CPU run at a tiny size",
        "workload": args.workload, "correct": line["correct"],
        "attempted": line["attempted"], "failed": line["failed"],
        "would_report": sorted(line["metrics"]), "device": dev,
    }), flush=True)
    return 0 if line["correct"] or args.control else 1


if __name__ == "__main__":
    sys.exit(main())
