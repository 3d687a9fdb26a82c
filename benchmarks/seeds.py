"""Many seeds of one cell, sound and broken, in one process on the chip.

    python3 benchmarks/seeds.py --workload <name> --seeds 1,2,3 --seconds <s> [--controls fault-plan,lane-flip,truth-shift] [--control-seeds 3]

Every run of `run.py` is a new process and pays the whole set-up; to read
`correct` on a dozen seeds, and each control on three, at the cell's own
size without paying it twenty times, this drives `run_cell` once a seed in
one process (the compiled shapes are shared; everything else is made anew
a seed). It prints one line a run and a summary; it reports no `setup_s`,
and its timings are not the benchmark's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args()
    try:
        spec, dev = run.ready(args.workload)
    except run.Refused as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = [(s, None) for s in seeds]
    for control in filter(None, args.controls.split(",")):
        plan += [(s, control) for s in seeds[: args.control_seeds]]
    rows = []
    for seed, control in plan:
        line = run.run_cell(spec, seed, args.seconds, False, dev, control=control,
                            t_start=time.monotonic())
        rows.append({
            "seed": seed, "control": control, "correct": line["correct"],
            "attempted": line["attempted"], "failed": line["failed"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items() if k != "setup_s"},
        })
        print(json.dumps(rows[-1]), flush=True)
    sound = [r for r in rows if r["control"] is None]
    broken = [r for r in rows if r["control"] is not None]
    print(json.dumps({
        "workload": args.workload, "device": dev,
        "sound_correct": sum(r["correct"] for r in sound), "sound_runs": len(sound),
        "controls_not_correct": sum(not r["correct"] for r in broken), "control_runs": len(broken),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
