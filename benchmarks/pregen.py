"""Build a cell's traffic for some seeds ahead of its runs (no chip, no JAX
device work): later runs in this checkout load it from `.bench_cache/`.

    python3 benchmarks/pregen.py --workload <name> --seeds 1,2,3 --seconds 10 [--rates 150,300]
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default=None, help="for sweep.py: tx/s values to build instead of the file's rate")
    args = ap.parse_args()
    spec = run.load_spec(args.workload)
    rates = [float(r) for r in args.rates.split(",")] if args.rates else [None]
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            traffic = spec["traffic"] if rate is None else dict(spec["traffic"], rate_tx_per_s=rate)
            _, how, _ = run.build_traffic(spec["config"], traffic, seed, args.seconds)
            print(json.dumps({"seed": seed, "rate": rate, **how}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
