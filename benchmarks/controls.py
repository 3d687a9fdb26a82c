"""Show that a cell's check can fail: run it with the timed path broken.

    python3 benchmarks/controls.py --control <kind> --workload <name> --seed <n> --seconds <s>

The same run as `run.py`, on the chip at the cell's own size, with one of
`harness/cell.CONTROLS` switched on. Each must end `correct: false`:

- `fault-plan`: the program's own seeded fault plan flips one verdict at
  the settle seam; the guards catch it, so the run is not a clean run of
  the chip path.
- `lane-flip`: the verdicts of the window's first chunk are inverted after
  the guards, where the verifier hands them to the driver: answers altered
  where they are produced.
- `truth-shift`: the table of verdicts by construction is shifted by one
  input.

This system is integer-exact and states no precision to lower, so the
control breaks a guarantee the configuration states. Not part of a check's
runs; the results of PR 24's are in PERF.md.
"""

from __future__ import annotations

import argparse
import sys

import run
from benchmarks.harness.cell import CONTROLS


def main() -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--control", required=True, choices=CONTROLS)
    args, rest = ap.parse_known_args()
    return run.main(rest, control=args.control)


if __name__ == "__main__":
    sys.exit(main())
