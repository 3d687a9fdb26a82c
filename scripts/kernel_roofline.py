"""Roofline accounting for the pallas verify kernel: measured throughput
vs the chip's integer-op ceiling, with the op count taken from the TRACED
program (no hand-waved estimates).

Thin wrapper over `bitcoinconsensus_tpu.obs.perf` (the op-walk, timing,
and provenance helpers live there and are shared with
`scripts/consensus_perf.py`):

- Op count: walk the jaxpr of ONE `verify_tiles` tile (the pallas grid
  runs B/tile instances of the same program; fori trip counts recovered
  from the carry-init literals) and sum arithmetic/logic/select/compare
  element counts — the int32 work the VPU actually executes.
- Throughput: min-of-N device-resident timing of the full compiled grid.
- Ceiling: TPU v5e VPU = (8, 128) vector unit x 4 ALUs at ~0.94 GHz
  ~= 3.85e12 int32 ops/s (MXU FLOPs are irrelevant — VPU-bound kernel).

Writes KERNEL_r{N}.json when invoked with --out; every artifact carries
a provenance block, so the regression gate can refuse cross-hardware
comparisons instead of trusting filenames.
"""

import json
import sys
from functools import partial

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import numpy as np
import jax

N = 10240
REPS = 15


def main():
    from bitcoinconsensus_tpu.obs import perf
    from bitcoinconsensus_tpu.ops.pallas_kernel import LANE_TILE, verify_tiles

    rng = np.random.default_rng(3)
    fields = rng.integers(0, 256, size=(N, 4, 32), dtype=np.uint8)
    w = np.zeros(N, np.int32)
    par = np.full(N, -1, np.int32)
    h2 = np.zeros(N, np.int32)
    n1 = np.zeros(N, np.int32)
    n2 = np.zeros(N, np.int32)
    v = np.ones(N, bool)

    dargs = tuple(jax.device_put(x) for x in (fields, w, par, h2, n1, n2, v))

    # Trace ONE tile's kernel body via interpret-mode jaxpr; time the full
    # compiled grid. kernel_report scales per-lane ops by the trace's lane
    # count, so the one-tile trace prices every grid instance.
    T = LANE_TILE
    rep = perf.kernel_report(
        "verify_tiles_pallas",
        verify_tiles, dargs,
        trace_fn=partial(verify_tiles, tile=T, interpret=True),
        trace_args=tuple(a[:T] for a in dargs),
        reps=REPS,
    )

    # Keep the historical KERNEL_r{N}.json key set alongside the
    # shared-module fields.
    out = dict(rep)
    out["tile"] = T
    out["note"] = (
        "ops counted from the traced kernel jaxpr (arith/logic/select/"
        "compare element counts); peak assumes v5e VPU 8x128x4 ALUs at "
        "0.94 GHz; min-of-N timing"
    )
    out["provenance"] = perf.provenance()
    print(json.dumps(out, indent=2))
    if "--out" in sys.argv:
        path = sys.argv[sys.argv.index("--out") + 1]
        with open(path, "w") as fh:
            json.dump(out, fh, indent=2)
            fh.write("\n")


if __name__ == "__main__":
    main()
