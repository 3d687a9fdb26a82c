"""Achieved integer rate of the verify kernel in a connect: the yardstick
count of operations a lane (benchmarks/opcount/verify_tiles.json, counted
by harness/opcount.py) times the padded lanes a connect dispatches, over
`kernel_ms.connect`. A rate, not a share: no int32 peak is published."""

import json
import os

from benchmarks.harness.stats import median
from benchmarks.layers._trace import kernel_ms_per

_COUNT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "opcount", "verify_tiles.json")


def read(ctx):
    ms = kernel_ms_per(ctx, "bench.connect", None)
    d = ctx["driver"]
    if not ms or d.get("kind") != "connect":
        return None
    with open(_COUNT) as f:
        ops_per_lane = json.load(f)["int_ops_per_lane"]
    padded = median([x["consensus_dispatch_padded_lanes_total"] for x in d["deltas"]])
    return ops_per_lane * padded / (ms / 1000.0) / 1e9
