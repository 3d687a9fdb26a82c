"""Count the integer operations the verify kernel performs for one lane.

A walker over the kernel's jaxpr, kept with the benchmark so that the
yardstick of `kernel_gops.connect` cannot move with the program: every
arithmetic, logic, compare and select primitive counts its output
elements; loops multiply by their trip counts (`scan` length, the static
bound of a lowered `fori_loop`), a `pallas_call` body by its grid; calls
are entered. Loads, stores, shape and type moves are counted apart. Copied
from the program's `obs/perf.walk_jaxpr`, which is sound as a count (what
was unsound there was the peak it was divided by).

    JAX_PLATFORMS=cpu python3 -m benchmarks.harness.opcount   # rewrites opcount/verify_tiles.json
"""

from __future__ import annotations

import json
import os
from typing import Tuple

COMPUTE = {
    "add", "sub", "mul", "and", "or", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "select_n", "eq", "ne",
    "lt", "le", "gt", "ge", "min", "max", "neg", "abs", "rem", "not",
    "reduce_and", "reduce_or", "reduce_sum", "reduce_min", "reduce_max",
}
MOVE = {"convert_element_type", "broadcast_in_dim", "concatenate", "iota"}


def while_trips(eqn) -> int:
    """Trip count of a lowered `fori_loop`: the largest scalar integer
    literal among the `while`'s operands is its static upper bound."""
    from jax.extend.core import Literal

    trips = 1
    for v in eqn.invars:
        if isinstance(v, Literal) and getattr(v.aval, "shape", None) == ():
            try:
                trips = max(trips, int(v.val))
            except (TypeError, ValueError):
                pass
    return trips


def walk(jaxpr) -> Tuple[int, int]:
    """(compute, move) element operations of one jaxpr."""
    import numpy as np

    comp = move = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "while":
            c, m = walk(eqn.params["body_jaxpr"].jaxpr)
            t = while_trips(eqn)
            comp, move = comp + c * t, move + m * t
            continue
        if prim == "scan":
            c, m = walk(eqn.params["jaxpr"].jaxpr)
            n = eqn.params["length"]
            comp, move = comp + c * n, move + m * n
            continue
        if prim == "pallas_call":
            # the body is traced for one grid step
            c, m = walk(eqn.params["jaxpr"])
            steps = int(np.prod(eqn.params["grid_mapping"].grid))
            comp, move = comp + c * steps, move + m * steps
            continue
        inner = False
        for p in eqn.params.values():
            sub = getattr(p, "jaxpr", p if hasattr(p, "eqns") else None)
            if sub is not None:
                c, m = walk(sub)
                comp, move, inner = comp + c, move + m, True
        if inner:
            continue
        outs = sum(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        if prim in MOVE:
            move += outs
        elif prim in COMPUTE:
            comp += outs
    return comp, move


def count_verify_tiles(lanes: int) -> dict:
    """Operations of `ops.pallas_kernel.verify_tiles` at `lanes` padded lanes."""
    import jax
    import jax.numpy as jnp
    from bitcoinconsensus_tpu.ops.pallas_kernel import LANE_TILE, verify_tiles

    S = jax.ShapeDtypeStruct
    args = (
        S((lanes, 4, 32), jnp.uint8), S((lanes,), jnp.int32), S((lanes,), jnp.int32),
        S((lanes,), jnp.int32), S((lanes,), jnp.int32), S((lanes,), jnp.int32),
        S((lanes,), jnp.bool_),
    )
    comp, move = walk(jax.make_jaxpr(verify_tiles)(*args).jaxpr)
    grid = lanes // LANE_TILE
    return {
        "lanes": lanes, "grid": grid,
        "int_ops": comp, "move_ops": move,
        "int_ops_per_lane": comp / lanes, "move_ops_per_lane": move / lanes,
    }


def main() -> None:
    import jax

    out = {
        "what": "element operations of ops/pallas_kernel.verify_tiles per padded lane, "
                "counted from its jaxpr by benchmarks/harness/opcount.py",
        "counted_at": "PR 24, on the CPU (a count needs no chip)",
        "jax": jax.__version__,
        "by_lanes": [count_verify_tiles(n) for n in (512, 8192)],
    }
    out["int_ops_per_lane"] = out["by_lanes"][-1]["int_ops_per_lane"]
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "opcount", "verify_tiles.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
