"""What one one-chip dispatch costs the host, piece by piece, on the live TPU.

One 8,192-lane dispatch of `tip-block.cold`'s size (7,800 real lanes out of
read-only buffers, as the native arena hands them over), repeated, every
piece on the host's clock and the kernel waited for in between, so that what
is timed is what the host pays whatever the kernel takes:

- `seven`: the dispatch as it travelled up to PR 42, rebuilt here from the
  program's own pieces: a copy of the seven arrays, the sentinels, the
  kernel called on seven host arrays (seven puts), the verdict checksum as a
  second program, four pulls, the guards.
- `packed`: the dispatch as `TpuSecpVerifier` sends it: one pass into one
  packed buffer, the sentinels, one put, one program, its host copy asked
  for at launch, one pull, the guards.
- `pieces` (not by default): the packed dispatch piece by piece.

Usage (chip only): `python scripts/dispatch_split.py [--lanes 7800]
[--reps 40] [--shape seven packed] [--root CHECKOUT]`. One JSON line a
shape: the median and the quartiles of every piece in ms, and their sums for
the launch side and the settle side. `--root` imports the package of another
checkout of this repository (a `git archive` of a commit to compare with):
`packed` then times that commit's own prepare, launch and settle callbacks.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np


def arena_lanes(n, padded):
    """The kernel's seven arguments for `n` real lanes padded to `padded`,
    read-only as the native arena's are: 63 mixed checks through the native
    prep, tiled (a kernel's time does not depend on what a lane holds)."""
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu import native_bridge

    base = native_bridge.prep_pack(ge._example_checks(63), 63)
    pad = native_bridge.prep_pack([], padded - n)
    reps = -(-n // 63)
    out = []
    for a, p in zip(base, pad):
        a = np.concatenate([np.concatenate([a] * reps)[:n], p])
        a.flags.writeable = False
        out.append(a)
    return tuple(out)


class Clock:
    def __init__(self):
        self.samples = {}

    def __call__(self, name):
        return _Lap(self.samples.setdefault(name, []))

    def report(self, sums):
        out = {}
        for name, xs in self.samples.items():
            q = statistics.quantiles(xs, n=4)
            out[name] = [round(1e3 * v, 4) for v in (q[1], q[0], q[2])]
        for total, names in sums.items():
            per_rep = [sum(v) for v in zip(*(self.samples[n] for n in names))]
            q = statistics.quantiles(per_rep, n=4)
            out[total] = [round(1e3 * v, 4) for v in (q[1], q[0], q[2])]
        return out


class _Lap:
    def __init__(self, into):
        self.into = into

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.into.append(time.perf_counter() - self.t0)


def seven(args, n, reps):
    """The seven-argument, two-program, four-pull dispatch."""
    import jax

    from bitcoinconsensus_tpu.crypto.jax_backend import _verdict_checksum
    from bitcoinconsensus_tpu.ops.pallas_kernel import verify_tiles
    from bitcoinconsensus_tpu.resilience import guards as G

    checksum = jax.jit(_verdict_checksum)
    padded = int(args[0].shape[0])
    clock = Clock()
    for rep in range(reps + 3):
        if rep == 3:
            clock.samples.clear()  # the first calls compile
        with clock("copy"):
            mine = tuple(np.array(a) for a in args)
        with clock("sentinels"):
            sset = G.install_sentinels(mine, n)
        with clock("kernel_call"):
            ok_d, needs_d = verify_tiles(*mine)
        with clock("checksum_call"):
            aux = checksum(ok_d)
        jax.block_until_ready((ok_d, needs_d, aux))  # the kernel: not the host's
        with clock("pull_ok"):
            ok = np.asarray(ok_d)
        with clock("pull_needs"):
            needs = np.asarray(needs_d)
        with clock("pull_count"):
            count = int(np.asarray(aux[0]))
        with clock("pull_wsum"):
            wsum = int(np.asarray(aux[1]))
        with clock("guards"):
            ok = G.validate_verdict(ok, padded, "split")
            needs = G.validate_verdict(needs, padded, "split")
            G.check_sentinels(sset, ok, needs, "split")
            G.check_checksum((count, wsum), ok, "split")
    return clock.report({
        "launch": ["copy", "sentinels", "kernel_call", "checksum_call"],
        "settle": ["pull_ok", "pull_needs", "pull_count", "pull_wsum", "guards"],
    })


def packed(args, n, reps):
    """The verifier's own dispatch, through its own callbacks."""
    import jax

    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier
    from bitcoinconsensus_tpu.resilience.inflight import Ticket

    v = TpuSecpVerifier()
    clock = Clock()
    for rep in range(reps + 3):
        if rep == 3:
            clock.samples.clear()
        with clock("prepare"):
            targs, sset = v._prepare_ticket(args, n)
        with clock("launch_call"):
            result, aux = v._launch_ticket(targs, n, "pallas", sset)
        jax.block_until_ready(result)
        ticket = Ticket(targs, n, "pallas", False, 0.0, 0.0, rep)
        ticket.sset, ticket.result, ticket.aux = sset, result, aux
        with clock("materialize"):
            v._materialize_guarded(ticket)
    out = clock.report({"launch": ["prepare", "launch_call"], "settle": ["materialize"]})
    out["phases"] = {k: round(1e3 * r["secs"] / max(r["calls"], 1), 4)
                     for k, r in v.phases.report().items()}
    return out


def pieces(args, n, reps):
    """The packed dispatch rebuilt from its pieces, the put made both ways:
    `put` + `call` (an explicit `device_put`, then the program on the device
    array) against `call_host` (the program called on the host buffer, its
    put made inside the call)."""
    import jax

    from bitcoinconsensus_tpu.crypto import lane_wire as W
    from bitcoinconsensus_tpu.crypto.jax_backend import _packed_program
    from bitcoinconsensus_tpu.resilience import guards as G

    program = _packed_program("pallas")
    padded = int(args[0].shape[0])
    clock = Clock()
    for rep in range(reps + 3):
        if rep == 3:
            clock.samples.clear()
        with clock("pack"):
            packed = W.pack_lanes(args, n)
        with clock("sentinels"):
            sset = G.install_sentinels(W._lane_views(packed)[:-1], n)
        with clock("put"):
            on_device = jax.device_put(packed)
        with clock("call"):
            result = program(on_device)
        with clock("copy_async"):
            result.copy_to_host_async()
        jax.block_until_ready(result)
        with clock("pull"):
            raw = np.asarray(result)
        with clock("split_guards"):
            ok, needs, tail = W.split_result(raw, 1, W.CHECKSUM_TAIL)
            ok = G.validate_verdict(ok, padded, "split")
            needs = G.validate_verdict(needs, padded, "split")
            G.check_sentinels(sset, ok, needs, "split")
            G.check_checksum((int(tail[0, 0]), int(tail[0, 1])), ok, "split")
        with clock("call_host"):
            other = program(packed)
        with clock("copy_async_host"):
            other.copy_to_host_async()
        jax.block_until_ready(other)
        with clock("pull_host"):
            np.asarray(other)
    return clock.report({
        "launch": ["pack", "sentinels", "put", "call", "copy_async"],
        "launch_host": ["pack", "sentinels", "call_host", "copy_async_host"],
        "settle": ["pull", "split_guards"],
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=7800)
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--shape", nargs="+", default=["seven", "packed"],
                    choices=["seven", "packed", "pieces"])
    ap.add_argument("--root", default=__file__.rsplit("/", 2)[0])
    opts = ap.parse_args()
    sys.path.insert(0, os.path.abspath(opts.root))
    import chip_guard
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    dev = chip_guard.require_tpu()
    padded = TpuSecpVerifier().pad(opts.lanes)
    args = arena_lanes(opts.lanes, padded)
    for shape in opts.shape:
        ms = {"seven": seven, "packed": packed, "pieces": pieces}[shape](args, opts.lanes, opts.reps)
        print(json.dumps({"shape": shape, "root": opts.root, "lanes": opts.lanes, "padded": padded,
                          "reps": opts.reps, "ms_median_q1_q3": ms, "device": dev}))


if __name__ == "__main__":
    main()
