"""Open-loop client of the served cell; a process of its own.

It never imports JAX or the program: it reads the generated schedule,
encodes the frames `harness/wire.py` documents, opens a few sessions, sends
each transaction's REQ frames back to back at its due time whether or not
earlier ones were answered, and keeps many requests outstanding. It times
from the due time and reports how late it sent. Lines on stdout: `READY`,
`WINDOW_START`, `WINDOW_END`, each with its own monotonic clock; the
results go to the file named by `--out`.
"""

from __future__ import annotations

import argparse
import asyncio
import os
import pickle
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.harness import wire  # noqa: E402


def say(word: str) -> None:
    print(f"{word} {time.monotonic():.6f}", flush=True)


async def reader(stream, on_frame) -> None:
    try:
        while True:
            hdr = await stream.readexactly(wire.HEADER_LEN)
            ftype, ln = wire.decode_header(hdr)
            on_frame(ftype, await stream.readexactly(ln), time.monotonic())
    except (asyncio.IncompleteReadError, ConnectionError):
        return


async def run(args) -> dict:
    with open(args.schedule, "rb") as f:
        data = pickle.load(f)
    flags = data["flags"]
    requests = []
    for stretch in ("warm", "window"):
        for req in data[stretch]["requests"]:
            txs = data[stretch]["txs"]
            frames = b"".join(
                wire.encode_request(
                    rid, req["tenant"], txs[req["tx"]]["raw"], i, flags,
                    spent_outputs=txs[req["tx"]]["outs"],
                )
                for i, rid in enumerate(req["rids"])
            )
            requests.append({
                "due": req["due"], "frames": frames, "rids": req["rids"],
                "session": req["session"] % args.sessions,
                "in_window": stretch == "window",
            })
    requests.sort(key=lambda r: r["due"])
    warm_s, seconds = data["warmup_s"], data["seconds"]

    verdicts, errors, done_at = {}, {}, {}
    left = {}  # rid -> request index, while unanswered
    open_rids = [0] * len(requests)

    def on_frame(ftype, payload, now):
        if ftype == wire.FRAME_RESP:
            rid, ok, err, se = wire.decode_response(payload)
            verdicts[rid] = (ok, err, se)
        elif ftype == wire.FRAME_ERR:
            rid, code, reason = wire.decode_error(payload)
            errors[rid] = (code, reason)
        else:
            return
        k = left.pop(rid, None)
        if k is not None:
            open_rids[k] -= 1
            if not open_rids[k]:
                done_at[k] = now

    streams, tasks = [], []
    for _ in range(args.sessions):
        r, w = await asyncio.open_connection("127.0.0.1", args.port)
        streams.append((r, w))
        tasks.append(asyncio.ensure_future(reader(r, on_frame)))
    say("READY")
    t0 = time.monotonic()
    sent_at = [None] * len(requests)
    started = False
    for k, req in enumerate(requests):
        if not started and req["in_window"]:
            # the window opens on the clock, not with its first arrival
            delay = t0 + warm_s - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            say("WINDOW_START")
            started = True
        delay = t0 + req["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        for rid in req["rids"]:
            left[rid] = k
        open_rids[k] = len(req["rids"])
        sent_at[k] = time.monotonic()
        w = streams[req["session"]][1]
        w.write(req["frames"])
        # no drain(): an open loop does not wait for the server to read
    delay = t0 + warm_s + seconds - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    say("WINDOW_END")
    deadline = time.monotonic() + args.drain_s
    while left and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    for _, w in streams:
        w.close()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return {
        "t0": t0,
        "requests": [
            {"due": t0 + r["due"], "sent": sent_at[k], "done": done_at.get(k),
             "rids": r["rids"], "in_window": r["in_window"]}
            for k, r in enumerate(requests)
        ],
        "verdicts": verdicts, "errors": errors,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--sessions", type=int, required=True)
    ap.add_argument("--drain-s", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = asyncio.run(run(args))
    tmp = args.out + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(out, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
