"""Block-replay bench (BASELINE config 5) — standalone entry point.

Builds a ~4k-sigop synthetic block (mixed P2WPKH / P2TR / P2WSH-2of3,
the `bench/checkblock.cpp:17-45` role) and times `connect_block` end to
end: context-free checks, UTXO/value/sigop accounting, and one batched
device dispatch for every input's signature algebra. Prints one JSON
line; the full multi-config picture lives in bench_configs.py.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from bench_configs import bench_block_replay  # noqa: E402

    sys.path.insert(0, os.path.dirname(__file__))
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    # ONE dispatch for the whole block: 5.6k checks (the 4.8k real ones
    # plus speculative multisig pairings) ride one shape, and
    # pad_step=2048 trims that shape to 6144 instead of 8192.
    verifier = TpuSecpVerifier(min_batch=512, chunk=8192, pad_step=2048)
    secs, n_inputs, n_txs = bench_block_replay(verifier)
    print(
        json.dumps(
            {
                "metric": "block_replay_wall",
                "value": round(secs * 1000, 1),
                "unit": "ms",
                "inputs": n_inputs,
                "txs": n_txs,
                "target_ms": 100.0,
            }
        )
    )


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(__file__))
    main()
