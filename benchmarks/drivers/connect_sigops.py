"""The `connect` driver, held to what a block at the sigop limit adds.

The loop, the timing, the corrupted block and the oracle comparison are
`drivers/connect.py`'s, unchanged. On top, `correct` needs:

- `ConnectResult.sigop_cost` of every timed connect equal to the plain
  reference's count (`harness/sigopref.py`, from the raw transactions and
  the spent outputs alone) and to the configuration's figure;
- every sampled input's verdict equal to the reference's own key walk over
  the benchmark's own curve code, beside the three ways `connect` compares;
- the signature cache success-only: after every timed connect it holds
  one entry an input (the pairing that verified). Inverting a chunk's
  verdicts changes no input's verdict here (19 of its 20 pairings then
  read true and the walk stops at the first), but it fills the cache with
  pairings that failed, which is how the `lane-flip` control shows;
- no input resolved by the exact host fallback inside the window.

The number of lanes is reported (`detail.pairings`), not required: a
lawful short cut must stay possible.
"""

from __future__ import annotations

import bisect

from ..harness import counters, oracle, sigopref
from . import connect


class Driver(connect.Driver):
    def setup(self) -> None:
        d = self.data
        self.reference_cost = sigopref.block_sigop_cost(
            sigopref.parse_tx(d["coinbase"]),
            [(sigopref.parse_tx(t["raw"]), t["outs"]) for t in d["txs"]],
        )
        self.costs: set = set()
        self.cached: set = set()
        super().setup()
        # what the corrupted block and the untimed iteration left
        self.costs.clear()
        self.cached.clear()

    def _connect(self, raw, view, sig_cache, script_cache):
        res = super()._connect(raw, view, sig_cache, script_cache)
        self.costs.add(res.sigop_cost)
        self.cached.add(len(sig_cache))
        return res

    def _reference_walks(self) -> dict:
        """The sampled inputs through the reference's own walk: how many
        disagree with what the timed path answered, and the pairings tried."""
        d = self.data
        results = self.last_results or []
        differ, tried = [], 0
        sample = oracle.sample_indices(d["n_inputs"], [], int(self.config["oracle_sample"]), self.seed)
        for i in sample:
            t = bisect.bisect_right(d["tx_start"], i) - 1
            index = i - d["tx_start"][t]
            record = d["txs"][t]
            pairs, ok = sigopref.p2wsh_multisig_input(
                sigopref.parse_tx(record["raw"]), index, record["outs"][index]
            )
            tried += len(pairs)
            if i >= len(results) or bool(results[i].ok) != ok:
                differ.append(i)
        return {"inputs": len(sample), "pairings_tried": tried, "differ": differ[:3],
                "mismatches": len(differ)}

    def verify(self) -> dict:
        out = super().verify()
        d = self.data
        walks = self._reference_walks()
        problems = out["problems"]
        want = {self.reference_cost, int(self.config["block"]["sigop_cost"])}
        if len(want) != 1 or self.costs != want:
            problems.append(f"sigop_cost: connects gave {sorted(self.costs)}, the reference "
                            f"{self.reference_cost}, the configuration {self.config['block']['sigop_cost']}")
        if walks["mismatches"]:
            problems.append(f"timed path vs the reference's key walk: inputs {walks['differ']}")
        cached = sorted(self.cached)
        if cached != [d["n_inputs"]]:
            problems.append(f"the signature cache held {cached} entries after a connect, "
                            f"not one an input ({d['n_inputs']})")
        fell_back = counters.rose(self.watch.before, self.watch.after,
                                  "consensus_exact_fallback_total")
        if fell_back:
            problems.append(f"{fell_back:g} input(s) went to the exact host fallback")
        out["compared"]["reference"] = {
            "sigop_cost": self.reference_cost, "walks": walks, "sig_cache_entries": cached,
        }
        out["correct"] = out["correct"] and not problems
        return out

    def detail(self) -> dict:
        d = self.data
        spec = None  # a program without the counter has nothing to read
        if "consensus_multisig_spec_pairings_total" in self.watch.after:
            spec = counters.rose(self.watch.before, self.watch.after,
                                 "consensus_multisig_spec_pairings_total") / len(self.walls)
        return {**super().detail(), "sigop_cost": sorted(self.costs), "weight": d["weight"],
                "block_bytes": len(d["block"]), "pairings": d["pairings"],
                "spec_pairings_a_connect": spec}
