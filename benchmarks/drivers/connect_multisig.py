"""The `connect` driver, held to what a block of m-of-n CHECKMULTISIG spends adds.

The loop, the timing, the first corrupted block and the oracle comparison
are `drivers/connect.py`'s, unchanged. On top, `correct` needs:

- every sampled input's verdict and `ScriptError`, as the last timed
  connect returned them, equal to the plain reference's own key walk
  (`harness/msigref.py` over `sigopref.multisig_walk` and the benchmark's
  own curve code), beside the three ways `connect` compares;
- `ConnectResult.sigop_cost` of every timed connect equal to the plain
  reference's count (`harness/sigopref.py`) and to the configuration's;
- three corrupted blocks in set-up (one bit of the first-pushed signature
  flipped; one bit of a middle signature flipped; two adjacent signatures
  swapped, each valid for a listed key), each rejected for exactly its
  victim with `EVAL_FALSE` and the view untouched, the oracle, the
  reference and the program agreeing;
- the signature cache success-only: after every timed connect it holds one
  entry a signature, m an input, and none of the pairings that failed.
  Inverting a chunk's verdicts changes no input's verdict here (the walk
  then succeeds at the first key it tries), but it fills the cache with
  pairings that failed, which is how the `lane-flip` control shows;
- no input resolved by the exact host fallback and no lane by the host
  fix-up inside the window.

The lanes are reported (`detail.pairings`, `spec_pairings_a_connect`,
`walk_pairings_a_connect` beside the reference's own count for the
sample), not required: a lawful short cut must stay possible. `correct`
reads neither counter, so a program without them gives a result.
"""

from __future__ import annotations

import bisect
from typing import Optional

from ..harness import cell, counters, msigref, oracle, sigopref
from . import connect

_HELD_AT_ZERO = ("consensus_exact_fallback_total", "consensus_host_fixup_total")
_SPEC = "consensus_multisig_spec_pairings_total"
_WALK = "consensus_multisig_walk_pairings_total"


def _named(triple) -> Optional[tuple]:
    """(ok, ScriptError's name) of an oracle triple, None for no answer."""
    from bitcoinconsensus_tpu.core.script_error import ScriptError

    if triple is None:
        return None
    ok, _error, script_error = triple
    return ok, "OK" if ok or script_error is None else ScriptError(script_error).name


class Driver(connect.Driver):
    def setup(self) -> None:
        d = self.data
        self.reference_cost = sigopref.block_sigop_cost(
            sigopref.parse_tx(d["coinbase"]),
            [(sigopref.parse_tx(t["raw"]), t["outs"]) for t in d["txs"]],
        )
        self.costs: set = set()
        self.cached: set = set()
        self.sample_walk: Optional[dict] = None
        super().setup()  # the first twin, then one untimed iteration
        first, *rest = d["twins"]
        self.twins = [
            self._judge(first, self.bad_block["victim_verdict"], self.bad_block["oracle_verdict"]),
            *(self._twin(t) for t in rest),
        ]
        # what the corrupted blocks and the untimed iteration left
        self.costs.clear()
        self.cached.clear()

    def _connect(self, raw, view, sig_cache, script_cache):
        res = super()._connect(raw, view, sig_cache, script_cache)
        self.costs.add(res.sigop_cost)
        self.cached.add(len(sig_cache))
        return res

    def _twin(self, twin: dict) -> dict:
        """Connect one corrupted block as `connect.Driver.setup` connects
        the first: rejected for exactly its victim, the view untouched."""
        victim = twin["victim"] + (1 if self.control == "truth-shift" else 0)
        view = self.funded.clone()
        res = self._connect(twin["block"], view, *cell.fresh_caches(self.config))
        got = oracle.as_triple(res.input_results[twin["victim"]]) if res.input_results else None
        tx = twin["tx"]
        index = twin["victim"] - self.data["tx_start"][tx["index"]]
        want = oracle.oracle_verdict(tx["raw"], index, tx["outs"], self.flags)
        if (res.ok or res.reason != "block-validation-failed"
                or res.script_failures != [victim] or len(view) != len(self.funded)):
            self.notes.append(
                f"corrupted block ({twin['name']}): ok={res.ok} reason={res.reason!r} "
                f"failures={res.script_failures[:5]} victim={victim} "
                f"view_untouched={len(view) == len(self.funded)}")
        return self._judge(twin, got, want)

    def _judge(self, twin: dict, got, want) -> dict:
        """The victim's verdict three ways against what the generator
        states: the program's (`got`), the oracle's (`want`), the plain
        reference's."""
        tx = twin["tx"]
        index = twin["victim"] - self.data["tx_start"][tx["index"]]
        ref = msigref.verify_input(tx["raw"], index, tx["outs"])
        stated = (False, twin["error"])
        seen = {"program": _named(got), "oracle": _named(want), "reference": (ref.ok, ref.error)}
        if any(v != stated for v in seen.values()):
            self.notes.append(f"corrupted block ({twin['name']}): stated {stated}, seen {seen}")
        return {"name": twin["name"], "stated": twin["error"], **seen,
                "reference_pairings_tried": len(ref.tried)}

    def _reference(self) -> dict:
        """The sampled inputs through the plain reference's walk: how many
        differ from what the last timed connect answered, and the pairings
        the walk tried for them."""
        d = self.data
        results = self.last_results or []
        differ, tried = [], 0
        sample = oracle.sample_indices(d["n_inputs"], [], int(self.config["oracle_sample"]), self.seed)
        parsed: dict = {}
        for i in sample:
            t = bisect.bisect_right(d["tx_start"], i) - 1
            if t not in parsed:
                parsed[t] = sigopref.parse_tx(d["txs"][t]["raw"])
            ref = msigref.verify_input(parsed[t], i - d["tx_start"][t], d["txs"][t]["outs"])
            tried += len(ref.tried)
            got = _named(oracle.as_triple(results[i])) if i < len(results) else None
            if got != (ref.ok, ref.error):
                differ.append((i, got, (ref.ok, ref.error)))
        return {"inputs": len(sample), "pairings_tried": tried, "mismatches": len(differ),
                "first": [repr(x) for x in differ[:3]]}

    def verify(self) -> dict:
        out = super().verify()
        d = self.data
        problems = out["problems"]
        ref = self._reference()
        if ref["mismatches"]:
            problems.append(f"timed path vs the plain reference's key walk: {ref['first']}")
        want = {self.reference_cost, int(self.config["block"]["sigop_cost"])}
        if len(want) != 1 or self.costs != want:
            problems.append(f"sigop_cost: connects gave {sorted(self.costs)}, the reference "
                            f"{self.reference_cost}, the configuration {self.config['block']['sigop_cost']}")
        cached, held = sorted(self.cached), d["n_inputs"] * d["sigs"]
        if cached != [held]:
            problems.append(f"the signature cache held {cached} entries after a connect, "
                            f"not one a signature ({held})")
        for name in _HELD_AT_ZERO:
            rose = counters.rose(self.watch.before, self.watch.after, name)
            if rose:
                problems.append(f"{name} rose by {rose:g} inside the window")
        out["compared"]["reference"] = {
            "sigop_cost": self.reference_cost, "sample": ref, "sig_cache_entries": cached,
        }
        self.sample_walk = {"inputs": ref["inputs"], "pairings_tried": ref["pairings_tried"]}
        out["corrupted_block"] = {"first": out["corrupted_block"], "twins": self.twins}
        out["correct"] = out["correct"] and not problems
        return out

    def _a_connect(self, name: str) -> Optional[float]:
        """A counter's rise over the window, a connect; a program without
        the counter has nothing to read."""
        if name not in self.watch.after:
            return None
        return counters.rose(self.watch.before, self.watch.after, name) / len(self.walls)

    def detail(self) -> dict:
        d = self.data
        return {**super().detail(), "sigop_cost": sorted(self.costs), "weight": d["weight"],
                "block_bytes": len(d["block"]), "pairings": d["pairings"],
                "walk_pairings_built": d["walk_pairings"],
                "spec_pairings_a_connect": self._a_connect(_SPEC),
                "walk_pairings_a_connect": self._a_connect(_WALK),
                "reference_sample_walk": self.sample_walk}
