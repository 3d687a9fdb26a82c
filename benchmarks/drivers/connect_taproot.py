"""The `connect` driver, held to what a block of the taproot era adds.

The loop, the timing, the first corrupted block and the oracle comparison
are `drivers/connect.py`'s, unchanged. On top, `correct` needs:

- every sampled input's verdict and `ScriptError`, as the last timed
  connect returned them, equal to the plain BIP 341/342 reference's
  (`harness/tapref.py`: from the raw transaction and the spent outputs
  alone, over the benchmark's own curve code), beside the three ways
  `connect` compares;
- `ConnectResult.sigop_cost` of every timed connect equal to the plain
  reference's count (`harness/sigopref.py`) and to the configuration's;
- three corrupted blocks in set-up (one signature bit flipped, an input of
  any kind; one bit of a script-path input's first merkle sibling flipped,
  so that its tweak lane fails on the device; the second signature of a
  2-of-3 replaced by the empty vector, so that no lane fails and the host
  ends the script false), each rejected for exactly its victim with the
  view untouched and the `ScriptError` the generator states, equal for the
  oracle, the reference and the program;
- no input resolved by the exact host fallback and no lane by the host
  fix-up inside the window.

The lanes by kind are reported (`detail.lanes_by_kind`, beside the
reference's own count for the sample), not required: a lawful short cut
must stay possible.
"""

from __future__ import annotations

import bisect
from typing import Optional

from ..harness import cell, counters, oracle, sigopref, tapref
from . import connect

_HELD_AT_ZERO = ("consensus_exact_fallback_total", "consensus_host_fixup_total")
_LANES = "consensus_checks_total"


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
        self.sample_checks: Optional[dict] = None
        super().setup()  # the signature twin, then one untimed iteration
        first, commitment, threshold = d["twins"]
        self.twins = [
            self._judge(first, self.bad_block["victim_verdict"], self.bad_block["oracle_verdict"]),
            self._twin(commitment), self._twin(threshold),
        ]
        self.costs.clear()  # what the corrupted blocks and the untimed iteration left

    def _connect(self, raw, view, sig_cache, script_cache):
        res = super()._connect(raw, view, sig_cache, script_cache)
        self.costs.add(res.sigop_cost)
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
        ref = tapref.verify_input(tx["raw"], index, tx["outs"])
        stated = (False, twin["error"])
        seen = {"program": _named(got), "oracle": _named(want), "reference": (ref.ok, ref.error)}
        if any(v != stated for v in seen.values()):
            self.notes.append(f"corrupted block ({twin['name']}, a {twin['kind']} input): "
                              f"stated {stated}, seen {seen}")
        return {"name": twin["name"], "kind": twin["kind"], "stated": twin["error"], **seen,
                "reference_checks": ref.checks}

    def _reference(self) -> dict:
        """The sampled inputs through the plain reference: how many differ
        from what the last timed connect answered, and the curve checks the
        reference made for them, by kind."""
        d = self.data
        results = self.last_results or []
        differ, checks = [], dict.fromkeys(tapref.KINDS, 0)
        sample = oracle.sample_indices(d["n_inputs"], [], int(self.config["oracle_sample"]), self.seed)
        spends: dict = {}
        for i in sample:
            t = bisect.bisect_right(d["tx_start"], i) - 1
            if t not in spends:
                spends[t] = tapref.Spend(d["txs"][t]["raw"], d["txs"][t]["outs"])
            ref = spends[t].verify(i - d["tx_start"][t])
            for kind, n in ref.checks.items():
                checks[kind] += n
            got = _named(oracle.as_triple(results[i])) if i < len(results) else None
            if got != (ref.ok, ref.error):
                differ.append((i, d["kinds"][i], got, (ref.ok, ref.error)))
        return {"inputs": len(sample), "checks": checks, "mismatches": len(differ),
                "first": [repr(x) for x in differ[:3]]}

    def verify(self) -> dict:
        out = super().verify()
        problems = out["problems"]
        ref = self._reference()
        if ref["mismatches"]:
            problems.append(f"timed path vs the plain BIP 341/342 reference: {ref['first']}")
        want = {self.reference_cost, int(self.config["block"]["sigop_cost"])}
        if len(want) != 1 or self.costs != want:
            problems.append(f"sigop_cost: connects gave {sorted(self.costs)}, the reference "
                            f"{self.reference_cost}, the configuration {self.config['block']['sigop_cost']}")
        for name in _HELD_AT_ZERO:
            rose = counters.rose(self.watch.before, self.watch.after, name)
            if rose:
                problems.append(f"{name} rose by {rose:g} inside the window")
        out["compared"]["reference"] = {"sigop_cost": self.reference_cost, "sample": ref}
        self.sample_checks = ref["checks"]
        out["corrupted_block"] = {"first": out["corrupted_block"], "twins": self.twins}
        out["correct"] = out["correct"] and not problems
        return out

    def detail(self) -> dict:
        d = self.data
        lanes = None  # a program that does not feed the counter in a connect reads none
        rose = counters.rose_by_label(self.watch.before, self.watch.after, _LANES, "kind")
        if rose:
            lanes = {k: v / len(self.walls) for k, v in sorted(rose.items())}
        return {**super().detail(), "sigop_cost": sorted(self.costs), "weight": d["weight"],
                "block_bytes": len(d["block"]), "lanes_built": d["lanes_by_kind"],
                "lanes_by_kind": lanes, "reference_sample_checks": self.sample_checks}
