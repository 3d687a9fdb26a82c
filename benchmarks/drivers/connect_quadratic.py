"""The `connect` driver, held to what one legacy transaction of thousands
of inputs adds.

The loop, the timing, the first corrupted block and the oracle comparison
are `drivers/connect.py`'s, unchanged. On top, `correct` needs:

- every sampled input's verdict and `ScriptError`, as the last timed
  connect returned them, equal to the plain reference's (`harness/
  sighashref.py`: Core's legacy `SignatureHash` written out, the
  benchmark's own curve code), beside the three ways `connect` compares.
  The reference accepts a sampled signature only if the digest it makes
  from the raw transaction is the one the generator signed, so the
  signer's, the program's and the reference's serialisations agree or a
  verdict differs;
- `ConnectResult.sigop_cost` of every timed connect equal to the plain
  reference's count (`harness/sigopref.py`) and to the configuration's;
- three corrupted blocks in set-up (one bit of a signature flipped; one
  input signed over a preimage in which the other inputs' scripts were not
  blanked; one input's hash-type byte changed from SIGHASH_ALL to
  SIGHASH_NONE after signing), each rejected for exactly its victim with
  `EVAL_FALSE` and the view untouched, the oracle, the reference and the
  program agreeing;
- the signature cache success-only: after every timed connect it holds one
  entry an input;
- no input resolved by the exact host fallback and no lane by the host
  fix-up inside the window.

The bytes hashed are reported (`detail.sighash_bytes_a_connect` beside the
reference's sum over the whole transaction), not required: a lawful short
cut (a shared prefix's midstate) must stay possible. `correct` reads
neither new counter, so a program without them gives a result.
"""

from __future__ import annotations

import os
from typing import Optional

from ..harness import cell, counters, oracle, sighashref, sigopref
from . import connect
from .connect_multisig import _named

_HELD_AT_ZERO = ("consensus_exact_fallback_total", "consensus_host_fixup_total")
_BYTES = "consensus_sighash_bytes_total"
_SECONDS = "consensus_sighash_seconds_total"


class Driver(connect.Driver):
    def setup(self) -> None:
        d = self.data
        self.parsed = sigopref.parse_tx(d["txs"][0]["raw"])
        self.reference_cost = sigopref.block_sigop_cost(
            sigopref.parse_tx(d["coinbase"]), [(self.parsed, d["txs"][0]["outs"])])
        self.costs: set = set()
        self.cached: set = set()
        self.sample_bytes: Optional[dict] = None
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
        want = oracle.oracle_verdict(tx["raw"], twin["victim"], tx["outs"], self.flags)
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
        ref = sighashref.verify_input(tx["raw"], twin["victim"], tx["outs"])
        stated = (False, twin["error"])
        seen = {"program": _named(got), "oracle": _named(want), "reference": (ref.ok, ref.error)}
        if any(v != stated for v in seen.values()):
            self.notes.append(f"corrupted block ({twin['name']}): stated {stated}, seen {seen}")
        return {"name": twin["name"], "stated": twin["error"], **seen,
                "reference_preimage_bytes": ref.preimage_bytes}

    def _reference(self) -> dict:
        """The sampled inputs through the plain reference: how many differ
        from what the last timed connect answered, and the bytes the
        reference hashed for them."""
        d = self.data
        results = self.last_results or []
        outs = d["txs"][0]["outs"]
        differ, hashed = [], 0
        sample = oracle.sample_indices(d["n_inputs"], [], int(self.config["oracle_sample"]), self.seed)
        for i in sample:
            ref = sighashref.verify_input(self.parsed, i, outs)
            hashed += ref.preimage_bytes
            got = _named(oracle.as_triple(results[i])) if i < len(results) else None
            if got != (ref.ok, ref.error):
                differ.append((i, got, (ref.ok, ref.error)))
        return {"inputs": len(sample), "preimage_bytes": hashed, "mismatches": len(differ),
                "first": [repr(x) for x in differ[:3]]}

    def verify(self) -> dict:
        out = super().verify()
        d = self.data
        problems = out["problems"]
        ref = self._reference()
        if ref["mismatches"]:
            problems.append(f"timed path vs the plain reference's SignatureHash: {ref['first']}")
        want = {self.reference_cost, int(self.config["block"]["sigop_cost"])}
        if len(want) != 1 or self.costs != want:
            problems.append(f"sigop_cost: connects gave {sorted(self.costs)}, the reference "
                            f"{self.reference_cost}, the configuration {self.config['block']['sigop_cost']}")
        cached = sorted(self.cached)
        if cached != [d["n_inputs"]]:
            problems.append(f"the signature cache held {cached} entries after a connect, "
                            f"not one an input ({d['n_inputs']})")
        for name in _HELD_AT_ZERO:
            rose = counters.rose(self.watch.before, self.watch.after, name)
            if rose:
                problems.append(f"{name} rose by {rose:g} inside the window")
        out["compared"]["reference"] = {
            "sigop_cost": self.reference_cost, "sample": ref, "sig_cache_entries": cached,
        }
        self.sample_bytes = {"inputs": ref["inputs"], "preimage_bytes": ref["preimage_bytes"]}
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
        from bitcoinconsensus_tpu import native_bridge

        d = self.data
        transform = getattr(native_bridge, "sha256_transform", None)
        return {**super().detail(), "sigop_cost": sorted(self.costs),
                "tx_bytes": d["tx_bytes"], "block_bytes": len(d["block"]),
                "reference_sighash_bytes": d["sighash_bytes"],
                "sighash_bytes_a_connect": self._a_connect(_BYTES),
                "sighash_thread_s_a_connect": self._a_connect(_SECONDS),
                "reference_sample": self.sample_bytes,
                "host_cpus": os.cpu_count(),
                "sha256_transform": transform() if transform else None}
