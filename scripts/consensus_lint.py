#!/usr/bin/env python
"""Consensus lint: prove every registered kernel overflow-free and
deterministic, and lint the host-side consensus path.

    python scripts/consensus_lint.py            # everything (CI gate)
    python scripts/consensus_lint.py --quick    # skip heavy kernels
    python scripts/consensus_lint.py --kernel limbs.fe_mul
    python scripts/consensus_lint.py --kernel pallas.verify_tiles
    python scripts/consensus_lint.py --report out.json
    python scripts/consensus_lint.py --negative oob-index-map
    python scripts/consensus_lint.py --exactness --report theorems.json
    python scripts/consensus_lint.py --schedule --report schedule.json

Exit status 0 iff every kernel proves clean AND the host lint is clean.
The JSON report carries the derived per-limb output bounds of every
kernel — plus, for Pallas kernels, the peak VMEM live set and grid, and
for kernels with f32 values, the per-value exactness trace — so
reviewers can diff bounds across PRs (CI uploads it as a build
artifact).

`--negative NAME` runs one of the deliberately broken toys — a Pallas
kernel from `analysis/pallas_check.NEGATIVES` or a scalar schedule from
`analysis/scalar_check.NEGATIVES` — and exits non-zero with its
diagnostics: the gate proving it still fires. `--negative list` lists
the available toys from both families.

`--exactness` is the exact-float theorem leg: for each f32-bearing
kernel (default: the MXU one-hot fe_mul candidate and the two existing
one-hot select chains) it re-proves the kernel and emits the
machine-checkable per-value bound trace — every float32 value
integer-valued with magnitude (and accumulated dot/reduce sums)
<= 2^24 — then requires every `f32-*` negative toy to be REJECTED with
a `float` violation. Exit 0 iff all theorems hold and all unsound toys
are rejected; `--report` writes the theorem sections as JSON.

`--schedule` is the scalar-schedule theorem leg: for every target in
`analysis/registry.all_schedules()` (digit recoders, the GLV lattice
split, the XLA and Pallas window ladders) it runs the scalar-semantics
prover (`analysis/scalar_check.py`) and prints THEOREM / VACUOUS /
FAIL, runs the sound toy-ladder self-test (the checker must PASS it),
then requires every `scalar-*` negative toy to be REJECTED with a
`schedule` violation. Exit 0 iff every target is THEOREM, the
self-test passes, and all unsound toys are rejected; `--report` writes
the certificates as JSON (CI uploads it as the schedule-certificates
artifact).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="skip heavy kernels (GLV ladder, verify kernel)")
    ap.add_argument("--kernel", action="append", default=None,
                    help="analyze only the named kernel(s)")
    ap.add_argument("--report", default=None,
                    help="write the per-kernel bound report as JSON")
    ap.add_argument("--list", action="store_true",
                    help="list registered kernels and exit")
    ap.add_argument("--negative", default=None, metavar="NAME",
                    help="run one broken toy Pallas kernel (or `list`); "
                         "exits non-zero with its diagnostics")
    ap.add_argument("--exactness", action="store_true",
                    help="exact-float theorem leg: prove every f32 value "
                         "in the one-hot MXU kernels integer-exact and "
                         "reject all f32-* negative toys")
    ap.add_argument("--schedule", action="store_true",
                    help="scalar-schedule theorem leg: certify the digit "
                         "recoders, GLV split, and window ladders, and "
                         "reject all scalar-* negative toys")
    args = ap.parse_args()

    from bitcoinconsensus_tpu.analysis import host_lint, registry

    if args.negative:
        from bitcoinconsensus_tpu.analysis import pallas_check, scalar_check
        if args.negative == "list":
            for n in sorted(set(pallas_check.NEGATIVES)
                            | set(scalar_check.NEGATIVES)):
                print(n)
            return 0
        if args.negative in scalar_check.NEGATIVES:
            rep = scalar_check.analyze_negative(args.negative)
        else:
            rep = pallas_check.analyze_negative(args.negative)
        print(f"negative toy `{args.negative}`: "
              f"{'FAILED the gate (expected)' if not rep.ok else 'PROVED CLEAN (gate is dead!)'}")
        for v in rep.violations:
            print(f"  {v.kind:10s} {v.where}")
            print(f"             {v.msg}")
        return 1 if not rep.ok else 0

    if args.exactness:
        return _exactness_leg(args, registry)

    if args.schedule:
        return _schedule_leg(args, registry)

    specs = registry.all_kernels(include_heavy=not args.quick)
    if args.kernel:
        wanted = set(args.kernel)
        specs = [registry.get_kernel(n) for n in sorted(wanted)]
    if args.list:
        for s in registry.all_kernels():
            print(f"{s.name:40s} {'heavy' if s.heavy else ''}")
        return 0

    print("== host lint (core/, models/ + crypto/ timing rule) ==")
    findings = host_lint.lint_consensus_host(REPO)
    for f in findings:
        print(f"  {f}")
    host_ok = not findings
    print(f"  {'clean' if host_ok else f'{len(findings)} finding(s)'}")

    print("\n== kernel region-annotation coverage (op names in a profiler trace) ==")
    region_findings = host_lint.lint_kernel_regions(
        include_heavy=not args.quick)
    for f in region_findings:
        print(f"  {f}")
    print(f"  {'clean' if not region_findings else f'{len(region_findings)} finding(s)'}")
    host_ok = host_ok and not region_findings
    findings = findings + region_findings

    print("\n== scalar-recoder schedule coverage (ops/ + crypto/glv.py) ==")
    scalar_findings = host_lint.lint_scalar_recoders(REPO)
    for f in scalar_findings:
        print(f"  {f}")
    print(f"  {'clean' if not scalar_findings else f'{len(scalar_findings)} finding(s)'}")
    host_ok = host_ok and not scalar_findings
    findings = findings + scalar_findings

    print("\n== kernel interval prover + determinism gate ==")
    all_ok = host_ok
    reports = []
    for spec in specs:
        t0 = time.time()
        try:
            rep = spec.analyze()
        except Exception as e:  # trace failure is a gate failure
            print(f"  {spec.name:40s} ERROR: {type(e).__name__}: {e}")
            all_ok = False
            reports.append({"name": spec.name, "ok": False,
                            "error": f"{type(e).__name__}: {e}"})
            continue
        dt = time.time() - t0
        status = "PROVEN" if rep.ok else "FAIL"
        wraps = f" wraps={rep.wrap_eqns}" if rep.wrap_eqns else ""
        vmem = ""
        if rep.vmem_peak_bytes is not None:
            vmem = (f" vmem={rep.vmem_peak_bytes / (1 << 20):.2f}MiB"
                    f" grid={tuple(rep.grid) if rep.grid else ()}")
        print(f"  {spec.name:40s} {status}  eqns={rep.n_eqns}"
              f" max|v|={rep.max_observed}{wraps}{vmem}  ({dt:.1f}s)")
        for v in rep.violations[:12]:
            print(f"      {v.kind:10s} {v.where}")
            print(f"                 {v.msg}")
        if len(rep.violations) > 12:
            print(f"      ... {len(rep.violations) - 12} more")
        all_ok = all_ok and rep.ok
        d = rep.to_dict()
        d["seconds"] = round(dt, 2)
        if spec.note:
            d["note"] = spec.note
        reports.append(d)

    if args.report:
        payload = {
            "host_lint": [str(f) for f in findings],
            "kernels": reports,
        }
        with open(args.report, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        print(f"\nreport written to {args.report}")

    print(f"\nconsensus lint: {'OK' if all_ok else 'FAILED'}")
    return 0 if all_ok else 1


# The f32-bearing consensus kernels: the MXU one-hot fe_mul candidate
# and the two existing one-hot select chains (ops/curve.py GLV G-table,
# ops/pallas_kernel.py VMEM G-table). Every f32 chain a consensus
# verdict can see must be listed here once it exists.
EXACTNESS_KERNELS = [
    "mxu.fe_mul_onehot",
    "curve.double_scalar_mult_glv",
    "pallas.verify_tiles",
]


def _exactness_leg(args, registry) -> int:
    from bitcoinconsensus_tpu.analysis import pallas_check

    names = args.kernel or EXACTNESS_KERNELS
    sections = []
    all_ok = True

    print("== exact-float theorems (carried f32 exactness prover) ==")
    for name in names:
        spec = registry.get_kernel(name)
        t0 = time.time()
        try:
            rep = spec.analyze()
        except Exception as e:  # trace failure is a gate failure
            print(f"  {name:40s} ERROR: {type(e).__name__}: {e}")
            sections.append({"name": name, "ok": False,
                             "error": f"{type(e).__name__}: {e}"})
            all_ok = False
            continue
        dt = time.time() - t0
        f32 = [e for e in rep.exactness
               if str(e.get("dtype", "")).startswith("float")]
        bounds = [e["bound"] for e in f32
                  if isinstance(e.get("bound"), int)]
        status = ("THEOREM" if rep.ok and f32 else
                  "VACUOUS" if rep.ok else "FAIL")
        print(f"  {name:40s} {status}  f32_values={len(f32)}"
              f" max_bound={max(bounds) if bounds else 0}  ({dt:.1f}s)")
        for v in rep.violations[:8]:
            print(f"      {v.kind:10s} {v.where}")
            print(f"                 {v.msg}")
        sections.append({"name": name, "ok": rep.ok, "theorem": status,
                         "f32_values": len(f32),
                         "max_bound": max(bounds) if bounds else 0,
                         "trace": rep.exactness})
        all_ok = all_ok and rep.ok

    print("\n== unsound f32 toys must be rejected ==")
    for name in sorted(n for n in pallas_check.NEGATIVES
                       if n.startswith("f32-")):
        rep = pallas_check.analyze_negative(name)
        rejected = (not rep.ok
                    and any(v.kind == "float" for v in rep.violations))
        verdict = ("REJECTED (expected)" if rejected
                   else "NOT REJECTED (gate is dead!)")
        print(f"  {name:40s} {verdict}")
        sections.append({"name": f"negative.{name}", "rejected": rejected})
        all_ok = all_ok and rejected

    if args.report:
        with open(args.report, "w") as fh:
            json.dump({"exactness": sections}, fh, indent=2, sort_keys=True)
        print(f"\nreport written to {args.report}")

    print(f"\nexactness theorems: {'OK' if all_ok else 'FAILED'}")
    return 0 if all_ok else 1


def _schedule_leg(args, registry) -> int:
    from bitcoinconsensus_tpu.analysis import scalar_check

    if args.kernel:
        specs = [registry.get_schedule(n) for n in sorted(set(args.kernel))]
    else:
        specs = registry.all_schedules(include_heavy=not args.quick)
    sections = []
    all_ok = True

    print("== scalar-schedule theorems "
          "(congruence + carry automaton + weight ledger) ==")
    for spec in specs:
        t0 = time.time()
        cert = spec.certify(quick=args.quick)
        dt = time.time() - t0
        print(f"  {spec.name:40s} {cert.status}  facts={len(cert.facts)}"
              f"  ({dt:.1f}s)")
        for f in cert.failures[:8]:
            print(f"      {f}")
        if len(cert.failures) > 8:
            print(f"      ... {len(cert.failures) - 8} more")
        d = cert.to_dict()
        d["seconds"] = round(dt, 2)
        if spec.note:
            d["note"] = spec.note
        sections.append(d)
        all_ok = all_ok and cert.ok

    print("\n== sound toy schedule must PASS (checker liveness) ==")
    t0 = time.time()
    self_cert = scalar_check.toy_ladder_selftest()
    print(f"  {'toy-ladder-selftest':40s} {self_cert.status}"
          f"  ({time.time() - t0:.1f}s)")
    for f in self_cert.failures[:8]:
        print(f"      {f}")
    sections.append({"name": "selftest.toy_ladder",
                     "status": self_cert.status, "ok": self_cert.ok})
    all_ok = all_ok and self_cert.ok

    print("\n== unsound scalar toys must be rejected ==")
    for name in sorted(scalar_check.NEGATIVES):
        rep = scalar_check.analyze_negative(name)
        rejected = (not rep.ok
                    and any(v.kind == "schedule" for v in rep.violations))
        verdict = ("REJECTED (expected)" if rejected
                   else "NOT REJECTED (gate is dead!)")
        print(f"  {name:40s} {verdict}")
        sections.append({"name": f"negative.{name}", "rejected": rejected})
        all_ok = all_ok and rejected

    if args.report:
        with open(args.report, "w") as fh:
            json.dump({"schedule": sections}, fh, indent=2, sort_keys=True,
                      default=str)
        print(f"\nreport written to {args.report}")

    print(f"\nschedule theorems: {'OK' if all_ok else 'FAILED'}")
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
