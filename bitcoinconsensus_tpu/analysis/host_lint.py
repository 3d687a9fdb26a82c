"""Host-side determinism lint for the consensus interpreter.

The jaxpr prover covers traced kernels; this covers the plain-Python
consensus path (`core/` — script interpreter, tx/block checks, sighash —
and `models/` — batch orchestration whose decisions feed verdicts).
Those modules must be bit-exact, replayable functions of their inputs:

- no float literals or float arithmetic (script semantics are integer;
  a float sneaking into, say, a fee or size comparison is a consensus
  fault that no test vector may cover),
- no `random` / `secrets` (verdicts must not depend on entropy),
- no reading clocks (`time.time`, `datetime.now`, `time.monotonic` —
  anything time-dependent belongs to policy, not consensus).

The clock rule also runs alone over `crypto/` (which legitimately uses
float literals for jax config and fill-ratio math): all host-side timing
flows through `bitcoinconsensus_tpu.obs` spans — the one sanctioned
clock reader — so ad-hoc `time.perf_counter()` pairs cannot drift in
beside the uniform telemetry.

The `precision` rule group runs alone over `ops/` and `crypto/`: every
`jnp.dot` / `lax.dot_general` there must pin
`precision=lax.Precision.HIGHEST` at the call site — the source-level
complement of the jaxpr prover's dot rule, catching the bug before
tracing and in paths no registered kernel reaches yet.

Pure-AST checks: no imports of the scanned modules, so a syntax-valid
file is lintable even when its dependencies are not importable.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Dict, FrozenSet, Iterator, List, Optional, Sequence, Tuple

# Rule groups, selectable per scanned tree.
ALL_RULES = frozenset({"float", "nondeterminism", "time"})
TIMING_RULES = frozenset({"time"})
# Async-dispatch discipline: on the device-dispatch path, forcing an
# in-flight JAX array to host (`np.asarray`, `.block_until_ready()`,
# `jax.device_get`) is a hidden synchronization point that silently
# serializes the pipeline — and bypasses the settle seam's guards. The
# ONLY sanctioned block points are the settle seam itself and
# `resilience/inflight.settle_array` (SYNC_ALLOWED_FUNCS).
SYNC_RULES = frozenset({"sync"})
# Function bodies allowed to materialize device buffers.
SYNC_ALLOWED_FUNCS = {
    "_materialize_guarded",  # crypto/jax_backend.py — the settle seam
    "settle_array",          # resilience/inflight.py — sanctioned helper
    "make_mesh",             # parallel/mesh.py — host device-list shaping
}
# module.attr calls that force a device→host sync.
SYNC_BANNED_CALLS = {
    ("np", "asarray"), ("numpy", "asarray"),
    ("np", "array"), ("numpy", "array"),
    ("jax", "device_get"),
}
# MXU precision discipline: every dot in the traced consensus ops must
# pin `precision=lax.Precision.HIGHEST` explicitly — the TPU MXU lowers
# default-precision f32 dots through bfloat16 passes (8-bit mantissa)
# that silently truncate 13-bit limbs. The jaxpr prover catches this
# after tracing (interval._r_dot); this catches it at review time, and
# in code paths no registered kernel reaches yet.
PRECISION_RULES = frozenset({"precision"})
# module-path suffixes whose calls take a precision keyword.
DOT_CALLS = {"jnp.dot", "jax.numpy.dot", "numpy.dot",
             "lax.dot_general", "jax.lax.dot_general",
             "jnp.matmul", "jax.numpy.matmul"}

# Pallas kernel-body discipline: inside `_kernel_body`, every limb
# constant must come through the consts_ref row table installed by
# `_kernel`'s set_const_provider — materializing an ndarray there makes
# Mosaic bake it into the kernel as a captured constant, bypassing the
# one audited constant path (analysis/pallas_check.py flags the same
# thing at the jaxpr level; this catches it at review time, pre-trace).
PALLAS_RULES = frozenset({"pallas"})

# Function bodies subject to the `pallas` rule.
PALLAS_KERNEL_BODIES = {"_kernel_body"}
# np/jnp constructors that materialize array constants.
ARRAY_CONSTRUCTORS = {"asarray", "array", "frombuffer", "fromiter"}
ARRAY_MODULES = {"np", "numpy", "jnp"}

BANNED_IMPORTS = {"random", "secrets"}
# module.attr calls whose mere presence is a violation
BANNED_CALLS = {
    ("time", "time"), ("time", "time_ns"), ("time", "monotonic"),
    ("time", "monotonic_ns"), ("time", "perf_counter"),
    ("time", "perf_counter_ns"), ("time", "process_time"),
    ("datetime", "now"), ("datetime", "utcnow"), ("datetime", "today"),
    ("date", "today"),
}
FLOAT_CAST = {"float"}


@dataclass
class LintFinding:
    path: str
    line: int
    rule: str
    msg: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


def _is_float_literal(node: ast.Constant) -> bool:
    return isinstance(node.value, float)


def _dotted_name(fn) -> str:
    """`a.b.c` attribute chain -> \"a.b.c\"; anything else -> \"\"."""
    parts = []
    while isinstance(fn, ast.Attribute):
        parts.append(fn.attr)
        fn = fn.value
    if isinstance(fn, ast.Name):
        parts.append(fn.id)
        return ".".join(reversed(parts))
    return ""


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: str, rules: FrozenSet[str] = ALL_RULES):
        self.path = path
        self.rules = rules
        self.findings: List[LintFinding] = []
        self._fn_stack: List[str] = []

    def _flag(self, node, rule, msg):
        self.findings.append(
            LintFinding(self.path, getattr(node, "lineno", 0), rule, msg))

    def visit_Constant(self, node: ast.Constant):
        if "float" in self.rules and _is_float_literal(node):
            self._flag(node, "float-literal",
                       f"float literal {node.value!r} in consensus host "
                       "code (integer semantics only)")
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import):
        if "nondeterminism" in self.rules:
            for alias in node.names:
                root = alias.name.split(".")[0]
                if root in BANNED_IMPORTS:
                    self._flag(node, "nondeterminism",
                               f"import of `{alias.name}` (entropy source)")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        root = (node.module or "").split(".")[0]
        if "nondeterminism" in self.rules and root in BANNED_IMPORTS:
            self._flag(node, "nondeterminism",
                       f"import from `{node.module}` (entropy source)")
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self._fn_stack.append(node.name)
        try:
            self.generic_visit(node)
        finally:
            self._fn_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def _in_kernel_body(self) -> bool:
        return any(n in PALLAS_KERNEL_BODIES for n in self._fn_stack)

    def visit_Call(self, node: ast.Call):
        fn = node.func
        if "precision" in self.rules:
            name = _dotted_name(fn)
            if name in DOT_CALLS:
                kw = next((k.value for k in node.keywords
                           if k.arg == "precision"), None)
                if not (isinstance(kw, ast.Attribute)
                        and kw.attr == "HIGHEST"):
                    self._flag(
                        node, "dot-precision",
                        f"{name}() without an explicit "
                        "precision=lax.Precision.HIGHEST — the TPU MXU "
                        "lowers default-precision f32 dots through "
                        "bfloat16 passes that silently truncate 13-bit "
                        "limbs; the exactness theorem only holds at "
                        "HIGHEST")
        if "pallas" in self.rules and self._in_kernel_body():
            name = None
            if (isinstance(fn, ast.Attribute)
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in ARRAY_MODULES
                    and fn.attr in ARRAY_CONSTRUCTORS):
                name = f"{fn.value.id}.{fn.attr}"
            elif isinstance(fn, ast.Name) and fn.id in ARRAY_CONSTRUCTORS:
                name = fn.id
            if name is not None:
                self._flag(
                    node, "pallas-consts",
                    f"{name}() inside a Pallas kernel body captures an "
                    "array constant — route limb constants through the "
                    "consts_ref row table (limbs.set_const_provider), the "
                    "one audited constant path into VMEM")
        if "sync" in self.rules and not any(
            n in SYNC_ALLOWED_FUNCS for n in self._fn_stack
        ):
            if isinstance(fn, ast.Attribute):
                if fn.attr == "block_until_ready":
                    self._flag(
                        node, "sync",
                        ".block_until_ready() outside the settle seam — "
                        "in-flight buffers settle through "
                        "resilience/inflight (settle_array or "
                        "_materialize_guarded), never ad-hoc blocking")
                elif (isinstance(fn.value, ast.Name)
                      and (fn.value.id, fn.attr) in SYNC_BANNED_CALLS):
                    self._flag(
                        node, "sync",
                        f"{fn.value.id}.{fn.attr}() on the dispatch path "
                        "forces a hidden device→host sync — route "
                        "materialization through inflight.settle_array "
                        "or the settle seam")
        if (
            "time" in self.rules
            and isinstance(fn, ast.Attribute)
            and isinstance(fn.value, ast.Name)
        ):
            key = (fn.value.id, fn.attr)
            if key in BANNED_CALLS:
                self._flag(node, "time-dependence",
                           f"call to {key[0]}.{key[1]}() — time flows "
                           "through obs spans only (consensus verdicts "
                           "must not read clocks, and ad-hoc timing "
                           "bypasses the telemetry registry)")
        if (
            "float" in self.rules
            and isinstance(fn, ast.Name)
            and fn.id in FLOAT_CAST
        ):
            self._flag(node, "float-op",
                       "float() cast in consensus host code")
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp):
        if "float" in self.rules and isinstance(node.op, ast.Div):
            self._flag(node, "float-op",
                       "true division `/` yields float; use `//` for "
                       "integer consensus arithmetic")
        self.generic_visit(node)


def _iter_py(root: str) -> Iterator[str]:
    for dirpath, _dirnames, filenames in os.walk(root):
        for f in sorted(filenames):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)


def lint_paths(
    paths: Sequence[str], rules: FrozenSet[str] = ALL_RULES
) -> List[LintFinding]:
    findings: List[LintFinding] = []
    for root in paths:
        files = _iter_py(root) if os.path.isdir(root) else [root]
        for path in files:
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
            try:
                tree = ast.parse(src, filename=path)
            except SyntaxError as e:
                findings.append(LintFinding(path, e.lineno or 0,
                                            "syntax", str(e)))
                continue
            v = _Visitor(path, rules)
            v.visit(tree)
            findings.extend(v.findings)
    return findings


def lint_consensus_host(repo_root: str) -> List[LintFinding]:
    """Full rules over core/ + models/; clock rule alone over crypto/
    (its device-dispatch driver may use floats but must route timing
    through obs spans, never raw perf_counter pairs); const-provider
    discipline over the Pallas kernel body."""
    pkg = os.path.join(repo_root, "bitcoinconsensus_tpu")
    findings = lint_paths([os.path.join(pkg, "core"),
                           os.path.join(pkg, "models")])
    # resilience/ and serving/ are host-side policy with wall-clock
    # deadlines: like crypto/ they may use floats but must read time
    # through obs.monotonic, never raw time.* pairs the telemetry
    # cannot see (sleeping is fine; reading a clock is not).
    findings += lint_paths([os.path.join(pkg, "crypto"),
                           os.path.join(pkg, "resilience"),
                           os.path.join(pkg, "serving")],
                          rules=TIMING_RULES)
    findings += lint_paths([os.path.join(pkg, "ops", "pallas_kernel.py")],
                           rules=PALLAS_RULES)
    # MXU precision discipline over the traced consensus ops: every dot
    # must pin Precision.HIGHEST at the call site (see PRECISION_RULES).
    findings += lint_paths([os.path.join(pkg, "ops"),
                            os.path.join(pkg, "crypto")],
                           rules=PRECISION_RULES)
    # Async-dispatch discipline over the in-flight pipeline: the dispatch
    # drivers and the queue itself must not force device buffers to host
    # outside the settle seam (see SYNC_ALLOWED_FUNCS).
    findings += lint_paths(
        [os.path.join(pkg, "crypto", "jax_backend.py"),
         os.path.join(pkg, "parallel", "mesh.py"),
         os.path.join(pkg, "resilience", "inflight.py"),
         # The network edge and the persistent store sit upstream of the
         # dispatch path: neither may ever force a device buffer to host.
         os.path.join(pkg, "serving", "ingress.py"),
         os.path.join(pkg, "models", "sigstore.py")],
        rules=SYNC_RULES)
    return findings


# -- scalar-recoder schedule coverage (PR 19) ----------------------------
#
# Any digit-recoding / scalar-split function in ops/ or crypto/glv.py
# must be registered with the scalar-schedule prover
# (analysis/scalar_check.REGISTERED_RECODERS), mirroring the PR 17
# region-coverage rule: a new recoder landing without a certificate
# would silently reopen the window-order / carry-fold hole the prover
# closed. Detection is AST-only: a function counts as a recoder when
# its name carries a scalar-decomposition hint, or its body extracts
# windowed digits — a `(x >> amt) & mask` where the shift amount is not
# a plain integer constant (fixed-shift carry propagation in the field
# ops is NOT a recoder; variable-shift extraction is).

SCALAR_RECODER_NAME_HINTS = (
    "digit", "window", "recode", "split_lambda", "scalar_bits",
    "to_limbs", "limbs_to",
)


def _is_var_shift_extract(node: ast.AST) -> bool:
    """`(expr >> amt) & mask` with a non-constant shift amount."""
    if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitAnd)):
        return False
    for side in (node.left, node.right):
        if (isinstance(side, ast.BinOp)
                and isinstance(side.op, ast.RShift)
                and not isinstance(side.right, ast.Constant)):
            return True
    return False


def scalar_recoder_functions(paths: Sequence[str]):
    """All (path, line, name) recoder-shaped functions under `paths`."""
    hits = []
    for root in paths:
        files = _iter_py(root) if os.path.isdir(root) else [root]
        for path in files:
            with open(path, "r", encoding="utf-8") as fh:
                try:
                    tree = ast.parse(fh.read(), filename=path)
                except SyntaxError:
                    continue  # lint_paths reports syntax errors
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                name = node.name.lower()
                named = any(h in name for h in SCALAR_RECODER_NAME_HINTS)
                extracts = any(_is_var_shift_extract(n)
                               for n in ast.walk(node))
                if named or extracts:
                    hits.append((path, node.lineno, node.name))
    return hits


def lint_scalar_recoders(
    repo_root: str = None,
    paths: Sequence[str] = None,
    registered=None,
) -> List[LintFinding]:
    """One finding per recoder-shaped function not registered with the
    scalar-schedule prover.

    `paths` / `registered` override the defaults (the negative-fixture
    tests feed a deliberately unregistered toy recoder through the same
    gate)."""
    if paths is None:
        pkg = os.path.join(repo_root, "bitcoinconsensus_tpu")
        paths = [os.path.join(pkg, "ops"),
                 os.path.join(pkg, "crypto", "glv.py")]
    if registered is None:
        from . import scalar_check
        registered = scalar_check.REGISTERED_RECODERS
    findings: List[LintFinding] = []
    for path, line, name in scalar_recoder_functions(paths):
        if name not in registered:
            findings.append(LintFinding(
                path, line, "scalar-coverage",
                f"`{name}` looks like a digit recoder / scalar split but "
                "is not registered with the scalar-schedule prover — add "
                "it to analysis/scalar_check.REGISTERED_RECODERS mapped "
                "to the target that certifies it (and extend the prover "
                "if no target covers it yet)"))
    return findings


# -- kernel region-annotation coverage (PR 17) ---------------------------
#
# Not an AST rule: this one traces. Every kernel registered in
# `analysis/registry` must execute under a `region:` named scope
# (`ops/regions.py`): the scopes are the op names a profiler trace of
# the chip shows (`region_verify_tiles` is the one the benchmark's trace
# reduction sums), so a kernel landing without one is anonymous there.
# Kept in this module because it is a lint (finding-shaped, wired into
# `scripts/consensus_lint.py`), with lazy imports so the pure-AST rules
# above stay dependency-free.

# A kernel passes when at least this fraction of its element ops sit
# under some region scope. Below 1.0 because trace plumbing (argument
# converts, output reshapes) legitimately sits outside the scopes.
REGION_MIN_COVERAGE = 0.90

# Primitives whose output elements count as element ops.
_ELEMENT_OPS = frozenset({
    "add", "sub", "mul", "and", "or", "xor", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "select_n", "eq", "ne",
    "lt", "le", "gt", "ge", "min", "max", "neg", "abs", "rem", "not",
    "convert_element_type", "broadcast_in_dim", "concatenate", "iota",
    "reduce_and", "reduce_or", "reduce_sum", "reduce_min", "reduce_max",
    "dot_general",
})


def _while_trips(eqn) -> int:
    """Trip count of a lowered `fori_loop` (a `while` whose carry init
    holds the static upper bound as a scalar int literal — take the
    largest such literal; exact for every fori in the verify kernel)."""
    from jax.extend.core import Literal

    trips = 1
    for v in eqn.invars:
        if isinstance(v, Literal) and getattr(v.aval, "shape", None) == ():
            try:
                trips = max(trips, int(v.val))
            except (TypeError, ValueError):
                pass
    return trips


def walk_jaxpr_regions(
    jaxpr, inherited: Tuple[str, ...] = (),
    acc: Optional[Dict[Tuple[str, ...], int]] = None, mult: int = 1,
) -> Dict[Tuple[str, ...], int]:
    """Element ops of a jaxpr by kernel-region stack.

    Returns ``{region_stack: ops}`` where ``region_stack`` is the tuple
    of region frames (outermost first; the last entry is the innermost
    region the op is charged to — empty tuple = under no region). Counts
    output elements of `_ELEMENT_OPS`, while×trips, scan×length, and
    recurses into any param carrying a jaxpr; sub-jaxprs inherit the
    parent equation's region stack, because scan/while bodies are
    re-traced without the caller's name stack.
    """
    import numpy as np

    from ..ops.regions import extract_regions

    if acc is None:
        acc = {}
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        regions = (
            tuple(extract_regions(str(eqn.source_info.name_stack)))
            or inherited
        )
        if prim == "while":
            walk_jaxpr_regions(eqn.params["body_jaxpr"].jaxpr, regions, acc,
                               mult * _while_trips(eqn))
            continue
        if prim == "scan":
            walk_jaxpr_regions(eqn.params["jaxpr"].jaxpr, regions, acc,
                               mult * eqn.params["length"])
            continue
        recursed = False
        for p in eqn.params.values():
            # ClosedJaxpr (.jaxpr) or raw Jaxpr (.eqns) — pallas_call
            # carries the latter.
            sub = getattr(p, "jaxpr", p if hasattr(p, "eqns") else None)
            if sub is not None:
                walk_jaxpr_regions(sub, regions, acc, mult)
                recursed = True
        if recursed or prim not in _ELEMENT_OPS:
            continue
        outs = sum(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        acc[regions] = acc.get(regions, 0) + outs * mult
    return acc


def region_coverage(fn, args) -> float:
    """Fraction of a traced callable's element ops under region scopes."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    acc = walk_jaxpr_regions(closed.jaxpr)
    total = sum(acc.values())
    if total <= 0:
        return 0.0
    return sum(n for stack, n in acc.items() if stack) / total


def lint_kernel_regions(
    include_heavy: bool = False,
    min_coverage: float = REGION_MIN_COVERAGE,
    specs=None,
) -> List[LintFinding]:
    """One finding per registry kernel not covered by named regions.

    `specs` overrides the registry list (the negative-fixture tests feed
    a deliberately unannotated toy through the same gate).
    """
    from . import registry

    if specs is None:
        specs = registry.all_kernels(include_heavy=include_heavy)
    findings: List[LintFinding] = []
    for spec in specs:
        try:
            fn, args = spec.build(registry.DEFAULT_BATCH)
            cov = region_coverage(fn, args)
        except Exception as e:  # an untraceable kernel is a finding too
            findings.append(LintFinding(
                spec.name, 0, "region",
                f"region-coverage trace failed: {type(e).__name__}: {e}"))
            continue
        if cov < min_coverage:
            findings.append(LintFinding(
                spec.name, 0, "region",
                f"only {cov:.0%} of element ops run under a region: "
                f"scope (< {min_coverage:.0%}) — annotate the kernel "
                f"with ops/regions.named_region so a profiler trace "
                f"names its ops"))
    return findings
