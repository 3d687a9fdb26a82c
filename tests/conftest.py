"""Test config: force an 8-device virtual CPU mesh before JAX imports.

Tests validate multi-chip sharding logic without TPU hardware (the driver
separately dry-runs the multichip path via __graft_entry__.dryrun_multichip).
"""

import os
import signal
import sys
import traceback

# The suite validates consensus + sharding logic on an 8-device virtual
# CPU mesh, never on real hardware: pinned here, before jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from bitcoinconsensus_tpu.utils import compile_cache  # noqa: E402

# Persistent compile cache, placed by the same rule as the backend
# (utils/compile_cache.py). NOTE on a hard-won stability story: jaxlib
# intermittently SEGFAULTS on its LARGEST compiles late in a long-lived
# pytest process — observed inside backend_compile_and_load AND in the
# persistent-cache read/write paths, with this cache on and off, with the
# native core on and off; the identical compiles in a clean process always
# pass. The suite therefore runs its two big-compile families
# (interpret-mode pallas equality, the shard_map mesh programs)
# in fresh subprocesses (tests/child_checks.py); the compiles that remain
# in-process are small.
# Set BITCOINCONSENSUS_TPU_TEST_CACHE=0 to disable the cache when
# debugging a suspected cache-layer crash.
if os.environ.get("BITCOINCONSENSUS_TPU_TEST_CACHE", "") in ("0", "off"):
    jax.config.update("jax_enable_compilation_cache", False)
else:
    compile_cache.configure()

import pytest  # noqa: E402

import child_checks  # noqa: E402

# Suite split by marker: `-m kernel` is the device-kernel families whose
# compiles dominate (the limb/curve/SHA ops, the exhaustive group sweep,
# the fresh-process Pallas and mesh checks); `-m consensus` is everything
# else. Measured times are in README.md.
_KERNEL_MODULES = {
    "test_ops_limbs",
    "test_ops_curve",
    "test_ops_sha256",
    "test_pallas_kernel",
    "test_parallel",
    "test_exhaustive_group",
}

# The tier-1 command runs `-n 6 --dist loadfile`: a file belongs to one
# worker, and the run lasts as long as its longest worker.
#
# Every process that dispatches to the kernel traces, lowers and loads (or
# compiles) both rungs of it, minutes of one core each even on a cache hit
# (`warm_kernel`, below). So the 23 files that dispatch are TWO scopes for
# the scheduler, not 23: two workers make the rungs' first calls, one rung
# each under its lock and then the other's, and run all of those files; the
# other four never load the program. Each scope opens with the file whose
# children compile for most of the run: its first test asks for them (the
# file's `children` fixture), its tests that wait for them (the ones with a
# `limit`) come last, and the scope's other files run in between.
# `pytest_xdist_make_scheduler` gives the scopes to xdist;
# `pytest_collection_modifyitems` orders them and fails the collection for a
# file that asks for `warm_kernel` and is in neither. What the scopes are
# worth: the same tree, day and cold cache with a file a unit of work again
# and the children's files first ran 1,256 s for 929-951 s, all six workers
# loading both rungs for 1.5 to 5 minutes a rung (CHANGES.md, PR 44).
_KERNEL_SCOPES = {
    "rungs-a": (
        "test_pallas_kernel",
        "test_block_stream",
        "test_reorg_block",
        "test_multisig_block",
        "test_taproot_block",
        "test_ingress",
        "test_native_block",
        "test_native_idx",
        "test_native_front",
        "test_native_batch",
        "test_block",
        "test_sigcache",
        "test_sigstore",
    ),
    "rungs-b": (
        "test_parallel",
        "test_worst_block",
        "test_quadratic_block",
        "test_ops_sha256",
        "test_benchmark_contract",
        "test_chip_smoke",
        "test_workloads",
        "test_batch",
        "test_dispatch_packing",
        "test_resilience",
        "test_serving",
        "test_obs",
        "test_obs_flight",
    ),
}
_SCOPE_OF = {
    module: scope for scope, modules in _KERNEL_SCOPES.items() for module in modules
}

# xdist 3.8 would hand files out by how many tests they hold
# (`--loadscope-reorder`, its default), which starts the giants last;
# `pytest_configure` turns that off and the collection order decides: the
# two scopes above, then the files that dispatch nothing, the one with
# minutes of compiles of its own first. Files not named follow in
# alphabetical order. Times: CHANGES.md, PR 44.
_FIRST = (
    *_SCOPE_OF,
    "test_exhaustive_group",
    "test_ops_curve",
    "test_ops_limbs",
    "test_mxu_mul",
    "test_regions",
    "test_warm_rungs",
    "test_limit",
)


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """`--dist loadfile` as xdist has it, but for `_KERNEL_SCOPES`: the files
    of one scope are one unit of work, so one worker runs them all."""
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class KernelScopes(LoadFileScheduling):
        def _split_scope(self, nodeid):
            path = super()._split_scope(nodeid)
            module = os.path.splitext(os.path.basename(path))[0]
            return _SCOPE_OF.get(module, path)

    return KernelScopes(config, log)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False
    if not hasattr(config, "workerinput"):
        # The native core builds on first import, in place. In a fresh
        # checkout six workers and the children would each run g++ on the
        # one output file, and a process that loads it half-written goes on
        # without the core: its ~190 native tests skip (step 0 of PR 44 ran
        # so). The controller builds it here, before xdist starts a worker.
        from bitcoinconsensus_tpu import native_bridge

        native_bridge.lib()


# ---------------------------------------------------------------------------
# The suite's one standing report: a line a process (every xdist worker,
# every child a test file started) at the run's end, from the package's own
# compile counters: seconds traced, lowered and in the backend, the
# persistent cache's hits and misses, and what `warm_kernel` waited for a
# rung. The next reader finds them in the run's log (`/tmp/_t1.log`).

_REPORTS = []


def pytest_sessionfinish(session):
    worker = getattr(session.config, "workerinput", {}).get("workerid", "main")
    lines = [
        child_checks.compile_report(f"worker {worker}"),
        *child_checks.CHILD_REPORTS,
    ]
    if hasattr(session.config, "workeroutput"):
        session.config.workeroutput["compile_reports"] = lines
    elif getattr(session.config.option, "dist", "no") == "no":
        _REPORTS.extend(lines)  # else the controller, which ran no test


@pytest.hookimpl(optionalhook=True)
def pytest_testnodedown(node, error):
    _REPORTS.extend(getattr(node, "workeroutput", {}).get("compile_reports", ()))


def pytest_terminal_summary(terminalreporter):
    for line in _REPORTS:
        terminalreporter.write_line(line)


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(_FIRST)}
    scopes = list(_KERNEL_SCOPES)
    module = lambda item: getattr(item.module, "__name__", "")  # noqa: E731

    def place(item):
        name = module(item)
        fixtures = getattr(item, "fixturenames", ())
        if "warm_kernel" in fixtures and name not in _SCOPE_OF:
            raise pytest.UsageError(
                f"{item.nodeid} asks for `warm_kernel`: name its file in "
                "conftest.py `_KERNEL_SCOPES`, or every worker that takes a "
                "file of its kind loads both rungs of the kernel"
            )
        if name not in _SCOPE_OF:
            return (len(scopes), 0, rank.get(name, len(rank)))
        # a scope: its files in order, the waits for the children last
        waits = "children" in fixtures and item.get_closest_marker("limit") is not None
        return (scopes.index(_SCOPE_OF[name]), waits, rank[name])

    items.sort(key=place)
    for item in items:
        if module(item) in _KERNEL_MODULES:
            item.add_marker(pytest.mark.kernel)
        else:
            item.add_marker(pytest.mark.consensus)


REFERENCE_ROOT = os.environ.get("BITCOIN_REFERENCE_ROOT", "/root/reference")
TEST_DATA_DIR = os.path.join(REFERENCE_ROOT, "depend", "bitcoin", "src", "test", "data")


def require_test_data():
    if not os.path.isdir(TEST_DATA_DIR):
        pytest.skip(f"consensus test vectors not found at {TEST_DATA_DIR}")
    return TEST_DATA_DIR


# ---------------------------------------------------------------------------
# A wall limit on every test. The machine has no pytest-timeout, so the
# suite arms one itself: SIGALRM on the worker's main thread fails THAT
# test with its name and the stack it was stuck in, and the worker goes
# on to the next. A Python handler interrupts what hangs in practice
# (joins, futures, sockets, subprocess waits, sleeps); inside one long C
# call (an XLA compile) it fires on return — still a failure, late.
# `@pytest.mark.limit(seconds)` gives one test another figure.

DEFAULT_LIMIT_S = 300


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("limit")
    seconds = float(marker.args[0]) if marker else DEFAULT_LIMIT_S

    def over(_signum, frame):
        pytest.fail(
            f"{item.nodeid} ran over its {seconds:g} s limit, stuck at:\n"
            + "".join(traceback.format_stack(frame, limit=12)),
            pytrace=False,
        )

    launched = launch_times()
    previous = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        result = yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
    _hold_to_the_warm_rungs(item, launched)
    return result


# ---------------------------------------------------------------------------
# The first call of each kernel shape, outside every test's own wait.
# A padded shape's first dispatch in a process traces (13-20 s on an idle
# core here), lowers (5-8 s) and compiles or loads the one-device packed
# program `jit_packed__verify_kernel`: a compile of ~165 s on two to three
# cores from an empty cache, a `cache_load` of 50-70 s on a hit (what
# XLA:CPU's loader takes for an executable of this size), and two to four
# times that under a cold tier-1 run's load (CHANGES.md, PR 44). The modules that
# dispatch ask for this fixture (`pytestmark = usefixtures("warm_kernel")`)
# so that the cost stands under one test's setup and no `timeout=` or
# `join_timeout_s` in a test covers a compile; they keep their batches on
# the two rungs warmed here (at most 7 checks a dispatch for the 8-lane
# rung, at most 15 for the 16-lane one), and they are named in
# `_KERNEL_SCOPES` above, so that two workers pay this and not six.

_WARM_RUNGS = (8, 16)

# ... and the list has one owner: these rungs of the one-device packed
# program are the EC programs tier-1 compiles in-process (the interpret-mode
# Pallas program and the mesh steps compile in children, `child_checks.py`).
# A test that launches the real program at any other lane count fails here
# by name, after its call: by what the call changed in the launch times the
# verifier reports (`consensus_dispatch_launch_seconds{padded}`, set by a
# launch alone). A stand-in behind `_run_packed` (`packed_stub.install_kernel`)
# launches nothing, and one patched over `_packed_program` compiles nothing:
# both are exempt, and neither hides a later test's launch at its shape.
# `tests/test_warm_rungs.py` proves the guard fires.


def launch_times() -> dict:
    """The verifier's launch times so far in this process, by label set."""
    if "bitcoinconsensus_tpu.crypto.jax_backend" not in sys.modules:
        return {}
    import chip_guard

    return {
        tuple(sorted(s["labels"].items())): s["value"]
        for s in chip_guard.samples("consensus_dispatch_launch_seconds")
    }


def _hold_to_the_warm_rungs(item, before):
    backend = sys.modules.get("bitcoinconsensus_tpu.crypto.jax_backend")
    # the program itself is an lru_cache of jits; a test's stand-in is not
    if backend is None or not hasattr(backend._packed_program, "cache_info"):
        return
    beyond = {
        int(dict(labels)["padded"])
        for labels, seconds in launch_times().items()
        if before.get(labels) != seconds
    }.difference(_WARM_RUNGS)
    if beyond:
        pytest.fail(
            f"{item.nodeid} launched the EC program at {sorted(beyond)} lanes: "
            f"tier-1 compiles it at {_WARM_RUNGS} alone (conftest.py "
            "`_WARM_RUNGS`). Keep the dispatch on a warm rung (`chunk=`) or "
            "answer it with `packed_stub.install_kernel`.",
            pytrace=False,
        )


@pytest.fixture(scope="session", name="warm_kernel")
def _warm_kernel():
    # A rung another worker is compiling right now comes last: two workers
    # that start together compile one rung each and load the other's.
    for rung in [r for r in _WARM_RUNGS if not child_checks.warm_rung(r, wait=False)]:
        child_checks.warm_rung(rung)
