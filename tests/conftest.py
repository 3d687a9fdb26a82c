"""Test config: force an 8-device virtual CPU mesh before JAX imports.

Tests validate multi-chip sharding logic without TPU hardware (the driver
separately dry-runs the multichip path via __graft_entry__.dryrun_multichip).
"""

import fcntl
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
# (interpret-mode pallas equality, the 8-device shard_map mesh programs)
# in fresh subprocesses (tests/child_checks.py); the compiles that remain
# in-process are small.
# Set BITCOINCONSENSUS_TPU_TEST_CACHE=0 to disable the cache when
# debugging a suspected cache-layer crash.
if os.environ.get("BITCOINCONSENSUS_TPU_TEST_CACHE", "") in ("0", "off"):
    jax.config.update("jax_enable_compilation_cache", False)
else:
    compile_cache.configure()

import pytest  # noqa: E402

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
# worker, and the run lasts as long as its longest worker. xdist 3.8 would
# hand files out by how many tests they hold (`--loadscope-reorder`, its
# default), which starts the three-test giants last; `pytest_configure`
# turns that off and the collection order below decides. First the two
# files whose children run for most of the run; then the files that make
# the kernel's first calls, since all but the two workers that compile
# wait idle, which leaves the cores to the children; then the files whose
# own compiles keep a core busy for minutes. Files not named follow in
# alphabetical order. Times: CHANGES.md, PR 25.
#
# xdist binds the next file in the queue to a worker as soon as that
# worker's file is down to two tests, whatever those two cost. So the
# seventh place, which goes to `test_pallas_kernel`'s worker at once, is a
# file of milliseconds; and inside a file the tests with a limit of their
# own, the long ones, run first, so that no file is parked behind them.
_FIRST = (
    "test_pallas_kernel",
    "test_parallel",
    "test_batch",
    "test_workloads",
    "test_block",
    "test_chip_smoke",
    "test_api_verify",
    "test_exhaustive_group",
    "test_ops_curve",
    "test_native_block",
)


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    rank = {name: i for i, name in enumerate(_FIRST)}
    module = lambda item: getattr(item.module, "__name__", "")  # noqa: E731
    items.sort(key=lambda item: (
        rank.get(module(item), len(rank)),
        item.get_closest_marker("limit") is None,
    ))
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


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("limit")
    seconds = float(marker.args[0]) if marker else DEFAULT_LIMIT_S

    def over(_signum, frame):
        pytest.fail(
            f"{item.nodeid} ran over its {seconds:g} s limit, stuck at:\n"
            + "".join(traceback.format_stack(frame, limit=12)),
            pytrace=False,
        )

    previous = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


# ---------------------------------------------------------------------------
# The first call of each kernel shape, outside every test's own wait.
# A padded shape's first dispatch in a process traces, lowers and compiles
# (or loads) `_verify_kernel`: about 2 minutes from an empty cache and 30 s
# or more from a warm one, per shape, per worker. The modules that
# dispatch ask for this fixture (`pytestmark = usefixtures("warm_kernel")`)
# so that the cost stands under one test's setup and no `timeout=` or
# `join_timeout_s` in a test covers a compile; they keep their batches on
# the two rungs warmed here (at most 7 checks a dispatch for the 8-lane
# rung, at most 15 for the 16-lane one).

_WARM_RUNGS = (8, 16)


def _first_call(name, call, wait):
    """Make `call`, the first call of program `name` in this process.
    Six workers that start together all miss an empty cache: whoever takes
    this lock first compiles `name` and writes it to the persistent cache,
    the others load it afterwards. Returns False, without calling, when
    another worker is compiling `name` and `wait` is not set."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        call()
        return True
    os.makedirs(cache_dir, exist_ok=True)
    with open(os.path.join(cache_dir, name + ".compiled"), "a+") as fh:
        try:  # the lock goes when fh closes
            fcntl.flock(fh, fcntl.LOCK_EX | (0 if wait else fcntl.LOCK_NB))
        except BlockingIOError:
            return False
        fh.seek(0)
        if not fh.read():
            call()
            fh.write("1")
            return True
    call()
    return True


@pytest.fixture(scope="session", name="warm_kernel")
def _warm_kernel():
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    verifier = TpuSecpVerifier()
    checks = ge._example_checks(max(_WARM_RUNGS) - 1)  # ecdsa/schnorr/tweak

    def first_call(rung, wait):
        batch = checks[: rung - 1]  # one lane of every shape is a sentinel
        assert verifier.pad(len(batch)) == rung

        def call():
            assert verifier.verify_checks(batch).all()

        return _first_call(f"verify_kernel_{rung}", call, wait)

    # A rung another worker is compiling right now comes last: two workers
    # that start together compile one rung each and load the other's.
    for rung in [r for r in _WARM_RUNGS if not first_call(r, wait=False)]:
        first_call(rung, wait=True)
