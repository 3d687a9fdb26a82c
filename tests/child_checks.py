"""Both ends of the suite's fresh-process checks.

jaxlib intermittently segfaults on its largest compiles late in a
long-lived pytest process (see `conftest.py`), so the interpret-mode
Pallas programs (`pallas_equality_check.py`) and the shard_map programs
(`mesh_checks.py`) compile in children. A child runs the checks
it is given one after another — checks that compile the same programs
share a child — and reports each by name (`main`). The test module starts
all of its children at once and each test waits for its own check
(`Children`), so the file costs its longest child, not their sum.

Workers and children share what they compile through the persistent cache:
`first_call` is the lock under which one process compiles a program and the
others wait and load it, `warm_rung` the first call of the one-device XLA
program, `warm_rung_beside` a child's load of it beside its own compile.
Every process ends with one `compile_report` line.
"""

import fcntl
import os
import subprocess
import sys
import threading
import time
import traceback

_PASS = "child check '{}': PASS"
_REPORT = "compile report: "

_STARTED = time.monotonic()
_WARM_NOTES = []  # what `warm_rung` took in this process, for its report

# The compile reports of this process's children, in the order they were
# read (`Children.__exit__`); `conftest.py` prints them at the run's end.
CHILD_REPORTS = []


def compile_report(who, notes=()) -> str:
    """The suite's one standing report, a line a process: what `who` spent
    tracing, lowering and in the backend (a compile, or the persistent
    cache's look-up and load) and how often that cache hit and missed, by
    the package's own counters (`utils/compile_cache.py`), then `notes`."""
    import chip_guard

    def by(name, label):
        return {s["labels"][label]: s["value"] for s in chip_guard.samples(name)}

    secs = by("consensus_compile_seconds_total", "stage")
    cache = by("consensus_compile_cache_total", "result")
    parts = [f"{stage} {secs.get(stage, 0.0):.1f} s"
             for stage in ("trace", "lower", "backend", "cache_load")]
    parts.append(f"cache {int(cache.get('hit', 0))} hit "
                 f"{int(cache.get('miss', 0))} miss")
    return f"{_REPORT}{who}: " + ", ".join([*parts, *_WARM_NOTES, *notes])


def first_call(name, call, wait=True) -> bool:
    """Make `call`, the first call of program `name` in this process.
    Processes that start together all miss an empty cache: whoever takes
    this lock first compiles `name` and writes it to the persistent cache,
    the others load it afterwards (a worker's `warm_kernel`, a child that
    compares with the same program). Returns False, without calling, when
    another process is compiling `name` and `wait` is not set."""
    import jax

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


_WARMING = threading.Lock()  # one first call of a rung at a time in a process


def warm_rung(rung, wait=True) -> bool:
    """The first call in this process of the one-device XLA program at
    `rung` lanes, as the verifier dispatches it, under the lock of its name
    (`first_call`): `conftest.py`'s `warm_kernel` for a worker, and a child's
    step before it compares with that program. What it took goes into this
    process's compile report."""
    import __graft_entry__ as ge
    from bitcoinconsensus_tpu.crypto.jax_backend import TpuSecpVerifier

    verifier = TpuSecpVerifier()
    batch = ge._example_checks(rung - 1)  # ecdsa/schnorr/tweak; one lane is a sentinel
    assert verifier.pad(len(batch)) == rung

    def call():
        t0 = time.monotonic()
        assert verifier.verify_checks(batch).all()
        _WARM_NOTES.append(
            f"rung {rung} first call {time.monotonic() - t0:.1f} s "
            f"after {t0 - _STARTED:.1f} s"
        )

    with _WARMING:
        return first_call(f"verify_kernel_{rung}", call, wait)


def warm_rung_beside(rung) -> None:
    """A child whose own compile is minutes of one thread loads `rung` on a
    second thread meanwhile, once a worker has compiled it (never compiles
    it here: the marker under `first_call`'s lock says when), so that its
    check finds the program called already. Without a cache, or with no
    worker to compile it, the check's own `warm_rung` does as before."""
    import jax

    import bitcoinconsensus_tpu.crypto.jax_backend  # noqa: F401 (places the cache)

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return
    marker = os.path.join(cache_dir, f"verify_kernel_{rung}.compiled")

    def load():
        while not (os.path.exists(marker) and os.path.getsize(marker)):
            time.sleep(2)
        warm_rung(rung)

    threading.Thread(target=load, daemon=True).start()


def main(checks, names, beside=None) -> int:
    """Child side: run `names` in order (with the rung `beside` loading on
    a second thread, `warm_rung_beside`); one PASS line per check that
    held, the traceback of each that did not, and the child's compile
    report as its last line. Returns the exit code."""
    if beside:
        warm_rung_beside(beside)
    failed = False
    took = []
    for name in names:
        t0 = time.monotonic()
        try:
            checks[name]()
        except Exception:
            failed = True
            traceback.print_exc()
        else:
            print(_PASS.format(name), flush=True)
        took.append(f"{name} {time.monotonic() - t0:.1f} s")
    print(compile_report("child " + "-".join(names), took), flush=True)
    return int(failed)


class Children:
    """Parent side, a context manager. `groups` maps a tuple of check
    names (one child) to that child's wall limit in seconds, set from its
    measured cold time; `logs` is a directory for the children's output.
    Children still running at exit are killed."""

    def __init__(self, helper, groups, logs):
        self._started = time.monotonic()
        self._children = {}
        for names, limit in groups.items():
            out = open(os.path.join(logs, "-".join(names) + ".out"), "w+")
            err = open(os.path.join(logs, "-".join(names) + ".err"), "w+")
            proc = subprocess.Popen(
                [sys.executable, helper, *names], stdout=out, stderr=err
            )
            for name in names:
                self._children[name] = (proc, limit, out, err)

    def expect(self, name) -> None:
        """Wait for `name`'s child (up to its limit) and require the
        check's PASS line; fails with the child's output otherwise."""
        proc, limit, out, err = self._children[name]
        left = self._started + limit - time.monotonic()
        try:
            proc.wait(timeout=max(left, 0))
            how = f"rc={proc.returncode}"
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            how = f"killed at its {limit} s limit"
        out.seek(0)
        stdout = out.read()
        if _PASS.format(name) not in stdout.splitlines():
            err.seek(0)
            raise AssertionError(
                f"child check '{name}' did not pass ({how})\n"
                f"stdout:\n{stdout}\nstderr:\n{err.read()[-4000:]}"
            )

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        for proc, _limit, out, err in dict.fromkeys(self._children.values()):
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            out.seek(0)
            CHILD_REPORTS.extend(
                line for line in out.read().splitlines()
                if line.startswith(_REPORT)
            )
            out.close()
            err.close()
