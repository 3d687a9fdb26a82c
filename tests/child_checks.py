"""Both ends of the suite's fresh-process checks.

jaxlib intermittently segfaults on its largest compiles late in a
long-lived pytest process (see `conftest.py`), so the interpret-mode
Pallas programs (`pallas_equality_check.py`) and the 8-device shard_map
programs (`mesh_checks.py`) compile in children. A child runs the checks
it is given one after another — checks that compile the same programs
share a child — and reports each by name (`main`). The test module starts
all of its children at once and each test waits for its own check
(`Children`), so the file costs its longest child, not their sum.
"""

import os
import subprocess
import sys
import time
import traceback

_PASS = "child check '{}': PASS"


def main(checks, names) -> int:
    """Child side: run `names` in order; one PASS line per check that
    held, the traceback of each that did not. Returns the exit code."""
    failed = False
    for name in names:
        try:
            checks[name]()
        except Exception:
            failed = True
            traceback.print_exc()
        else:
            print(_PASS.format(name), flush=True)
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
        for proc, _limit, out, err in self._children.values():
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            out.close()
            err.close()
