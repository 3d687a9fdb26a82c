"""The per-test wall limit `conftest.py` arms (there is no pytest-timeout
here): run in a child pytest, so the deliberate failure is not in this
run's count."""

import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)

_CASES = '''
import time
import pytest


@pytest.mark.limit(1)
def test_sleeps_past_its_limit():
    time.sleep(30)


def test_next_in_the_same_worker():
    assert True
'''


def test_limit_fails_the_one_test_and_the_worker_goes_on(tmp_path):
    (tmp_path / "test_cases.py").write_text(_CASES)
    res = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-v", "-p", "conftest",
         "-c", os.path.join(ROOT, "pyproject.toml"), "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": TESTS},
    )
    out = res.stdout
    assert res.returncode == 1, out + res.stderr
    assert "test_sleeps_past_its_limit FAILED" in out
    assert "test_next_in_the_same_worker PASSED" in out
    assert "test_sleeps_past_its_limit ran over its 1 s limit" in out
    assert "time.sleep(30)" in out  # the stack it was stuck in
    assert "1 failed, 1 passed" in out
