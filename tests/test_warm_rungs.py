"""The guard `conftest.py` keeps on the EC programs tier-1 compiles
in-process: run in a child pytest, so the deliberate failure is not in this
run's count (as `test_limit.py` does for the wall limit)."""

import os
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(TESTS)

# The real `_packed_program` around a kernel of one op: the program the
# guard watches, compiled in milliseconds at any lane count.
_CASES = '''
import jax.numpy as jnp
import numpy as np
import pytest

from bitcoinconsensus_tpu.crypto import jax_backend as JB
from bitcoinconsensus_tpu.crypto.lane_wire import ROW_BYTES
from packed_stub import host_lane_verdicts, install_kernel


@pytest.fixture(autouse=True)
def one_op_kernel(monkeypatch):
    program = JB._packed_program  # one test below stands in for it
    monkeypatch.setattr(JB, "_verify_kernel", lambda *lanes: lanes[-1])
    program.cache_clear()
    yield
    program.cache_clear()


def _launch(verifier, rows):
    return np.asarray(verifier._run_packed(np.zeros((rows, ROW_BYTES), np.uint8), rows - 1))


def test_on_a_warm_rung():
    assert _launch(JB.TpuSecpVerifier(), 16).shape == (18,)


def test_launches_a_rung_nobody_warmed():
    assert _launch(JB.TpuSecpVerifier(), 32).shape == (34,)


def test_stand_in_at_the_same_rung():
    verifier = install_kernel(JB.TpuSecpVerifier(), lambda args, n: host_lane_verdicts(*args))
    assert _launch(verifier, 64).shape == (66,)


def test_stand_in_over_the_program(monkeypatch):
    monkeypatch.setattr(JB, "_packed_program", lambda backend: (
        lambda packed: jnp.zeros(len(packed) + 2, jnp.int32)))
    assert _launch(JB.TpuSecpVerifier(), 128).shape == (130,)


def test_launches_where_a_stand_in_had():
    assert _launch(JB.TpuSecpVerifier(), 128).shape == (130,)
'''


def test_a_launch_beyond_the_warm_rungs_fails_by_name(tmp_path):
    (tmp_path / "test_cases.py").write_text(_CASES)
    res = subprocess.run(
        [sys.executable, "-m", "pytest", str(tmp_path), "-v", "-p", "conftest",
         "-c", os.path.join(ROOT, "pyproject.toml"), "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "PYTHONPATH": TESTS},
    )
    out = res.stdout
    assert res.returncode == 1, out + res.stderr
    assert "test_on_a_warm_rung PASSED" in out
    assert "test_launches_a_rung_nobody_warmed FAILED" in out
    assert "test_stand_in_at_the_same_rung PASSED" in out
    assert "test_stand_in_over_the_program PASSED" in out
    assert "test_launches_a_rung_nobody_warmed launched the EC program at [32] lanes" in out
    # the gauge is the process's: the stand-in's launch does not hide this one
    assert "test_launches_where_a_stand_in_had launched the EC program at [128] lanes" in out
    assert "2 failed, 3 passed" in out
