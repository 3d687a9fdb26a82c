"""CPU checks of what `chip_smoke.py` guarantees on the chip.

The legs run at tiny size with `xla` as the expected top rung (there is
no Pallas below a TPU); `main()` must refuse the CPU; a launch that raises
must fail the smoke with the exception's text in its output even though
the ladder keeps every verdict right; and the compile cache must follow
the placement rule both ways.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_guard
import chip_smoke
from conftest import launch_times

pytestmark = pytest.mark.usefixtures("warm_kernel")  # conftest.py: first calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Every dispatch stays on the 8- and 16-lane rungs `warm_kernel` has called.
TINY = dict(
    batch_inputs=8, block_inputs=8, serve_requests=8, serve_cold=4,
    serve_threads=2,
)


@pytest.fixture
def verifier(monkeypatch):
    """A fresh process-wide verifier, so ladder state neither leaks in from
    earlier tests nor out of these."""
    from bitcoinconsensus_tpu import native_bridge
    from bitcoinconsensus_tpu.crypto import jax_backend

    if not native_bridge.available():
        pytest.skip("the smoke requires the native core")
    v = jax_backend.TpuSecpVerifier()
    monkeypatch.setattr(jax_backend, "_default", v)
    return v


def _json_lines(text):
    return [json.loads(line) for line in text.splitlines() if line.startswith("{")]


def test_legs_pass_at_tiny_size_on_the_top_rung(verifier, capsys):
    dev = chip_guard.device_info()
    before = launch_times()
    assert chip_smoke.run(dev, seed=21, backend="xla", **TINY) == 0
    lines = _json_lines(capsys.readouterr().out)
    assert [l.get("leg") for l in lines[:3]] == [
        "verify_batch", "connect_block", "serving",
    ]
    assert all(l["device"] == dev for l in lines)
    for leg in lines[:3]:
        assert leg["mismatches"] == 0 and leg["dispatches"]["xla"] > 0
    assert lines[0]["cached_replay"]["dispatches"] == 0
    assert lines[1]["corrupted_block"]["reason"] == "block-validation-failed"
    assert lines[2]["shed"] == 0 and lines[2]["pending_after_close"] == 0
    # the gauge is the process's: the legs' shapes are the ones they changed
    launched = {dict(labels)["padded"] for labels, secs in launch_times().items()
                if before.get(labels) != secs}
    shapes = [s for s in lines[3]["launch_seconds"] if str(s["padded"]) in launched]
    assert shapes and all(
        s["backend"] == "xla" and s["first_seconds"] is not None for s in shapes
    )
    assert lines[-1] == {"ok": True, "device": dev}
    assert verifier._resilience.ladder.current == "xla"


def test_wrong_expected_rung_fails(verifier, capsys):
    # What the chip run would see if the verifier came up without Pallas.
    dev = chip_guard.device_info()
    assert chip_smoke.run(dev, seed=21, backend="pallas", **TINY) == 1
    assert "top rung is 'xla'" in capsys.readouterr().err


def test_main_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "platform is 'cpu'" in out.err


def test_main_refuses_without_the_native_core(monkeypatch, capsys):
    from bitcoinconsensus_tpu import native_bridge

    monkeypatch.setattr(
        chip_guard, "require_tpu", lambda: {"platform": "tpu"}
    )
    monkeypatch.setattr(native_bridge, "available", lambda: False)
    monkeypatch.setattr(native_bridge, "why_absent", lambda: "g++: not found")
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "native host core did not load: g++: not found" in out.err


def test_launch_failure_fails_the_smoke_and_keeps_its_text(verifier, capsys, monkeypatch):
    from bitcoinconsensus_tpu.crypto import jax_backend

    def refuse(packed):
        raise RuntimeError("Mosaic failed to compile: scoped vmem exceeded")

    monkeypatch.setattr(jax_backend, "_packed_program", lambda backend: refuse)
    dev = chip_guard.device_info()
    assert chip_smoke.run(dev, seed=21, backend="xla", **TINY) == 1
    out = capsys.readouterr()
    # The ladder did its job — no mismatch was reported — and the smoke
    # still failed, naming the counters that moved and the reason.
    assert "left the device path" in out.err
    assert "Mosaic failed to compile: scoped vmem exceeded" in out.err
    assert "consensus_inflight_failures_total" in out.err
    last = _json_lines(out.out)[-1]
    assert last["ok"] is False
    assert last["last_failure"]["exc"] == "RuntimeError"
    assert last["last_failure"]["stage"] == "launch"
    assert "scoped vmem exceeded" in last["last_failure"]["error"]
    assert verifier._resilience.ladder.current == "host"


_CACHE_PROBE = """
import json, jax
calls = []
update = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), update(k, v))
import bitcoinconsensus_tpu.crypto.jax_backend
from bitcoinconsensus_tpu.utils import compile_cache
print(json.dumps({"calls": calls, "dir": jax.config.jax_compilation_cache_dir,
                  "default": compile_cache.DEFAULT_DIR}))
"""


@pytest.mark.parametrize("placed", ["/tmp/placed-from-outside", None])
def test_compile_cache_placement(placed):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300, check=True,
    )
    got = json.loads(res.stdout.splitlines()[-1])
    assert got["default"] == os.path.join(ROOT, ".jax_cache")
    if placed:
        assert "jax_compilation_cache_dir" not in got["calls"]
        assert got["dir"] == placed
    else:
        assert got["dir"] == got["default"]
