"""Drive the rest of a run past the harness's look for a chip, on the CPU
at rehearsal size: sound it ends `correct`, and with the timed path broken
underneath (or the truth table shifted) it ends not correct."""

import pytest

import run
from benchmarks.harness import chipguard
from benchmarks.harness.cell import CONTROLS

CELLS = ["tip-block.cold", "tip-block.warm", "mempool-serve.steady"]


def _run(workload, control, seed):
    spec = run.load_spec(workload, rehearsal=True)
    if "corrupt_tx_share" in spec["traffic"]:
        spec["traffic"]["corrupt_tx_share"] = 0.1  # a few corrupted txs among the window's thirty
    dev = dict(chipguard.device_info(), count=1)
    return run.run_cell(spec, seed, 2.0, False, dev, control=control)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = _run(workload, None, 41)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"setup_s"} and len(line["metrics"]) >= 2


@pytest.mark.parametrize("control", CONTROLS)
@pytest.mark.parametrize("workload", CELLS)
def test_broken_run_is_not_correct(workload, control):
    line = _run(workload, control, 43)
    assert line["correct"] is False
