"""``correct`` of the ``nemotron_h`` cells has to be able to fail: at
rehearsal size on the CPU a sound run is correct, the fp8 control is not,
and neither is a run with any fault of ``faults_nemotron_h.py`` planted
but the ones it names as beyond the comparison (``CANNOT_SEE``), which
are held to reading as a sound run: the blind spot is written down, not
hoped away."""
import os

import pytest

import common
import faults_nemotron_h
import run as run_mod

SPEC = os.path.join(common.HERE, "rehearsal_nemotron_h.json")


def drive(seed, patch=None, control=0, trace=0):
    rc = run_mod.main(["--spec", SPEC, "--workload", "rehearsal-nemotron-h",
                       "--seed", str(seed), "--seconds", "1.0", "--trace",
                       str(trace), "--control", str(control)], patch=patch)
    assert rc == 0
    return run_mod.main.last


@pytest.fixture(autouse=True)
def sound_program():
    """A planted fault patches the program's modules: put them back."""
    yield
    faults_nemotron_h.restore()


def test_sound_run_is_correct_and_control_is_not():
    last = drive(2_900_000_011, control=1)
    assert last["correct"], last["rows"]
    assert last["run"].attempted > 0 and last["run"].failed == 0
    assert last["control_correct"] == {"control": False}, \
        last["run"].notes["stand_ins"]


@pytest.mark.parametrize("fault", sorted(faults_nemotron_h.FAULTS))
def test_planted_fault_is_not_correct_unless_beyond_sight(fault):
    last = drive(2_900_000_012, patch=faults_nemotron_h.FAULTS[fault])
    assert last["correct"] == (fault in faults_nemotron_h.CANNOT_SEE), \
        last["rows"]
    assert last["run"].failed == 0      # whole answers, wrongly made


def test_traced_run_reports_the_cells_metrics():
    """Every metric the real cell lists that a CPU can read; the device
    shares (rooflines) find no kernel here and are left out."""
    last = drive(2_900_000_013, trace=1)
    run = last["run"]
    held, away = (run.counters[c] for c in ("moe.rows",
                                            "moe.rows_elsewhere"))
    assert held > 0 and away > 0
    assert held >= run.counters["moe.expert_rows_max"]
    assert run.records["counters_in_trace"]["moe.rows_elsewhere"] > 0
    assert run.records["kernel_class_s"] == {}     # no kernel on the CPU
    assert run.records["forward_flops_in_window"] > 0
