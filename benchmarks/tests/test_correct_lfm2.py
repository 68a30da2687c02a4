"""``correct`` of the ``lfm2`` cells has to be able to fail: at rehearsal
size on the CPU a sound run is correct, the fp8 control is not, and
neither is a run with any fault of ``faults_lfm2.py`` planted."""
import os

import pytest

import common
import faults_lfm2
import run as run_mod

SPEC = os.path.join(common.HERE, "rehearsal_lfm2.json")


def drive(seed, patch=None, control=0, trace=0):
    rc = run_mod.main(["--spec", SPEC, "--workload", "rehearsal-lfm2",
                       "--seed", str(seed), "--seconds", "1.0", "--trace",
                       str(trace), "--control", str(control)], patch=patch)
    assert rc == 0
    return run_mod.main.last


@pytest.fixture(autouse=True)
def sound_program():
    """A planted fault patches the program's modules: put them back."""
    yield
    faults_lfm2.restore()


def test_sound_run_is_correct_and_control_is_not():
    last = drive(2_900_000_011, control=1)
    assert last["correct"], last["rows"]
    assert last["run"].attempted > 0 and last["run"].failed == 0
    assert last["control_correct"] == {"control": False}, \
        last["run"].notes["stand_ins"]


@pytest.mark.parametrize("fault", sorted(faults_lfm2.FAULTS))
def test_planted_fault_is_not_correct(fault):
    last = drive(2_900_000_012, patch=faults_lfm2.FAULTS[fault])
    assert not last["correct"], last["rows"]
    assert last["run"].failed == 0      # whole answers, wrongly made


def test_traced_run_reports_the_cells_metrics():
    """Every metric the real cell lists that a CPU can read; the device
    shares (rooflines) find no kernel here and are left out."""
    last = drive(2_900_000_013, trace=1)
    run = last["run"]
    assert run.counters["moe.rows"] > 0
    assert run.counters["moe.rows"] >= run.counters["moe.expert_rows_max"]
    assert run.records["counters_in_trace"]["moe.rows"] > 0
    assert run.records["kernel_class_s"] == {}     # no kernel on the CPU
    assert run.records["forward_flops_in_window"] > 0
