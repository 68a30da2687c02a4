"""The trace reduction: on planes built by hand (every number below can be
checked on paper), and on a recorded chip trace kept in ``testdata/``."""
import glob
import os

import pytest

import common
import trace as tr

MS = 1_000_000  # ns


def _planes():
    # one device; window 0..100 ms.  Two executions of jit_step_fn:
    #   10..40 ms holding fusion.1 10..20, a while 20..40 with a kernel
    #   custom-call.7 22..30 and fusion.2 30..38 inside it;
    #   60..80 ms holding custom-call.7 60..70 and fusion.1 70..80.
    # Idle: 0..10 (generator), 40..60 (fence), 80..100 (nothing).
    ops = [["fusion.1", 10 * MS, 10 * MS], ["while.3", 20 * MS, 20 * MS],
           ["custom-call.7", 22 * MS, 8 * MS], ["fusion.2", 30 * MS, 8 * MS],
           ["custom-call.7", 60 * MS, 10 * MS], ["fusion.1", 70 * MS, 10 * MS]]
    mods = [["jit_step_fn(1)", 10 * MS, 30 * MS],
            ["jit_step_fn(1)", 60 * MS, 20 * MS]]
    spans = [[tr.WINDOW_SPAN, 0, 100 * MS], ["generator", 0, 9 * MS],
             ["fence", 41 * MS, 18 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "modules": mods,
                         "ops": ops}], "spans": spans}


def test_reduction_by_hand():
    red = tr.reduce_planes(_planes(), r"custom-call")
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.100)
    assert red.busy_s == pytest.approx(0.050)          # 30 + 20 ms
    prog = red.program("jit_step_fn")
    assert prog["durations_s"] == pytest.approx([0.030, 0.020])
    assert prog["kernel_s"] == pytest.approx([0.008, 0.010])
    gaps = dict(red.idle_gaps)
    assert gaps["generator"] == pytest.approx(0.010)
    assert gaps["fence"] == pytest.approx(0.020)
    assert gaps[tr.UNCOVERED] == pytest.approx(0.020)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)
    ops = dict(red.device_ops)
    assert ops["fusion.1"] == pytest.approx(0.020)
    assert ops["custom-call.7"] == pytest.approx(0.018)
    assert ops["while.3"] == pytest.approx(0.004)      # 20 - 8 - 8: self time
    assert sum(ops.values()) == pytest.approx(red.busy_s)


def test_window_clips_and_no_device_reads_nothing():
    planes = _planes()
    planes["spans"][0] = [tr.WINDOW_SPAN, 15 * MS, 50 * MS]   # 15..65 ms
    red = tr.reduce_planes(planes, r"custom-call")
    assert red.window_s == pytest.approx(0.050)
    assert red.busy_s == pytest.approx(0.030)          # 15..40 and 60..65
    empty = tr.reduce_planes({"devices": [], "spans": []})
    assert empty.devices == 0 and empty.program("jit_step_fn") is None


def _metric(name):
    return common.load_module(
        os.path.join(common.HERE, "metrics", name + ".py"),
        "metric_" + name.replace(".", "_"))


def test_readers_return_nothing_without_a_trace():
    import harness
    Run = harness.Run(cell={}, cfg={}, traffic={}, limits={}, peaks={},
                      family=None, ref=None, seed=0, seconds=1.0,
                      traced=True, t_proc=0.0, scratch="")
    for name in ("step_ms.train", "decode_step_ms.serve",
                 "flash_roofline.train", "decode_attn_roofline.serve",
                 "device_idle_share.train", "mfu.train", "mfu.serve",
                 "decode_batch_occupancy.serve", "queue_wait_p95_ms.serve"):
        assert _metric(name).read(Run) is None, name


RECORDED = sorted(glob.glob(os.path.join(common.HERE, "testdata",
                                         "*.planes.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    """The reduction of a trace recorded on the v5e equals the numbers
    written down beside it when it was looked at by hand."""
    planes = tr.load_planes(path)
    want = common.load_json(path.replace(".planes.json.gz", ".expect.json"))
    red = tr.reduce_planes(planes, want["kernel_op"])
    assert red.devices == want["devices"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 100 * (1 - red.busy_s / red.window_s) == \
        pytest.approx(want["idle_share_pct"], rel=1e-6)
    prog = red.program(want["program"])
    assert len(prog["durations_s"]) == want["executions"]
    assert sum(prog["kernel_s"]) == pytest.approx(want["kernel_s"], rel=1e-9)
    assert red.device_ops[0][0] == want["top_op"]
