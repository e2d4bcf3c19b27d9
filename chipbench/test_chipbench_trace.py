"""The trace reduction: busy as a union, idle gaps named by host spans,
device time per program, on made-up events and on a small trace
recorded on a TPU v5e (`testdata/record_trace.py`)."""
from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import device_trace as dt

RECORDED = Path(__file__).parent / "testdata" / "tiny_tpu.xplane.pb"
MS = 1e6                                        # ns


def test_union_merges_and_clips():
    got = dt.union([(5, 8), (0, 3), (2, 4), (7, 12), (20, 30)], 1, 25)
    assert got == [(1, 4), (5, 12), (20, 25)]
    assert dt.gaps(got, 0, 26) == [(0, 1), (4, 5), (12, 20), (25, 26)]


def _events():
    ev = dt.Events()
    dev = "/device:TPU:0"
    # two calls of a step (40 ms each, overlapping ops counted once),
    # one small program, and a 30 ms gap under a host span
    ev.ops[dev] = [("fusion.1", 10 * MS, 30 * MS), ("fusion.2", 30 * MS,
                                                   20 * MS),
                   ("fusion.1", 80 * MS, 40 * MS), ("copy", 120 * MS,
                                                    5 * MS)]
    ev.modules[dev] = [("jit_train_step(7)", 10 * MS, 40 * MS),
                       ("jit_train_step(7)", 80 * MS, 40 * MS),
                       ("jit_copy(2)", 120 * MS, 5 * MS),
                       ("jit_train_step(7)", 200 * MS, 40 * MS)]
    ev.spans = [("bench.window", 0, 150 * MS),
                ("bench.trainer.save", 52 * MS, 26 * MS),
                ("bench.pipeline.next", 49 * MS, 2 * MS)]
    return ev


def test_summary_of_made_up_events():
    s = dt.summarize(_events())
    assert s.window_s == pytest.approx(0.150)
    # busy: [10, 50) and [80, 125) ms
    assert s.busy_s == pytest.approx(0.085)
    assert s.idle_share == pytest.approx(1 - 85 / 150)
    # the call at 200 ms lies outside the window
    assert s.module_seconds(r"^jit_train_step$") == (pytest.approx(0.080), 2)
    assert s.module_seconds(r"^jit_copy$") == (pytest.approx(0.005), 1)
    names = dict(s.idle_gaps)
    # [50, 80) is the save's; [0, 10) and [125, 150) have no span over them
    assert s.idle_gaps[0] == ("trainer.save", pytest.approx(0.030))
    assert set(names) == {"trainer.save", "host:other"}
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.070)]
    assert len(b["idle_gaps"]) == 3


def test_programs_known_by_the_kernel_they_run():
    """Two programs of one name; only the second runs the custom call."""
    ev = dt.Events()
    dev = "/device:TPU:0"
    kernel = '%closed_call.3 = bf16[4] custom-call(%a), ' \
        'custom_call_target="tpu_custom_call"'
    ev.ops[dev] = [("%fusion.1 = f32[4] fusion(%x)", 10 * MS, 20 * MS),
                   (kernel, 42 * MS, 3 * MS), (kernel, 47 * MS, 3 * MS),
                   ("%fusion.2 = f32[4] fusion(%y)", 50 * MS, 5 * MS)]
    ev.modules[dev] = [("jit__unknown(11)", 10 * MS, 20 * MS),
                       ("jit__unknown(22)", 40 * MS, 20 * MS)]
    ev.spans = [("bench.window", 0, 100 * MS)]
    s = dt.summarize(ev)
    assert s.module_seconds(r"^jit__unknown$") == (pytest.approx(0.040), 2)
    running = s.seconds_of_programs_running("tpu_custom_call")
    assert running == (pytest.approx(0.020), 1)
    assert s.kernel_seconds("tpu_custom_call") == pytest.approx(0.006)
    assert s.kernel_seconds("other_target") == 0


def test_no_device_events_is_an_error():
    ev = _events()
    ev.ops, ev.modules = {}, {}
    with pytest.raises(ValueError):
        dt.summarize(ev)


def test_recorded_tpu_trace():
    """Three calls of one program with a 20 ms host span between the
    second and the third, inside `bench.window`, on a v5e."""
    ev = dt.load(str(RECORDED))
    assert list(ev.ops) == ["/device:TPU:0"]
    s = dt.summarize(ev)
    assert s.module_seconds(r"^jit_small_matmul$")[1] == 3
    assert 0 < s.busy_s < s.window_s
    assert s.idle_gaps[0][0] == "host_wait"
    assert s.idle_gaps[0][1] == pytest.approx(0.020, rel=0.5)
