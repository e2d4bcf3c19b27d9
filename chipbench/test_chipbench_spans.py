"""The readers of the program's spans: on records made by hand, the
window is taken by count and a reader reads nothing where the program
recorded too few; on each cell driven at smoke size, every reader reads
a number, and the spans lie inside the benchmark's own timings."""
from __future__ import annotations

import importlib
import time

import pytest

from chipbench import smoke, spec
from chipbench.common import RunData
from repro.util import trace

MS = 1_000_000                                   # ns
SAVE = ("save_d2h_s.train", "save_snapshot_s.train", "save_encode_s.train",
        "save_store_s.train")
RESUME = ("restore_fetch_s.resume", "restore_decode_s.resume",
          "program_load_s.resume")
SERVE = ("admit_host_ms.serve", "decode_host_ms.serve")


@pytest.fixture(autouse=True)
def fresh():
    trace.clear()
    yield
    trace.clear()


def read(name: str, run: RunData):
    return spec.metric_reader(name).read(run)


def span(name, lo, hi, parent=None, **attrs):
    """A record from `lo` to `hi` ms."""
    return trace.record(name, lo * MS, hi * MS, parent=parent, **attrs)


def made_up_saves(scale: float):
    """One `trainer.save` and what it submitted, every time `scale`
    times the first's."""
    t = 1000 * scale
    save = span("trainer.save", t, t + 50 * scale)
    span("trainer.save.drain", t, t + 10 * scale, save)
    span("trainer.save.d2h", t + 10 * scale, t + 30 * scale, save)
    submit = span("ckpt.submit", t + 30 * scale, t + 45 * scale, save)
    span("ckpt.wait", t + 30 * scale, t + 35 * scale, submit)
    ckpt = span("ckpt.save", t + 50 * scale, t + 200 * scale, save)
    span("ckpt.save.encode", t + 50 * scale, t + 60 * scale, ckpt)
    span("ckpt.save.write", t + 60 * scale, t + 100 * scale, ckpt)
    span("ckpt.save.encode", t + 100 * scale, t + 110 * scale, ckpt)
    span("ckpt.save.write", t + 110 * scale, t + 150 * scale, ckpt)
    span("ckpt.save.commit", t + 150 * scale, t + 200 * scale, ckpt)


def test_save_readers_take_the_last_saves():
    made_up_saves(1.0)
    made_up_saves(2.0)
    one = RunData(save_commit_s=[0.2])
    assert read("save_d2h_s.train", one) == pytest.approx(0.040)
    assert read("save_snapshot_s.train", one) == pytest.approx(0.020)
    assert read("save_encode_s.train", one) == pytest.approx(0.040)
    assert read("save_store_s.train", one) == pytest.approx(0.260)
    both = RunData(save_commit_s=[0.1, 0.2])
    assert read("save_store_s.train", both) == pytest.approx(0.195)
    for name in SAVE:
        assert read(name, RunData(save_commit_s=[0.1, 0.2, 0.3])) is None
        assert read(name, RunData(save_commit_s=[])) is None


def made_up_resume(t: float, fetch: float, decode: float):
    run = span("trainer.run", t, t + 100)
    restore = span("trainer.restore", t, t + 50, run)
    ckpt = span("ckpt.restore", t + 1, t + 1 + fetch + decode, restore)
    span("ckpt.restore.fetch", t + 1, t + 1 + fetch, ckpt)
    span("ckpt.restore.decode", t + 1 + fetch, t + 1 + fetch + decode, ckpt)
    # compiles: one in the restore (tracing), two nested at the step
    span("jit.compile", t + 40, t + 45, restore)
    span("jit.compile", t + 60, t + 80, run)
    span("jit.compile", t + 65, t + 70, run)


def test_resume_readers_take_the_last_roots():
    made_up_resume(0, 30, 2)
    made_up_resume(1000, 10, 4)
    made_up_resume(2000, 20, 6)
    two = RunData(resumes=2)
    assert read("restore_fetch_s.resume", two) == pytest.approx(0.015)
    assert read("restore_decode_s.resume", two) == pytest.approx(0.005)
    assert read("program_load_s.resume", two) == pytest.approx(0.025)
    for name in RESUME:
        assert read(name, RunData(resumes=4)) is None
        assert read(name, RunData(resumes=0)) is None


def test_serve_readers_take_the_last_admissions_and_decodes():
    for i, wait in enumerate((5, 30, 50)):
        add = span("engine.add", 100 * i, 100 * i + 60, sid=i)
        span("engine.fork", 100 * i, 100 * i + 1, add)
        span("engine.prefill.wait", 100 * i + 60 - wait, 100 * i + 60, add)
    for i, (batch, wait) in enumerate(((2, 1), (2, 8), (0, 0), (2, 6))):
        step = span("engine.step", 1000 + 20 * i, 1010 + 20 * i,
                    batch=batch)
        if batch:
            span("engine.step.wait", 1010 + 20 * i - wait, 1010 + 20 * i,
                 step)
    run = RunData(add_s=[0.06, 0.06], steps=[(2, 10), (2, 10)])
    assert read("admit_host_ms.serve", run) == pytest.approx(20.0)
    assert read("decode_host_ms.serve", run) == pytest.approx(3.0)
    too_many = RunData(add_s=[0.06] * 4, steps=[(2, 10)] * 4)
    nothing = RunData(add_s=[], steps=[])
    for name in SERVE:
        assert read(name, too_many) is None
        assert read(name, nothing) is None


@pytest.mark.parametrize("name,metrics", [
    ("smollm-360m.train-ckpt", SAVE),
    ("smollm-360m.resume", RESUME),
    ("smollm-360m.serve-sessions", SERVE)])
def test_readers_read_the_cell_at_smoke_size(name, metrics):
    """Each new reader reads a number from the program's spans in a run
    of its cell, and the spans lie inside the timings the benchmark
    takes around the same calls."""
    cell = smoke.tiny_cell(name)
    assert {m["name"] for m in cell.per_layer} >= set(metrics)
    driver = importlib.import_module(
        f"chipbench.drivers.{cell.traffic['driver']}")
    data = driver.run(cell, 2**33 + 11, 1.0, False, time.perf_counter())[
        "data"]
    got = {m: read(m, data) for m in metrics}
    assert all(v is not None and v > 0 for v in got.values()), got
    if name.endswith("resume"):
        read_s = read("restore_read_s.resume", data)
        assert got["restore_fetch_s.resume"] + \
            got["restore_decode_s.resume"] <= read_s
    if name.endswith("serve-sessions"):
        adds = [r for r in trace.records("engine.add")
                if r.parent is None][-len(data.add_s):]
        waits = sum(w.seconds for a in adds
                    for w in trace.children(a, "engine.prefill.wait"))
        assert got["admit_host_ms.serve"] + 1e3 * waits / len(adds) <= \
            read("prefill_ms.serve", data)
    if name.endswith("train-ckpt"):
        assert got["save_encode_s.train"] + got["save_store_s.train"] <= \
            read("save_commit_s.train", data)
