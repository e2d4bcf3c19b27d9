"""The span tracer (`repro.util.trace`): nesting and self time, the ring
bound, the parent across the I/O pool, compile and collection
records, spans in the profiler's trace, and the spans the trainer, the
checkpoint manager and the serving engine record."""
import gc
import glob
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, CheckpointManager
from repro.configs import get_smoke_config
from repro.core import Cluster
from repro.models import get_model
from repro.serving.engine import Engine, EngineConfig
from repro.util import trace

from test_trainer import _setup

MS = 1_000_000                                   # ns


@pytest.fixture(autouse=True)
def fresh():
    trace.clear()
    yield
    trace.clear()


@pytest.fixture()
def cluster(tmp_path):
    c = Cluster(n_servers=2, data_dir=str(tmp_path / "wtf"), replication=1)
    yield c
    c.close()


def test_nesting_and_self_time():
    with trace.span("outer", step=3) as outer:
        with trace.span("inner") as inner:
            time.sleep(0.01)
        time.sleep(0.005)
    assert inner.parent == outer.id and outer.parent is None
    assert outer.attrs == {"step": 3}
    assert inner.thread == outer.thread == threading.get_ident()
    assert trace.children(outer) == [inner]
    assert trace.records("outer") == [outer]
    assert trace.self_time(outer) == pytest.approx(
        outer.seconds - inner.seconds)
    assert trace.self_time(inner) == inner.seconds
    assert trace.current() is None
    row = trace.summary()["outer"]
    assert row["count"] == 1 and row["max_s"] == outer.seconds
    assert row["self_s"] == pytest.approx(trace.self_time(outer))


def test_ring_keeps_the_newest_and_counts_the_dropped():
    t = trace.Tracer(ring=3)
    for i in range(5):
        with t.span("s", i=i):
            pass
    assert [r.attrs["i"] for r in t.records("s")] == [2, 3, 4]
    assert t.dropped("s") == 2
    assert t.summary()["s"]["dropped"] == 2


def test_covered_is_the_union_of_nested_compile_intervals():
    """Compile events nest (cache retrieval inside backend compile): the
    union counts each instant once and is clipped to the span."""
    root = trace.record("trainer.run", 0, 120 * MS)
    for lo, hi in ((0, 100), (20, 50), (90, 130)):
        trace.record("jit.compile", lo * MS, hi * MS, parent=root)
    got = trace.children(root, "jit.compile")
    assert len(got) == 3
    assert trace.covered(got, root) == pytest.approx(0.120)
    assert trace.covered(got) == pytest.approx(0.130)
    assert trace.self_time(root) == pytest.approx(0.0)


def test_compile_events_recorded_under_the_open_span():
    with trace.span("compiling") as rec:
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    got = trace.children(rec, "jit.compile")
    events = {r.attrs["event"] for r in got}
    assert any("backend_compile" in e for e in events), events
    assert not any("time_saved" in e for e in events)
    assert all(rec.start_ns <= r.start_ns <= r.end_ns <= rec.end_ns + MS
               for r in got)


def test_forced_collection_is_a_py_gc_record():
    with trace.span("collecting") as rec:
        gc.collect()
    got = trace.children(rec, "py.gc")
    assert any(r.attrs["generation"] == 2 for r in got)
    assert all(isinstance(r.attrs["collected"], int) for r in got)
    row = trace.summary()["py.gc"]
    assert row["attrs"]["generation"] == 2          # a label: the largest
    assert row["attrs"]["collected"] == sum(
        r.attrs["collected"] for r in trace.records("py.gc"))


def test_collections_with_no_span_open_stay_bounded():
    """A process that opens no span never drains the collector's queue:
    it holds at most a ring's worth, and counts what fell out."""
    t = trace.Tracer(ring=8)
    gc.callbacks.append(t.on_gc)
    try:
        for _ in range(50):
            gc.collect()
    finally:
        gc.callbacks.remove(t.on_gc)
    assert len(t._pending) == 8
    assert len(t.records("py.gc")) == 8
    assert t.dropped("py.gc") >= 42
    assert t.summary()["py.gc"]["dropped"] == t.dropped("py.gc")


def test_span_in_the_profiler_trace(tmp_path):
    """The span lies in the profiler's trace as `wtf.<name>`, as long as
    its in-memory record to within 1 ms."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("probe") as rec:
            time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    found = [e.duration_ns for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name == "wtf.probe"]
    assert len(found) == 1
    assert abs(found[0] - (rec.end_ns - rec.start_ns)) < MS


def test_async_save_recorded_under_its_submitter(cluster):
    """The save runs on a pool worker; its `ckpt.save` names the span that
    submitted it, and its parts nest under it there."""
    mgr = CheckpointManager(cluster.client(), "/ckpt")
    ack = AsyncCheckpointer(mgr)
    tree = {"w": np.arange(64, dtype=np.float32), "b": np.ones(3)}
    with trace.span("trainer.save") as submitter:
        ack.save(1, tree)
    ack.wait()
    (submit,) = trace.children(submitter, "ckpt.submit")
    (save,) = trace.children(submitter, "ckpt.save")
    assert save.thread != submitter.thread
    assert save.attrs["step"] == 1 and save.attrs["store_batches"] >= 1
    assert save.attrs["data_bytes_written"] >= 64 * 4 + 3 * 8
    assert submit.attrs["bytes"] == 64 * 4 + 3 * 8
    assert not trace.children(submit)       # the wait had no save before
    (wait,) = trace.children(submitter, "io.op_wait")
    assert wait.thread == save.thread
    assert len(trace.children(save, "ckpt.save.encode")) == 2
    assert len(trace.children(save, "ckpt.save.write")) == 2
    assert len(trace.children(save, "ckpt.save.commit")) == 1
    assert trace.records("ckpt.wait")       # the save was awaited
    row = trace.summary()["ckpt.save"]["attrs"]
    assert row == {"step": 1, "bytes": save.attrs["bytes"],
                   "store_batches": save.attrs["store_batches"],
                   "data_bytes_written": save.attrs["data_bytes_written"]}


def test_trainer_save_and_restore_spans(cluster):
    """A toy-width trainer with one asynchronous save, then a restart
    that restores it: every span the benchmark's readers read, under the
    parent they look for it under."""
    trainer, _ = _setup(cluster, steps=2, ckpt_every=2)
    trainer.run()
    (first,) = trace.records("trainer.run")
    (save,) = trace.children(first, "trainer.save")
    assert save.attrs["step"] == 2
    for name in ("trainer.save.drain", "trainer.save.d2h", "ckpt.submit",
                 "ckpt.save"):
        assert len(trace.children(save, name)) == 1, name
    (ckpt,) = trace.children(save, "ckpt.save")
    assert trace.children(ckpt, "ckpt.save.encode")
    assert trace.children(ckpt, "ckpt.save.write")
    assert trace.children(ckpt, "ckpt.save.commit")

    again, _ = _setup(cluster, steps=3, ckpt_every=10)
    again.run()
    second = trace.records("trainer.run")[-1]
    assert second.parent is None and second.attrs["step"] == 2
    (restore,) = trace.children(second, "trainer.restore")
    assert trace.children(restore, "trainer.device_put")
    (ckpt,) = trace.children(restore, "ckpt.restore")
    assert ckpt.attrs["bytes"] > 0 and ckpt.attrs["fetch_batches"] >= 1
    (fetch,) = trace.children(ckpt, "ckpt.restore.fetch")
    (decode,) = trace.children(ckpt, "ckpt.restore.decode")
    assert fetch.end_ns <= decode.start_ns
    assert trace.children(second, "jit.compile")


def test_engine_add_and_step_spans():
    cfg = get_smoke_config("smollm-360m").replace(compute_dtype="float32")
    model = get_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 EngineConfig(page_tokens=4, num_pages=64))
    rng = np.random.RandomState(0)
    root = eng.add(rng.randint(0, cfg.vocab, 6).astype(np.int32), max_new=1)
    # forks mid-page: the open page is copied at admission
    sid = eng.add(rng.randint(0, cfg.vocab, 9).astype(np.int32), max_new=3,
                  fork_from=root)
    eng.step()
    eng.release(sid)
    assert eng.step() == []
    first, fork = trace.records("engine.add")
    assert fork.attrs == {"sid": sid, "prompt_tokens": 9,
                          "shared_tokens": 6}
    assert not trace.children(first, "engine.fork")
    for name in ("engine.fork", "engine.cow", "engine.prefill.wait"):
        assert len(trace.children(fork, name)) == 1, name
    step, idle = trace.records("engine.step")
    # 10 tokens in one block of the table's 3 pages, for the one KV head
    assert step.attrs == {"batch": 1, "table_width": 3, "kv_blocks": 1,
                          "kv_blocks_grid": 1}
    assert idle.attrs == {"batch": 0} and not trace.children(idle)
    (prepare,) = trace.children(step, "engine.step.prepare")
    (wait,) = trace.children(step, "engine.step.wait")
    assert prepare.end_ns <= wait.start_ns


def test_engine_step_kv_blocks_end_mid_block():
    """Pages of 128 tokens make blocks of two pages: after the step the
    long sequence (301 tokens) ends inside its second block, and the
    short one's second block lies wholly past its end."""
    cfg = get_smoke_config("smollm-360m").replace(
        compute_dtype="float32", n_heads=4, n_kv_heads=2)
    model = get_model(cfg)
    eng = Engine(model, model.init(jax.random.PRNGKey(0)),
                 EngineConfig(page_tokens=128, num_pages=8))
    rng = np.random.RandomState(0)
    for n in (5, 300):
        eng.add(rng.randint(0, cfg.vocab, n).astype(np.int32), max_new=2)
    eng.step()
    (step,) = trace.records("engine.step")
    assert step.attrs == {"batch": 2, "table_width": 3,
                          "kv_blocks": 2 * (1 + 2),
                          "kv_blocks_grid": 2 * 2 * 2}
