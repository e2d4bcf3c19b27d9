"""Training traffic: `Trainer.run` reading a seeded token corpus from a WTF
cluster through `DataPipeline`, with an asynchronous checkpoint in the
window where the mix asks for one.

One `Trainer.run` call drives everything.  Its first `warm_steps` steps
are set-up: they compile (or load) the step and are the steps the
reference follows.  The window opens when the loop asks the pipeline for
the next batch and closes when `run()` has returned, every save
committed, and the last step is done on the device.  The number of
window steps follows `--seconds` through the cell's nominal step time; a
mix with `"save": "mid"` saves once, after the middle window step.  The
mix names its rate metric (`rate_metric`): tokens of the window's steps
over the window.

The benchmark hands the trainer the real pipeline behind a wrapper (a
span around each `next()`, whose start times also give the loop's stall
in the iteration that saved) and wraps the public jitted step
`Trainer.train_step` (each step's loss and metrics; the optimizer's
first moment after step 1, the weights after the last set-up step, and a
checksum of the state the trainer then saves).  The model is reached
through the configuration's family (`families/`): its sizes, the
program's configuration, the reference and the step's FLOPs.
"""
from __future__ import annotations

import collections
import contextlib
import math
import tempfile
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import Cluster
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.data.records import RecordWriter
from repro.models import get_model
from repro.train import AdamWConfig, TrainHyper
from repro.train.trainer import Trainer, TrainerConfig

from chipbench import check, device_trace, reference, spec
from chipbench.common import (RunData, Spans, fingerprint, flat, log,
                              sub_seeds)
from chipbench.device_trace import span

CORPUS = "/corpus/shard0"
SPARE_STEPS = 8          # records beyond the run, so prefetch never wraps
WRITE_CHUNK = 64         # records per atomic append of the corpus


class PipelineProbe:
    """The real `DataPipeline`, with a span around each `next()` and a
    hook before each request; keeps every batch it hands out."""

    def __init__(self, real: DataPipeline, spans: Spans,
                 before: Callable[[int], None]):
        self._real, self._spans, self._before = real, spans, before
        self.batches: List[dict] = []
        self.states_set: list = []     # cursors the trainer handed it

    @property
    def state(self):
        return self._real.state

    @state.setter
    def state(self, value):
        self.states_set.append(value)
        self._real.state = value

    def __getattr__(self, name):
        return getattr(self._real, name)

    def __iter__(self):
        inner = iter(self._real)

        def gen():
            try:
                while True:
                    self._before(len(self.batches))
                    with self._spans("pipeline.next"):
                        item = next(inner)
                    self.batches.append(item)
                    yield item
            finally:
                inner.close()
        return gen()


class StepProbe:
    """The trainer's jitted step; records what the reference needs from
    the set-up steps, every step's metrics (its loss among them), and a
    checksum of the state after step `save_step` (the state the trainer
    then saves).  After each step it calls `on_step` with the step's
    number."""

    def __init__(self, step, warm: int, spans: Spans,
                 save_step: Optional[int] = None,
                 on_step: Optional[Callable[[int], None]] = None):
        self._step, self._warm, self._spans = step, warm, spans
        self._save_step, self._on_step = save_step, on_step
        self.metrics: List[dict] = []      # what each step returned
        self.m1_norms = None
        self.params_after_warm: Optional[dict] = None
        self.saved_fp = None

    @property
    def losses(self) -> List[jax.Array]:
        return [m["loss"] for m in self.metrics]

    def __call__(self, state, batch):
        with self._spans("step.dispatch"):
            state, metrics = self._step(state, batch)
        self.metrics.append(metrics)
        n = len(self.metrics)
        if n == 1:
            self.m1_norms = reference.leaf_norms(flat(state["opt"].m))
        if n == self._warm:
            self.params_after_warm = jax.device_get(flat(state["params"]))
            jax.block_until_ready(fingerprint(flat(state)))   # compile now
        if n == self._save_step:
            self.saved_fp = fingerprint(flat(state))
        if self._on_step is not None:
            self._on_step(n)
        return state, metrics


def loop_stall(starts: List[float], save: int, syncs: List[int],
               first: int) -> Optional[float]:
    """Seconds the training loop lost in the iteration that saved, from
    the times it asked the pipeline for each batch (`starts[i]`: batch i
    feeds step i + 1).  After a step in `syncs` the loop waits for the
    device (it logs the loss), so at `starts[a]` the device has finished
    step a and the steps that follow run back to back.  With `a` the last
    such step before `save` and `b` the first at or after `first`, the
    device needs `m = (starts[a] - starts[b]) / (a - b)` a step, and the
    stall is `starts[save] - starts[a] - (save - a) * m`: the host copy
    of the state and the save's submission, while the device waits."""
    before = [s for s in syncs if s < save]
    if not before or save >= len(starts):
        return None
    a = max(before)
    earlier = [s for s in syncs if first <= s < a]
    if not earlier:
        return None
    b = min(earlier)
    m = (starts[a] - starts[b]) / (a - b)
    return starts[save] - starts[a] - (save - a) * m


def _write_corpus(fs, corpus: np.ndarray) -> None:
    fs.mkdir("/corpus")
    w = RecordWriter(fs, CORPUS, corpus.shape[1] * corpus.itemsize)
    for i in range(0, len(corpus), WRITE_CHUNK):
        w.append_many([r.tobytes() for r in corpus[i:i + WRITE_CHUNK]])
    w.close()


def _rows_of(batches: List[dict], corpus: np.ndarray):
    """Corpus index of each row the trainer was fed, per step, and how
    many rows were not rows of the corpus or came twice."""
    index = {r.tobytes(): i for i, r in enumerate(corpus)}
    seen, bad, steps = set(), 0, []
    for b in batches:
        idx = []
        for tok, lab in zip(np.asarray(b["tokens"]), np.asarray(b["labels"])):
            i = index.get(np.concatenate([tok, lab[-1:]]).astype(
                corpus.dtype).tobytes())
            if i is None or i in seen:
                bad += 1
            else:
                seen.add(i)
            idx.append(i)
        steps.append(idx)
    return steps, bad


def _reference_rows(corpus: np.ndarray, idx: List[int]):
    blocks = corpus[np.asarray(idx)]
    return blocks[:, :-1], blocks[:, 1:]


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        calibrate: bool = False) -> dict:
    tr, conf, params = cell.traffic, cell.config, cell.params
    fam = spec.family(conf)
    dm, cfg = fam.dims(conf), fam.program_config(conf)
    B, S, warm = tr["batch"], tr["seq"], tr["warm_steps"]
    steps = 0 if calibrate else max(
        2, round(seconds / params["nominal_step_s"]))
    total = warm + steps
    save_step = (warm + math.ceil(steps / 2)
                 if tr.get("save") == "mid" and steps else None)
    seeds = sub_seeds(seed)
    hp = dict(tr["optimizer"])
    log(f"{cell.name}: {cfg.n_layers} x {cfg.d_model}, heads "
        f"{cfg.n_heads}/{cfg.n_kv_heads} of {cfg.head_dim_}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}, remat {cfg.remat}; {B} x {S}; "
        f"{warm} set-up steps, {steps} window steps, save after "
        f"{save_step}")

    corpus = np.random.default_rng(seeds["data"]).integers(
        0, dm.vocab, ((total + SPARE_STEPS) * B, S + 1), dtype=np.int32)
    spans = Spans()
    win: dict = {}
    compiles = collections.Counter()
    setup_compile_s = collections.Counter()     # by event, before the window

    def on_compile(event, duration, **kw):
        if "compil" not in event:
            return
        if "open" not in win:
            setup_compile_s[event.rsplit("/", 1)[-1]] += duration
        elif "close" not in win:
            compiles[event] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp, \
            contextlib.ExitStack() as stack:
        stack.callback(jax.monitoring.unregister_event_duration_listener,
                       on_compile)
        cluster = Cluster(n_servers=tr["servers"], data_dir=tmp + "/wtf",
                          replication=tr["replication"])
        stack.callback(cluster.close)
        window = stack.enter_context(contextlib.ExitStack())
        fs = cluster.client()
        _write_corpus(fs, corpus)
        win["corpus"] = time.perf_counter()

        saving = stack.enter_context(contextlib.ExitStack())

        def before(i):
            if i == save_step:
                saving.close()
            if i != warm or not steps:
                return
            if trace:
                window.enter_context(device_trace.record(tmp + "/trace"))
            window.enter_context(span("window"))
            win["open"] = time.perf_counter()

        def on_step(n):
            # "trainer.save": from the step that saves until the loop asks
            # for the next batch (`Trainer._save` and its host copy);
            # "run.tail": from the last step until `run()` returns (the
            # queued steps and the wait for the save's commit)
            if n == save_step:
                saving.enter_context(span("trainer.save"))
            if n == total:
                window.enter_context(span("run.tail"))

        pipe = PipelineProbe(DataPipeline(fs, PipelineConfig(
            src_paths=(CORPUS,), work_dir="/epochs", block_tokens=S + 1,
            global_batch=B, seed=seeds["shuffle"],
            prefetch=tr["prefetch"])), spans, before)
        trainer = Trainer(
            get_model(cfg), pipe, CheckpointManager(fs, "/ckpt", keep=3),
            hyper=TrainHyper(adamw=AdamWConfig(**hp)),
            cfg=TrainerConfig(total_steps=total,
                              ckpt_every=save_step or total + 1,
                              log_every=tr["log_every"],
                              seed=seeds["init"]))
        probe = StepProbe(trainer.train_step, warm, spans, save_step, on_step)
        trainer.train_step = probe

        win["trainer"] = time.perf_counter()
        out = trainer.run()
        jax.block_until_ready(out["state"])
        summary = None
        if steps:
            win["close"] = time.perf_counter()
            window.close()           # leaves the window span, stops the trace
            log(f"set-up {win['open'] - t_start:.2f}s: cluster and corpus "
                f"ready at {win['corpus'] - t_start:.2f}s, trainer at "
                f"{win['trainer'] - t_start:.2f}s; compiles and cache "
                f"loads (s) {dict(setup_compile_s)}; window "
                f"{win['close'] - win['open']:.2f}s, saves committed in "
                f"{trainer.async_ckpt.durations}s")
            if compiles:
                log(f"compiles inside the window: {dict(compiles)}")
            if trace:
                t0 = time.perf_counter()
                summary = device_trace.summarize(device_trace.load(
                    device_trace.trace_file(tmp + "/trace")))
                log(f"trace read in {time.perf_counter() - t0:.1f}s")
        mem_peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), out["state"])
        del out
        losses = [float(x) for x in probe.losses]
        log(f"losses: set-up {losses[:warm]}, window first/last "
            f"{losses[warm:warm + 1]} {losses[-1:]}")
        prog = {"losses": losses,
                "grad_norms": {k: v / (1 - hp["b1"]) for k, v in
                               jax.device_get(probe.m1_norms).items()}}
        fed, bad_rows = _rows_of(pipe.batches, corpus)
        numbers = {"rows_not_from_corpus": float(bad_rows),
                   "nonfinite_losses": float(sum(
                       not math.isfinite(x) for x in losses))}
        if save_step is not None:
            numbers.update(_check_checkpoint(
                trainer.ckpt, template, save_step, probe.saved_fp))
        data = RunData(
            window_steps=steps, tokens_per_step=B * S,
            flops_per_step=fam.train_flops_per_step(
                dm, B, S, probe.metrics[warm:]),
            pipeline_wait_s=spans.durations("pipeline.next",
                                            win.get("open", math.inf)),
            save_stall_s=None if save_step is None else loop_stall(
                [a for a, _ in spans.times["pipeline.next"]], save_step,
                _sync_steps(total, tr["log_every"]), warm),
            save_commit_s=list(trainer.async_ckpt.durations),
            trace=summary)
        p_warm = probe.params_after_warm
        del trainer, probe
        if not bad_rows:
            rows = [_reference_rows(corpus, fed[i]) for i in range(warm)]
            t0 = time.perf_counter()
            ref = reference.run_steps(fam, dm, seeds["init"], rows, hp,
                                      warm, p3_of=p_warm)
            prog["change_norms"] = ref.pop("prog_change_norms")
            del p_warm
            numbers.update(check.train_numbers(prog, ref))
            log(f"reference: {warm} steps in "
                f"{time.perf_counter() - t0:.1f}s; losses {ref['losses']}")
            if calibrate:
                return _calibration(fam, dm, seeds, rows, hp, warm,
                                    numbers, ref)
        dev = jax.devices()
        return {
            "numbers": numbers, "data": data,
            "e2e": {tr["rate_metric"]:
                    steps * B * S / (win["close"] - win["open"]),
                    "setup_s": win["open"] - t_start},
            "device": {"platform": dev[0].platform,
                       "kind": dev[0].device_kind, "count": len(dev),
                       "memory_peak_bytes": mem_peak},
            "attempted": steps,
            "failed": int(sum(not math.isfinite(x)
                              for x in losses[warm:]))}


def _sync_steps(total: int, log_every: int) -> List[int]:
    """The steps after which `Trainer.run` reads the loss back, and so
    waits for the device: the first, and every `log_every`-th."""
    return sorted({1} | set(range(log_every, total + 1, log_every)))


def _check_checkpoint(mgr, template, step: int, saved_fp) -> dict:
    """The committed checkpoint read back through `restore`, against the
    checksum of the state the trainer handed to its save."""
    man = mgr.read_manifest(step)
    ahead = man["pipeline"]["step_in_epoch"] - step
    if ahead:
        log(f"known fault: the saved pipeline cursor is {ahead} batch(es) "
            f"ahead of step {step} (prefetch; ROADMAP B2)")
    want = jax.device_get(saved_fp)
    t0 = time.perf_counter()
    restored = jax.device_put(mgr.restore(template, step))
    got = jax.device_get(fingerprint(flat(restored)))
    del restored
    differ = sum(1 for k, v in want.items()
                 if k not in got or not np.array_equal(v, got[k]))
    differ += sum(1 for k in got if k not in want)
    log(f"checkpoint of step {step}: {len(want) - differ} of {len(want)} "
        f"leaves read back as saved ({time.perf_counter() - t0:.1f}s)")
    return {"ckpt_leaves_differ": float(differ),
            "ckpt_step_differs": float(man["step"] != step)}


def _calibration(fam, dm, seeds, rows, hp, warm, numbers, ref) -> dict:
    """The program's numbers and, in its place, the control (the
    reference below the stated precision) and a half batch."""
    half = [(t[: len(t) // 2], l[: len(l) // 2]) for t, l in rows]
    out = {"program": numbers}
    for name, kw in (("control", {"rows": rows, "round_to": "float8_e4m3fn"}),
                     ("half_batch", {"rows": half})):
        other = reference.run_steps(fam, dm, seeds["init"], kw["rows"], hp,
                                    warm, round_to=kw.get("round_to"))
        out[name] = check.train_numbers(other, ref)
        log(f"{name}: {out[name]}")
    return out
