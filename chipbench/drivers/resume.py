"""Resume traffic: a training job restarting from its last checkpoint.

Set-up: `Trainer.run` takes `ckpt_steps` steps on a fresh state, and the
benchmark saves that state with `CheckpointManager.save` into the cell's
WTF cluster, with the pipeline cursor of the steps taken; one resume then
warms up.  Window: until `--seconds` is spent, drop the trainer and its
device state, build a new `Trainer` with its own jit, as a restarted
process would (warm only from the persistent compile cache), and call
`run()` for one step past the checkpoint.  A resume ends when that
step's loss is on the host.  The cluster stays up across resumes: nothing
of WTF outlives its process yet, so the kill is simulated in one.

Checked once the window has closed: every resume's restored state
against the checksum of the state saved, its pipeline cursor, the rows it
was fed, and its step's loss against the reference's step
`ckpt_steps + 1`; the weights the window's last resume stepped to against
the reference's after `ckpt_steps + 1` steps (`change_gap`); and the
set-up steps' losses and first gradient as in the training cells.
"""
from __future__ import annotations

import contextlib
import math
import tempfile
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import Cluster
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.models import get_model
from repro.train import AdamWConfig, TrainHyper
from repro.train.trainer import Trainer, TrainerConfig

from chipbench import check, device_trace, reference, spec
from chipbench.common import (RunData, Spans, fingerprint, flat, log,
                              sub_seeds)
from chipbench.device_trace import span
from chipbench.drivers.train import (CORPUS, SPARE_STEPS, PipelineProbe,
                                     StepProbe, _calibration, _rows_of,
                                     _reference_rows, _write_corpus)


class TimedManager(CheckpointManager):
    """The program's manager, with a span around `restore`."""

    def __init__(self, *a, spans: Spans, **kw):
        super().__init__(*a, **kw)
        self._spans = spans

    def restore(self, template, step=None):
        with self._spans("ckpt.restore"):
            return super().restore(template, step)


class InputProbe(StepProbe):
    """Also a checksum of the state the first step is handed: the state
    `restore_or_init` restored."""

    def __init__(self, step, warm, spans):
        super().__init__(step, warm, spans)
        self.restored_fp = None

    def __call__(self, state, batch):
        if not self.metrics:
            self.restored_fp = fingerprint(flat(state))
        return super().__call__(state, batch)


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        calibrate: bool = False) -> dict:
    """With `calibrate`, one resume stands for the window, untimed, and
    the result is the program's numbers and, in its place, the control's
    and a half batch's (`train._calibration`)."""
    tr, conf = cell.traffic, cell.config
    fam = spec.family(conf)
    dm, cfg = fam.dims(conf), fam.program_config(conf)
    B, S, k = tr["batch"], tr["seq"], tr["ckpt_steps"]
    seeds = sub_seeds(seed)
    hp = dict(tr["optimizer"])
    log(f"{cell.name}: {cfg.n_layers} x {cfg.d_model}, vocab {cfg.vocab}, "
        f"remat {cfg.remat}; {B} x {S}; save after {k} steps, then resumes "
        f"for {seconds}s")
    corpus = np.random.default_rng(seeds["data"]).integers(
        0, dm.vocab, ((k + 1 + SPARE_STEPS) * B, S + 1), dtype=np.int32)
    spans = Spans()
    model = get_model(cfg)
    hyper = TrainHyper(adamw=AdamWConfig(**hp))

    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp, \
            contextlib.ExitStack() as stack:
        cluster = Cluster(n_servers=tr["servers"], data_dir=tmp + "/wtf",
                          replication=tr["replication"])
        stack.callback(cluster.close)
        window = stack.enter_context(contextlib.ExitStack())
        fs = cluster.client()
        _write_corpus(fs, corpus)

        def trainer(total: int, warm: int):
            pipe = PipelineProbe(DataPipeline(fs, PipelineConfig(
                src_paths=(CORPUS,), work_dir="/epochs", block_tokens=S + 1,
                global_batch=B, seed=seeds["shuffle"],
                prefetch=tr["prefetch"])), spans, lambda i: None)
            t = Trainer(model, pipe, TimedManager(fs, "/ckpt", keep=3,
                                                  spans=spans),
                        hyper=hyper,
                        cfg=TrainerConfig(total_steps=total,
                                          ckpt_every=total + 1,
                                          log_every=tr["log_every"],
                                          seed=seeds["init"]))
            t.train_step = InputProbe(t.train_step, warm, spans)
            return t, pipe

        # set-up: the job's first steps, and its checkpoint
        first, first_pipe = trainer(k, 0)
        out = first.run()
        state = out["state"]
        saved_fp = jax.device_get(fingerprint(flat(state)))
        host = jax.device_get(state)
        del out, state
        with spans("ckpt.save"):
            first.ckpt.save(k, host, extra={
                "pipeline": {"epoch": 0, "step_in_epoch": k}})
        del host
        setup_probe, fed_setup = first.train_step, first_pipe.batches
        del first
        log(f"set-up: {k} steps and a save "
            f"({spans.durations('ckpt.save', 0)[0]:.1f}s), done at "
            f"{time.perf_counter() - t_start:.1f}s")

        resumes = []

        def resume():
            """One restart; returns the weights it stepped to."""
            t0 = time.perf_counter()
            t, pipe = trainer(k + 1, 0)
            out = t.run()
            probe = t.train_step
            resumes.append({
                "s": time.perf_counter() - t0,
                "loss": out["history"][-1]["loss"],
                "cursor": pipe.states_set[0].to_dict(),
                "fp": probe.restored_fp, "batches": pipe.batches,
                "metrics": probe.metrics[-1]})
            return out["state"]["params"]

        resume()            # warms the restarted trainer up
        if trace:
            window.enter_context(device_trace.record(tmp + "/trace"))
        window.enter_context(span("window"))
        opened = time.perf_counter()
        last = None
        while last is None or time.perf_counter() - opened < seconds:
            last = None     # the previous restart's state goes first
            last = resume()
            if calibrate:
                break
        closed = time.perf_counter()
        window.close()
        log(f"resumes (s): warm-up {resumes[0]['s']:.2f}, window "
            f"{[round(r['s'], 2) for r in resumes[1:]]}")
        summary = (device_trace.summarize(device_trace.load(
            device_trace.trace_file(tmp + "/trace"))) if trace else None)
        mem_peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        p_last = jax.device_get(flat(last))
        del last
        n = len(resumes) - 1

        numbers = _check_resumes(resumes, saved_fp, k, corpus, fed_setup)
        setup_losses = [float(x) for x in setup_probe.losses]
        window_losses = [r["loss"] for r in resumes[1:]]
        numbers["nonfinite_losses"] = float(sum(
            not math.isfinite(x) for x in setup_losses + window_losses))
        if not numbers["rows_not_from_corpus"]:
            fed, _ = _rows_of(fed_setup + resumes[1]["batches"][:1], corpus)
            rows = [_reference_rows(corpus, idx) for idx in fed]
            ref = reference.run_steps(fam, dm, seeds["init"], rows, hp,
                                      k + 1, p3_of=p_last)
            # the set-up steps, and the window's resume farthest from the
            # reference's step k + 1
            prog = {"losses": setup_losses + [max(
                        window_losses,
                        key=lambda x: abs(x - ref["losses"][k]))],
                    "grad_norms": {n_: v / (1 - hp["b1"]) for n_, v in
                                   jax.device_get(
                                       setup_probe.m1_norms).items()},
                    "change_norms": ref.pop("prog_change_norms")}
            del p_last
            numbers.update(check.train_numbers(prog, ref))
            log(f"reference losses {ref['losses']}; resumed "
                f"{window_losses}")
            if calibrate:
                return _calibration(fam, dm, seeds, rows, hp, k + 1,
                                    numbers, ref)
        dev = jax.devices()
        data = RunData(
            resumes=n, restore_s=spans.durations("ckpt.restore", opened),
            window_steps=n, flops_per_step=fam.train_flops_per_step(
                dm, B, S, [r["metrics"] for r in resumes[1:]]),
            trace=summary)
        return {
            "numbers": numbers, "data": data,
            "e2e": {"resume_s": (closed - opened) / n,
                    "setup_s": opened - t_start},
            "device": {"platform": dev[0].platform,
                       "kind": dev[0].device_kind, "count": len(dev),
                       "memory_peak_bytes": mem_peak},
            "attempted": n,
            "failed": int(sum(not math.isfinite(r["loss"])
                              for r in resumes[1:]))}


def _check_resumes(resumes, saved_fp, k, corpus, fed_setup) -> dict:
    """Exact checks of every resume: the state it restored, its cursor,
    and the rows of its step (those of the first resume, and rows of the
    corpus that the set-up steps did not take)."""
    differ = cursor = 0
    for r in resumes:
        got = jax.device_get(r.pop("fp"))
        differ += sum(1 for name, v in saved_fp.items()
                      if name not in got or not np.array_equal(v, got[name]))
        cursor += r["cursor"] != {"epoch": 0, "step_in_epoch": k}
    _, bad = _rows_of(fed_setup + resumes[0]["batches"][:1], corpus)
    first = [np.asarray(b["tokens"]) for b in resumes[0]["batches"][:1]]
    for r in resumes[1:]:
        same = [np.asarray(b["tokens"]) for b in r["batches"][:1]]
        bad += sum(not np.array_equal(a, b) for a, b in zip(first, same))
    return {"ckpt_leaves_differ": float(differ),
            "cursor_differs": float(cursor),
            "rows_not_from_corpus": float(bad)}
