"""The correctness check sees the faults a training cell can have.

Each test drives a whole run at smoke size (the harness's look for a chip
skipped) with the timed path broken underneath, and sees `correct` come
out false: a step that returns its state unchanged; half of the batch
left out, the mean taken over the rest; a token altered where the
pipeline produces it; a checkpoint leaf altered where it is written.
The control, the reference computed in float8 in the program's place,
fails the cell's limits too.  (One chip: there is no exchange to leave
out.)
"""
from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import check, smoke, spec
from chipbench.drivers import resume, serve, train
from chipbench.run import run_cell

CKPT_CELL, RESUME_CELL = "smollm-360m.train-ckpt", "smollm-360m.resume"
CELLS = [w["name"] for w in spec.benchmark()["workloads"]]
TRAIN_CELLS = [c for c in CELLS if spec.cell(c).traffic["driver"] == "train"]
STEP_CELLS = [c for c in CELLS
              if spec.cell(c).traffic["driver"] in ("train", "resume")]
SERVE_CELLS = [c for c in CELLS if spec.cell(c).traffic["driver"] == "serve"]


def _state_unchanged(monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def make(model, hyper, rules=None):
        step = real(model, hyper, rules)
        return lambda state, batch: (state, step(state, batch)[1])
    monkeypatch.setattr(trainer_mod, "make_train_step", make)


def _half_batch(monkeypatch):
    import repro.train.trainer as trainer_mod
    real = trainer_mod.make_train_step

    def make(model, hyper, rules=None):
        step = real(model, hyper, rules)

        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    monkeypatch.setattr(trainer_mod, "make_train_step", make)


def _token_altered(monkeypatch):
    from repro.data.pipeline import DataPipeline
    real = DataPipeline._blocks_of

    def blocks_of(self, raw):
        b = real(self, raw).copy()
        b[0, 5] = (b[0, 5] + 1) % 256
        return b
    monkeypatch.setattr(DataPipeline, "_blocks_of", blocks_of)


def _ckpt_leaf_altered(monkeypatch):
    import repro.checkpoint.manager as manager_mod
    real = manager_mod.leaf_to_bytes

    def leaf_to_bytes(leaf):
        arr = np.array(leaf, copy=True)
        if arr.size > 1:
            arr.reshape(-1)[1] += 1
        return real(arr)
    monkeypatch.setattr(manager_mod, "leaf_to_bytes", leaf_to_bytes)


def _decode_pools_unchanged(monkeypatch):
    import repro.serving.engine as engine_mod
    real = engine_mod._decode_step

    def decode(params, k_pool, v_pool, *a, **kw):
        tok, _, _ = real(params, k_pool, v_pool, *a, **kw)
        return tok, k_pool, v_pool
    monkeypatch.setattr(engine_mod, "_decode_step", decode)


def _decode_token_altered(monkeypatch):
    import repro.serving.engine as engine_mod
    real = engine_mod._decode_step

    def decode(params, k_pool, v_pool, *a, cfg, **kw):
        tok, kp, vp = real(params, k_pool, v_pool, *a, cfg=cfg, **kw)
        return (tok + 1) % cfg.vocab, kp, vp
    monkeypatch.setattr(engine_mod, "_decode_step", decode)


FAULTS = [(CKPT_CELL, _state_unchanged), (CKPT_CELL, _half_batch),
          (CKPT_CELL, _token_altered), (CKPT_CELL, _ckpt_leaf_altered)]
FAULTS += [(RESUME_CELL, _state_unchanged), (RESUME_CELL, _token_altered),
           (RESUME_CELL, _ckpt_leaf_altered)]
FAULTS += [(c, _state_unchanged) for c in TRAIN_CELLS if c != CKPT_CELL]
FAULTS += [(c, f) for c in SERVE_CELLS
           for f in (_decode_pools_unchanged, _decode_token_altered)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(smoke.tiny_cell(name), 2**33 + 11, 1.0, False,
                    time.perf_counter())
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("name", STEP_CELLS)
def test_control_is_not_correct(name):
    """The reference in float8, in the program's place, fails the cell's
    limits on every seed tried, where the program passes them, and so
    does half a batch.  (In the resume cell, the program's numbers are
    those of its set-up steps and of a resume.)"""
    cell = smoke.tiny_cell(name)
    driver = resume if cell.traffic["driver"] == "resume" else train
    args = cell.params["limits"], cell.params.get("not_compared", {})
    for seed in (3, 4):
        out = driver.run(cell, seed, 0, False, time.perf_counter(),
                         calibrate=True)
        assert check.judge(out["program"], *args)[0], out["program"]
        assert not check.judge(out["control"], *args)[0], out["control"]
        assert not check.judge(out["half_batch"], *args)[0]


@pytest.mark.parametrize("name", SERVE_CELLS)
def test_serving_control_is_not_correct(name):
    """The reference in float8, in the program's place, puts first tokens
    whose gap fails the serving cell's limit, where the served tokens
    pass it, on every seed tried."""
    cell = smoke.tiny_cell(name)
    args = cell.params["limits"], cell.params.get("not_compared", {})
    for seed in (3, 4):
        out = serve.run(cell, seed, 0, False, time.perf_counter(),
                        calibrate=True)
        assert check.judge(out["program"], *args)[0], out["program"]
        assert not check.judge(out["control"], *args)[0], out["control"]
