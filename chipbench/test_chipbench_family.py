"""A model family enters the benchmark by new files alone, and the
reference drives a family's hooks.

In a copy of the benchmark's files, a family module under a new name (the
dense family, copied), a configuration naming it, and a cell of it for
each driver are added, with entries appended to `BENCHMARK.json` and
nothing else changed; a fresh process then runs each new cell at smoke
size and reads the fault tests' cell selection.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, spec

FAMILY, CONFIG = "dense_copy", "smollm-360m-copy"
LIKE = {"train": "qwen2-7b-2l.train", "resume": "smollm-360m.resume",
        "serve": "smollm-360m.serve-sessions"}
RUN = """
import json, sys, time
from chipbench import smoke, test_chipbench_faults as faults
from chipbench.run import run_cell
out = {"TRAIN_CELLS": faults.TRAIN_CELLS, "STEP_CELLS": faults.STEP_CELLS,
       "SERVE_CELLS": faults.SERVE_CELLS}
for name in sys.argv[1:]:
    out[name] = run_cell(smoke.tiny_cell(name), 2**33 + 7, 1.0, False,
                         time.perf_counter())
print(json.dumps(out))
"""


def _files(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def _only_appended(old, new) -> bool:
    """`new` is `old` with items appended to its lists, at any depth."""
    if isinstance(old, dict):
        return (isinstance(new, dict) and list(old) == list(new)
                and all(_only_appended(old[k], new[k]) for k in old))
    if isinstance(old, list):
        return (isinstance(new, list) and len(new) >= len(old)
                and all(_only_appended(a, b) for a, b in zip(old, new)))
    return old == new


def _add_family(root: Path) -> dict:
    """The files a configuration of a new family brings; returns the new
    cells by driver."""
    here = root / "chipbench"
    shutil.copy(here / "families" / "dense.py",
                here / "families" / f"{FAMILY}.py")
    conf = json.loads((here / "configs" / "smollm-360m.json").read_text())
    conf["name"] = CONFIG
    conf["program"]["family"] = FAMILY
    (here / "configs" / f"{CONFIG}.json").write_text(json.dumps(conf))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "smollm-360m")
    bench["configs"].append({**entry, "name": CONFIG,
                             "file": f"chipbench/configs/{CONFIG}.json"})
    cells = {}
    for driver, like in LIKE.items():
        w = next(w for w in bench["workloads"] if w["name"] == like)
        name = cells[driver] = f"{CONFIG}.{w['traffic']}"
        bench["workloads"].append({**w, "name": name, "config": CONFIG})
        shutil.copy(here / "cells" / f"{like}.json",
                    here / "cells" / f"{name}.json")
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return cells


@pytest.fixture(scope="module")
def copy_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    shutil.copy(spec.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(spec.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(root)
    bench_before = json.loads((root / "BENCHMARK.json").read_text())
    cells = _add_family(root)
    added = _files(root)
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1", JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root),
                                           str(spec.ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", RUN, *cells.values()],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {"cells": cells, "out": json.loads(p.stdout.splitlines()[-1]),
            "before": before, "added": added, "after": _files(root),
            "bench": (bench_before,
                      json.loads((root / "BENCHMARK.json").read_text()))}


@pytest.mark.parametrize("driver", sorted(LIKE))
def test_new_family_cell_is_correct(copy_run, driver):
    line = copy_run["out"][copy_run["cells"][driver]]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["metrics"] and all(m["value"] > 0
                                   for m in line["metrics"].values())


def test_fault_tests_select_the_new_cells(copy_run):
    out, cells = copy_run["out"], copy_run["cells"]
    assert cells["train"] in out["TRAIN_CELLS"]
    assert {cells["train"], cells["resume"]} <= set(out["STEP_CELLS"])
    assert cells["serve"] in out["SERVE_CELLS"]


def test_no_existing_file_was_edited(copy_run):
    """Only new files, and entries appended to BENCHMARK.json; the runs
    wrote nothing among the benchmark's files."""
    before, added = copy_run["before"], copy_run["added"]
    changed = {f for f in before if added[f] != before[f]}
    assert changed == {"BENCHMARK.json"}
    assert _only_appended(*copy_run["bench"])
    assert len(added) == len(before) + 5   # family, config, three cells
    assert copy_run["after"] == added


@dataclass(frozen=True)
class ToyDims:
    vocab: int = 8
    d: int = 4


def test_run_steps_hands_statistics_to_after_update():
    """With a toy family, `run_steps` hands `after_update` each step's row
    statistics summed over the rows, and AdamW leaves a leaf named in
    `non_gradient_leaves` alone though its gradient is not zero."""
    seen = []

    class Toy:
        @staticmethod
        def leaf_specs(dm):
            return {"bias": ((dm.vocab,), "zeros"),
                    "embedding": ((dm.vocab, dm.d), "normal")}

        @staticmethod
        def row_logits(p, tokens, dm, round_to=None):
            x = jnp.take(p["embedding"], tokens, axis=0)
            return x @ p["embedding"].T + p["bias"]

        @staticmethod
        def row_loss(p, tokens, labels, dm, round_to=None):
            count = jnp.zeros(dm.vocab).at[tokens].add(1.0)
            return (reference.cross_entropy(
                Toy.row_logits(p, tokens, dm), labels), {"count": count})

        @staticmethod
        def after_update(p, stats, dm):
            seen.append(np.asarray(stats["count"]))
            return {**p, "bias": p["bias"] + 0.01 * stats["count"]}

        @staticmethod
        def non_gradient_leaves(dm):
            return frozenset({"bias"})

    dm = ToyDims()
    rng = np.random.default_rng(0)
    rows = [(rng.integers(0, dm.vocab, (3, 5), dtype=np.int32),
             rng.integers(0, dm.vocab, (3, 5), dtype=np.int32))
            for _ in range(2)]
    hp = {"lr": 0.01, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
          "weight_decay": 0.0, "grad_clip": 1.0, "warmup_steps": 0,
          "decay_steps": 10, "min_lr_ratio": 0.1}
    out = reference.run_steps(Toy, dm, 7, rows, hp, 2)
    for (tokens, _), counts in zip(rows, seen, strict=True):
        np.testing.assert_array_equal(
            counts, np.bincount(tokens.ravel(), minlength=dm.vocab))
    assert out["grad_norms"]["bias"] > 0
    bias = np.zeros(dm.vocab, np.float32)
    for counts in seen:
        bias = bias + np.float32(0.01) * counts
    assert out["change_norms"]["bias"] == pytest.approx(
        float(np.linalg.norm(bias)), rel=1e-6)
    assert out["change_norms"]["embedding"] > 0
