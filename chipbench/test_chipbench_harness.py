"""The benchmark's harness on the CPU: discovery by name, the result line,
the refusal without a TPU, and every cell driven at smoke size."""
from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import reference, smoke, spec
from chipbench.run import run_cell

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# what every family module exposes (`families/__init__.py`), and what a
# family whose cells serve adds
FAMILY = ("dims", "program_config", "leaf_specs", "row_logits", "row_loss",
          "after_update", "non_gradient_leaves", "train_flops_per_step")
SERVING = ("forward_flops", "paged_attention_bytes")


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    """A cell is its config file, its family's module, traffic file, cell
    file, driver and the readers of its per-layer metrics; each is found
    by name alone."""
    c = spec.cell(name, BENCH)
    driver = importlib.import_module(f"chipbench.drivers.{c.traffic['driver']}")
    assert callable(driver.run)
    fam = spec.family(c.config)
    for attr in FAMILY + (SERVING if c.traffic["driver"] == "serve" else ()):
        assert callable(getattr(fam, attr)), attr
    assert isinstance(fam.TINY, dict) and fam.TINY
    assert "limits" in c.params
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]).read)
    dm = fam.dims(c.config)
    hash(dm)                             # a static argument of the reference
    assert dm.vocab % 128 == 0, "a vocabulary the program pads would add " \
        "rows to its softmax that the reference does not have"


@pytest.mark.parametrize("program", [{"arch": "smollm-360m"},
                                     {"family": "no-such-family"}])
def test_family_is_named_not_defaulted(program):
    """A configuration that names no family, or one with no module, is an
    error and not the dense family."""
    with pytest.raises(KeyError):
        spec.family({"name": "x", "program": program})


def test_benchmark_names_and_files():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for conf in BENCH["configs"]:
        f = json.loads((spec.ROOT / conf["file"]).read_text())
        assert set(conf["reduced"]) == set(f["reduced"])
        assert conf["file"].startswith(BENCH["paths"][0] + "/")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x for x in layers)


def test_peaks_by_device_kind():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


@pytest.mark.parametrize("name", CELLS)
def test_reference_weights_are_the_programs(name):
    """The reference's weight recipe over the cell's family gives, from
    the seed, the weights the program's trainer starts from."""
    from chipbench.common import flat
    from repro.models import get_model
    from repro.train import init_state

    conf = smoke.tiny_cell(name).config
    fam = spec.family(conf)
    mine = reference.init_params(fam, fam.dims(conf), 1234)
    prog = flat(jax.jit(lambda k, m=get_model(fam.program_config(conf)):
                        init_state(m, k))(
        jax.random.PRNGKey(1234))["params"])
    assert sorted(mine) == sorted(prog)
    for k in mine:
        np.testing.assert_array_equal(np.asarray(mine[k]),
                                      np.asarray(prog[k]), err_msg=k)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_at_smoke_size(name):
    """The cell's traffic drives its entry through the program's CPU path,
    and the result line has the contract's keys, `checks` last."""
    cell = smoke.tiny_cell(name)
    line = run_cell(cell, 2**33 + 7, 1.0, False, time.perf_counter())
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["checks"]) == set(cell.params["limits"])
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    json.dumps(line, allow_nan=False)


def test_no_tpu_no_result():
    """Without a TPU the entry exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.HERE / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
