"""A cell of BENCHMARK.json at a size a CPU test run can hold: the same
files, driver and checks, with the model's widths and the traffic cut
down.  Only the tests use it; every measurement is made at the cell's own
size.

The gaps of norms are noisier with a few thousand weights to a leaf than
with millions, so the smoke cell compares the reference's three numbers
under limits of its own, read on the CPU at this size (seeds 1-4 and the
tests' seeds): the program reads at most 6.1e-05 (loss), 3.6e-03 (grad)
and 2.4e-02 (change); the float8 control at least 5.0e-04 and 3.1e-02 on
the first two; half a batch at least 9.2e-04, 7.9e-02 and 2.0e-02.  The
serving mix's served tokens read a gap of at most 1.7e-03 (seeds 1-8 and
the tests' seeds; 24 tokens a run), the float8 control at least 5.8e-03.
The exact checks keep their limit, 0.  These were read on the dense
family's tiny model (its `TINY`); a family may give limits of its own,
read the same way, as `SMOKE_LIMITS` by driver.
"""
from __future__ import annotations

import dataclasses

from chipbench import spec

WINDOW_STEPS = 4
TINY_TRAFFIC = {
    "train": {"batch": 4, "seq": 32},
    "resume": {"batch": 4, "seq": 32},
    # two waves in the tests' one-second window: each session's turns
    # fork from the system prompt and then from each other, mid-page
    "serve": {"sessions": 4, "system_tokens": 8, "user_tokens": 5,
              "new_tokens": 6, "turns": 2, "page_tokens": 4,
              "num_pages": 128},
}
TRAIN_LIMITS = {"loss_gap": 2e-4, "grad_gap": 0.015, "change_gap": 0.06}
SMOKE_LIMITS = {"train": TRAIN_LIMITS, "resume": TRAIN_LIMITS,
                "serve": {"served_gap": 0.0035}}


def tiny_cell(name: str) -> spec.Cell:
    c = spec.cell(name)
    fam, driver = spec.family(c.config), c.traffic["driver"]
    own = getattr(fam, "SMOKE_LIMITS", {}).get(driver, {})
    return dataclasses.replace(
        c, config={**c.config, **fam.TINY},
        traffic={**c.traffic, **TINY_TRAFFIC[driver]},
        params={**c.params, "nominal_step_s": 1.0 / WINDOW_STEPS,
                "nominal_wave_s": 0.5,
                "limits": {**c.params["limits"], **SMOKE_LIMITS[driver],
                           **own},
                "not_compared": {}})
