#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip, in one
process: for each seed, the program's (a training cell's first steps, a
resume cell's set-up steps and one resume, or a serving cell's sampled
turns, against the reference), the control's (the reference below the
stated precision, in the program's place) and, for training and resume,
a half batch's (the reference on half of each batch, in the program's
place).  Training measures no window; serving reads the turns of its
set-up wave, served at the cell's own batch and prompt lengths.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1 2 3 ...

Prints one JSON line a seed, and a summary: each number's largest
program reading and smallest control and fault readings.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import importlib

    import jax
    from chipbench import spec
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("no TPU: the readings are the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = spec.cell(args.workload)
    driver = importlib.import_module(
        f"chipbench.drivers.{cell.traffic['driver']}")
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = driver.run(cell, seed, 0, False, T_START, calibrate=True)
        out["seed"] = seed
        out["seconds"] = time.perf_counter() - t0
        rows.append(out)
        print(json.dumps(out), flush=True)
    summary = {}
    for part in (p for p in rows[0] if p not in ("seed", "seconds")):
        for k in rows[0][part]:
            vals = [r[part][k] for r in rows]
            summary[f"{part}.{k}"] = max(vals) if part == "program" \
                else min(vals)
    print(json.dumps({"summary": summary, "seeds": args.seeds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
