#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips of this machine.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

Prints the device, sets the cell up from the seed (weights, data, the
WTF cluster), warms up, measures the window, checks what the window
produced against the plain reference, and prints one JSON line as the
last line of standard output: the end-to-end metrics with `--trace 0`,
the per-layer metrics (read from a profiler trace of the window) with
`--trace 1`.  The numbers compared, each beside its limit, are the last
lines of standard error and the last key of that line.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT                  # `chipbench.*`, not this directory
    sys.path.insert(1, os.path.join(ROOT, "src"))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks=None) -> dict:
    """Drive one run of `cell` and assemble the result line."""
    from chipbench import check, spec
    driver = importlib.import_module(
        f"chipbench.drivers.{cell.traffic['driver']}")
    res = driver.run(cell, seed, seconds, trace, t_start)
    correct, checks = check.judge(res["numbers"], cell.params["limits"],
                                  cell.params.get("not_compared", {}))
    metrics, device = {}, dict(res["device"])
    if not trace:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                  "unit": m["unit"]}
    else:
        data = res["data"]
        data.peaks = peaks
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"]).read(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = data.trace.busy_s
        device["window_s"] = data.trace.window_s
    line = {"correct": correct, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = res["data"].trace.breakdown()
    # a number that is not finite fails its check; JSON has no spelling
    # for it, so it is written as null
    line["checks"] = {k: {"value": v["value"] if math.isfinite(v["value"])
                          else None, "limit": v["limit"]}
                      for k, v in checks.items()}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    from chipbench import check, spec

    devs = jax.devices()
    d = devs[0]
    print(f"[chipbench] device platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        print(f"[chipbench] no TPU (platform {d.platform!r}): nothing is "
              f"measured", file=sys.stderr, flush=True)
        return 2
    cell = spec.cell(args.workload)
    if len(devs) < cell.chips:
        print(f"[chipbench] {args.workload} needs {cell.chips} chips, "
              f"found {len(devs)}", file=sys.stderr, flush=True)
        return 2
    peaks = spec.peaks(d.device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[chipbench] compile cache {enable_compile_cache()}",
          file=sys.stderr, flush=True)
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    T_START, peaks)
    check.print_checks(line["checks"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
