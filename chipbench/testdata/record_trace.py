#!/usr/bin/env python3
"""Record the small device trace that `test_chipbench_trace.py` reads.

  python3 chipbench/testdata/record_trace.py <out.xplane.pb>

On a TPU: inside a `bench.window` span, three calls of one small jitted
program with a `bench.host_wait` span of 20 ms of host time between the
second and the third, and 10 ms of margin at either end of the window
(the device's clock reads about a millisecond off the host's), so the
trace has device work, an idle gap with a known host span over it, and
the window's bounds.  Prints what the reduction reads from it.
"""
from __future__ import annotations

import collections
import os
import shutil
import sys
import tempfile
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from chipbench import device_trace as dt

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def small_matmul(x):
        return jnp.tanh(x @ x) @ x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    small_matmul(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        with dt.record(tmp):
            with dt.span("window"):
                time.sleep(0.01)
                for i in range(3):
                    if i == 2:
                        with dt.span("host_wait"):
                            time.sleep(0.02)
                    small_matmul(x).block_until_ready()
                time.sleep(0.01)
        shutil.copy(dt.trace_file(tmp), out)
    ev = dt.load(out)
    s = dt.summarize(ev)
    calls = collections.Counter(n for n, _, _ in s.calls)
    print(f"devices {sorted(ev.ops)}; programs {dict(calls)}; busy "
          f"{s.busy_s!r} s of {s.window_s!r} s; gaps {s.idle_gaps}; "
          f"spans {[n for n, _, _ in ev.spans]}; size {os.path.getsize(out)}")
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(out).planes:
        print("plane", plane.name, [(ln.name, sum(1 for _ in ln.events))
                                    for ln in plane.lines])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
