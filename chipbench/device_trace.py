"""From the profiler's trace of a traced run to the device's numbers.

The traced run records one `jax.profiler` trace over its window, with the
benchmark's host spans in it (`span`, written as `TraceAnnotation`s named
`bench.<what>`).  The window is the span `bench.window`, so device events
and host spans are read on the profiler's own clock.

* busy: the union of the intervals in which an operation ran on a device,
  within the window, averaged over the devices; idle share 1 - busy/window;
* device time per program (the `XLA Modules` line) and per operation
  (`XLA Ops`), and each call of a program and of a kernel (a custom
  call), so that a program can be known by the kernel it runs;
* the longest idle gaps, each named by the host span that overlaps it
  most (`host:other` where none does).
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CUSTOM_CALL = "custom_call_target="       # in the HLO text of a kernel call
TOP = 10

Interval = Tuple[float, float]          # [start, end) in ns


@contextlib.contextmanager
def span(name: str):
    """A host span in the trace; costs nothing while no trace runs."""
    with jax.profiler.TraceAnnotation("bench." + name):
        yield


@contextlib.contextmanager
def record(log_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1          # TraceAnnotations, not every call
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def trace_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {log_dir}")
    return found[0]


@dataclass
class Events:
    """What the reduction reads from a trace."""
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)          # device -> [(name, start, dur)]
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def load(path: str) -> Events:
    from jax.profiler import ProfileData
    ev = Events()
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dst = ev.ops if line.name == OPS_LINE else ev.modules
                    dst.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.spans.extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name.startswith("bench."))
    return ev


def union(intervals: Iterable[Interval], lo: float,
          hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi)."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    best, name = 0.0, "host:other"
    for n, s, d in spans:
        o = overlap(gap, (s, s + d))
        if o > best:
            best, name = o, n[len("bench."):]
    return name


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the devices
    devices: int
    op_s: Dict[str, float]             # operation -> device seconds
    idle_gaps: List[Tuple[str, float]]  # longest first
    # each program call, by name, and each kernel call (a custom call), by
    # its HLO text, in the window: (name, start, end) in ns
    calls: List[Tuple[str, float, float]] = field(default_factory=list)
    kernels: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and calls of the programs whose name matches."""
        rx = re.compile(pattern)
        hits = [e - s for n, s, e in self.calls if rx.search(n)]
        return sum(hits) / 1e9, len(hits)

    def kernel_seconds(self, pattern: str) -> float:
        """Device seconds of the kernel calls whose HLO text matches."""
        rx = re.compile(pattern)
        return sum(e - s for t, s, e in self.kernels if rx.search(t)) / 1e9

    def seconds_of_programs_running(self, pattern: str) -> Tuple[float, int]:
        """Device seconds and calls of the program calls in which a kernel
        whose HLO text matches ran (programs that share a name, as every
        `jax.jit` of a `functools.partial` does, told apart by what they
        run)."""
        rx = re.compile(pattern)
        starts = sorted(s for t, s, _ in self.kernels if rx.search(t))
        seconds, calls = 0.0, 0
        for _, s, e in self.calls:
            i = bisect.bisect_left(starts, s)
            if i < len(starts) and starts[i] < e:
                seconds, calls = seconds + (e - s) / 1e9, calls + 1
        return seconds, calls

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:TOP]]}


def _base_name(name: str) -> str:
    """`jit_train_step(12)` -> `jit_train_step`: a program's calls."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """`%fusion.3 = bf16[...] fusion(...)` -> `fusion.3`."""
    return name.split(" = ", 1)[0].lstrip("%")


def summarize(ev: Events, window: Optional[Interval] = None) -> Summary:
    if window is None:
        ws = [(s, s + d) for n, s, d in ev.spans if n == WINDOW]
        if len(ws) != 1:
            raise ValueError(f"{len(ws)} {WINDOW} spans in the trace")
        window = ws[0]
    lo, hi = window
    busy_lists = ev.ops or ev.modules
    if not busy_lists:
        raise ValueError("no device operations in the trace")
    busy_ns, all_gaps = 0.0, []
    for dev, evs in busy_lists.items():
        b = union(((s, s + d) for _, s, d in evs), lo, hi)
        busy_ns += sum(e - s for s, e in b)
        all_gaps.extend(gaps(b, lo, hi))
    calls = [(_base_name(n), max(s, lo), min(s + d, hi))
             for evs in ev.modules.values() for n, s, d in evs
             if overlap((lo, hi), (s, s + d)) > 0]
    kernels = []
    op_s: Dict[str, float] = defaultdict(float)
    for evs in ev.ops.values():
        for n, s, d in evs:
            o = overlap((lo, hi), (s, s + d))
            if o > 0:
                op_s[_op_name(n)] += o / 1e9
                if CUSTOM_CALL in n:
                    kernels.append((n, max(s, lo), min(s + d, hi)))
    window_spans = [x for x in ev.spans if x[0] != WINDOW]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy_ns / len(busy_lists) / 1e9,
        devices=len(busy_lists), op_s=dict(op_s),
        idle_gaps=[(name_gap(g, window_spans), (g[1] - g[0]) / 1e9)
                   for g in longest],
        calls=calls, kernels=kernels)
