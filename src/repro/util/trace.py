"""In-process spans at the program's layer boundaries, on the profiler's
clock.

``span(name)`` does two things.  It opens a
``jax.profiler.TraceAnnotation`` named ``wtf.<name>``, so that while a
profiler trace runs the span lies in it beside the device's operations,
on the trace's own clock.  And it records the span in memory, always:
name, start and end (``time.perf_counter_ns``), id, parent id, thread,
and attributes.  The parent is the innermost span open in the current
context (``contextvars``), or the one passed as ``parent=`` where work
is handed to another thread outside that context.  (An op submitted to
the I/O pool runs in a copy of its submitter's context, so its spans
nest under the submitter's with no ``parent=``.)

Records go into a bounded ring per name (``RING``; what falls out is
counted in ``dropped``).  One lock guards the rings; it is a leaf of the
declared lock order (``analysis.lockspec``: ``trace.rings``).

Two sources record without a ``with`` block:

* ``jit.compile``: every ``jax.monitoring`` duration event whose name
  contains ``compil`` (tracing, lowering, backend compile and
  compilation-cache retrieval) becomes a record that ends when the event
  is reported and starts its duration earlier, under the span open in
  the reporting thread at that moment.  The cache's
  ``compile_time_saved_sec`` is time not spent and is left out.
* ``py.gc``: each garbage collection, with its ``generation`` and the
  objects ``collected``.  The collector's hook takes no lock: it queues
  the record, in a queue as bounded as a ring, for the next read to
  move into the ring.

The profiler and the compile events are JAX's, and the filesystem's
core runs without JAX: the tracer binds to JAX at the first span opened
after some module has imported it, and registers its compile listener
then.  No profiler trace can run before that.

Counts stay in the ``*Stats`` classes; a span carries, as attributes, the
deltas of the counters its work moved.

Reading: ``records(name)``, ``children(record)``, ``self_time(record)``,
``covered(records)`` and ``summary()``.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import gc
import itertools
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterable, List, Optional

from repro.core.testing import witness_lock

RING = 4096                     # records kept per name
PREFIX = "wtf."                 # of every span's name in the profiler trace
# attributes that name something rather than count it: ``summary()``
# gives their largest value, not their sum
LABELS = frozenset({"step", "sid", "generation"})

_current: contextvars.ContextVar[Optional["Record"]] = \
    contextvars.ContextVar("wtf_trace_span", default=None)


class Record:
    """One span: times in ``perf_counter_ns``; ``parent`` is an id."""

    __slots__ = ("name", "start_ns", "end_ns", "id", "parent", "thread",
                 "attrs")

    def __init__(self, name: str, start_ns: int, end_ns: int, id: int,
                 parent: Optional[int], thread: int,
                 attrs: Dict[str, Any]):
        self.name, self.start_ns, self.end_ns = name, start_ns, end_ns
        self.id, self.parent, self.thread = id, parent, thread
        self.attrs = attrs

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __repr__(self) -> str:
        return (f"Record({self.name!r}, id={self.id}, parent={self.parent}, "
                f"{self.seconds:.6f}s, {self.attrs})")


def current() -> Optional[Record]:
    """The innermost span open in this context, if any."""
    return _current.get()


def _parent_id(parent: Optional[Record]) -> Optional[int]:
    return None if parent is None else parent.id


_NO_ANNOTATION = contextlib.nullcontext()
_annotation_cls = None          # jax.profiler.TraceAnnotation, once bound
_bind_lock = threading.Lock()


def _annotation(name: str, bind: bool = True):
    """The profiler annotation of a span named ``name``; nothing before
    JAX is loaded.  The first call after binds the tracer to JAX."""
    cls = _annotation_cls
    if cls is None:
        if not bind or not _bind():
            return _NO_ANNOTATION
        cls = _annotation_cls
    return cls(PREFIX + name)


def _bind() -> bool:
    global _annotation_cls
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    monitoring = getattr(jax, "monitoring", None)
    if profiler is None or monitoring is None:
        return False
    with _bind_lock:
        if _annotation_cls is None:
            monitoring.register_event_duration_secs_listener(
                _TRACER.on_compile_event)
            _annotation_cls = profiler.TraceAnnotation
    return True


class _Span:
    __slots__ = ("_tracer", "_rec", "_ann", "_token")

    def __init__(self, tracer: "Tracer", rec: Record):
        self._tracer, self._rec = tracer, rec
        self._ann = _annotation(rec.name)

    def __enter__(self) -> Record:
        self._ann.__enter__()
        self._token = _current.set(self._rec)
        self._rec.start_ns = time.perf_counter_ns()
        return self._rec

    def __exit__(self, *exc) -> None:
        self._rec.end_ns = time.perf_counter_ns()
        _current.reset(self._token)
        self._ann.__exit__(*exc)
        self._tracer._add(self._rec)


class Tracer:
    """The rings of records and the functions that read them."""

    def __init__(self, ring: int = RING):
        self._ring = ring
        self._ids = itertools.count(1)
        self._lock = witness_lock(threading.Lock(), "trace.rings")
        self._rings: Dict[str, Deque[Record]] = {}
        self._dropped: Dict[str, int] = collections.defaultdict(int)
        # ``py.gc`` records, made where the lock may already be held by
        # the same thread (a collection can start inside any
        # allocation); moved into their ring by the next read.  Bounded
        # like the ring.  Each is queued with its number in ``_gc_seq``:
        # the numbers skipped at the move (from ``_gc_next``, under the
        # lock) fell out of the queue.
        self._pending: Deque[tuple] = collections.deque(maxlen=ring)
        self._gc_seq = itertools.count()
        self._gc_next: Optional[int] = 0
        self._gc: Optional[tuple] = None

    # ------------------------------------------------------------ recording
    def span(self, name: str, parent: Optional[Record] = None,
             **attrs) -> _Span:
        """A context manager that records the span and yields its
        record, whose ``attrs`` may still be added to inside it."""
        if parent is None:
            parent = _current.get()
        return _Span(self, Record(name, 0, 0, next(self._ids),
                                  _parent_id(parent), threading.get_ident(),
                                  attrs))

    def record(self, name: str, start_ns: int, end_ns: int,
               parent: Optional[Record] = None, **attrs) -> Record:
        """Record a span that has already ended (work whose start and
        end are seen from outside it)."""
        if parent is None:
            parent = _current.get()
        rec = Record(name, start_ns, end_ns, next(self._ids),
                     _parent_id(parent), threading.get_ident(), attrs)
        self._add(rec)
        return rec

    def _add(self, rec: Record) -> None:
        with self._lock:
            ring = self._rings.get(rec.name)
            if ring is None:
                ring = self._rings[rec.name] = collections.deque(
                    maxlen=self._ring)
            elif len(ring) == self._ring:
                self._dropped[rec.name] += 1
            ring.append(rec)

    @contextlib.contextmanager
    def _drained(self):
        """Hold the lock, with the queued ``py.gc`` records moved into
        their ring."""
        with self._lock:
            while self._pending:
                seq, rec = self._pending.popleft()
                lost = 0 if self._gc_next is None else seq - self._gc_next
                self._gc_next = seq + 1
                ring = self._rings.setdefault(
                    "py.gc", collections.deque(maxlen=self._ring))
                if len(ring) == self._ring:
                    lost += 1
                if lost:
                    self._dropped["py.gc"] += lost
                ring.append(rec)
            yield

    def on_compile_event(self, event: str, duration: float,
                         **_kw) -> None:
        """``jax.monitoring`` duration listener: ``jit.compile`` records."""
        if "compil" not in event or event.endswith("compile_time_saved_sec"):
            return
        end = time.perf_counter_ns()
        self.record("jit.compile", end - int(duration * 1e9), end,
                    event=event)

    def on_gc(self, phase: str, info: Dict[str, int]) -> None:
        """``gc.callbacks`` hook: ``py.gc`` records, without the lock."""
        if phase == "start":
            ann = _annotation("py.gc", bind=False)
            ann.__enter__()
            self._gc = (ann, time.perf_counter_ns(), _current.get())
            return
        if self._gc is None:
            return
        ann, start, parent = self._gc
        self._gc = None
        end = time.perf_counter_ns()
        ann.__exit__(None, None, None)
        self._pending.append((next(self._gc_seq), Record(
            "py.gc", start, end, next(self._ids), _parent_id(parent),
            threading.get_ident(), {"generation": info.get("generation"),
                                    "collected": info.get("collected")})))

    # -------------------------------------------------------------- reading
    def records(self, name: str) -> List[Record]:
        """The records of ``name`` still held, oldest first."""
        with self._drained():
            return list(self._rings.get(name, ()))

    def dropped(self, name: str) -> int:
        """Records of ``name`` that fell out of its ring."""
        with self._drained():
            return self._dropped.get(name, 0)

    def children(self, rec: Record,
                 name: Optional[str] = None) -> List[Record]:
        """The records whose parent is ``rec`` (of ``name`` alone, if
        given), in order of start."""
        with self._drained():
            rings = [self._rings.get(name, ())] if name is not None \
                else list(self._rings.values())
            kids = [r for ring in rings for r in ring if r.parent == rec.id]
        return sorted(kids, key=lambda r: r.start_ns)

    def self_time(self, rec: Record) -> float:
        """Seconds of ``rec`` that none of its children covers."""
        return rec.seconds - covered(self.children(rec), rec)

    def summary(self) -> Dict[str, Dict[str, Any]]:
        """Per name: records held, dropped, their total, self and longest
        seconds, and ``attrs``: the sum of each numeric attribute (the
        counter deltas a span carries), or its largest value for a label
        (``LABELS``: the last step or session, the oldest generation
        collected)."""
        with self._drained():
            names = {n: list(r) for n, r in self._rings.items()}
            dropped = dict(self._dropped)
        kids: Dict[int, List[Record]] = collections.defaultdict(list)
        for recs in names.values():
            for r in recs:
                kids[r.parent].append(r)
        out = {}
        for name in sorted(names):
            recs = names[name]
            attrs: Dict[str, Any] = {}
            for r in recs:
                for k, v in r.attrs.items():
                    if isinstance(v, bool) or not isinstance(v, (int, float)):
                        continue
                    attrs[k] = v if k not in attrs else (
                        max(attrs[k], v) if k in LABELS else attrs[k] + v)
            out[name] = {
                "count": len(recs), "dropped": dropped.get(name, 0),
                "total_s": sum(r.seconds for r in recs),
                "self_s": sum(r.seconds - covered(kids[r.id], r)
                              for r in recs),
                "max_s": max(r.seconds for r in recs), "attrs": attrs}
        return out

    def clear(self) -> None:
        """Forget every record held and every count of dropped ones."""
        with self._lock:
            self._pending.clear()
            self._gc_next = None            # the next queued starts afresh
            self._rings.clear()
            self._dropped.clear()


def covered(recs: Iterable[Record], within: Optional[Record] = None) -> float:
    """Seconds covered by the union of the records' intervals, clipped
    to ``within`` where given."""
    lo = within.start_ns if within is not None else None
    hi = within.end_ns if within is not None else None
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted((r.start_ns, r.end_ns) for r in recs):
        if lo is not None:
            s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is not None and s <= cur_e:
            cur_e = max(cur_e, e)
            continue
        if cur_e is not None:
            total += cur_e - cur_s
        cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


_TRACER = Tracer()
gc.callbacks.append(_TRACER.on_gc)

span = _TRACER.span
record = _TRACER.record
records = _TRACER.records
dropped = _TRACER.dropped
children = _TRACER.children
self_time = _TRACER.self_time
summary = _TRACER.summary
clear = _TRACER.clear
