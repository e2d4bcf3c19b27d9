"""What the readers of the program's spans share: the program's tracer,
and the window's records, taken by the counts that `RunData` carries.

The benchmark also runs on a program that records no spans; there
`trace` is None and every reader reads nothing."""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Union

try:
    from repro.util import trace
except ImportError:                 # a program without spans
    trace = None


def last(name: str, n: int, roots: bool = True,
         where: Optional[Callable] = None) -> Optional[list]:
    """The last `n` records of `name` (roots alone, with `roots`; those
    `where` accepts, where given); None where `n` is 0 or the program
    recorded fewer."""
    if trace is None or not n:
        return None
    recs = [r for r in trace.records(name)
            if (not roots or r.parent is None) and (where is None or where(r))]
    return recs[-n:] if len(recs) >= n else None


def below(recs: Optional[list],
          *levels: Union[str, Sequence[str]]) -> Optional[List[list]]:
    """For each record, its descendants down `levels` (at each a name or
    names of children); None where one of them has none."""
    if recs is None:
        return None
    out = []
    for rec in recs:
        found = [rec]
        for names in levels:
            names = (names,) if isinstance(names, str) else tuple(names)
            found = sorted((c for f in found for n in names
                            for c in trace.children(f, n)),
                           key=lambda c: c.start_ns)
        if not found:
            return None
        out.append(found)
    return out


def less(recs: Sequence, name: str) -> List[float]:
    """Each record's seconds less those of its children `name`."""
    return [r.seconds - sum(c.seconds for c in trace.children(r, name))
            for r in recs]


def descendants(rec, name: str) -> list:
    """The records of `name` at any depth under `rec`."""
    found, todo = [], [rec]
    while todo:
        for c in trace.children(todo.pop()):
            (found if c.name == name else todo).append(c)
    return found


def mean_total(groups: Optional[List[list]]) -> Optional[float]:
    """The mean, over the groups, of the seconds of each group's
    records."""
    if groups is None:
        return None
    return sum(r.seconds for g in groups for r in g) / len(groups)
