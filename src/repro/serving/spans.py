"""Host spans of the serving path: one facility, always on.

    with span("engine.wave", live=8):
        ...
    with span("engine.step") as attrs:
        ...
        attrs["reads"] = n        # known only at the end

A span records its name, start and end on `time.perf_counter`, the span
that encloses it (its parent) and its attrs into one bounded in-memory
buffer, read through `records()`; the oldest records fall out first. The
same block is a `jax.profiler.TraceAnnotation`, so in a profiler trace it
sits in the host plane, on the device ops' clock. Attrs given when the
span opens go on both at once; keys added to the dict that `with` gives
are set when it closes. There is no switch and no exporter: the profiler
trace is the operator's view, `records()` the benchmark's.
"""
from __future__ import annotations

import contextlib
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

import jax

clock = time.perf_counter
MAX_RECORDS = 65536

_records: deque = deque(maxlen=MAX_RECORDS)
_ids = itertools.count()
_open: List["Record"] = []         # the spans open now, innermost last


@dataclass
class Record:
    id: int
    name: str
    parent: Optional[int]           # id of the enclosing span, if any
    start: float
    end: float = float("nan")       # set when the span closes
    attrs: Dict[str, object] = field(default_factory=dict)


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Dict[str, object]]:
    rec = Record(next(_ids), name, _open[-1].id if _open else None, clock(),
                 attrs=dict(attrs))
    _open.append(rec)
    try:
        with jax.profiler.TraceAnnotation(name, **attrs) as tm:
            try:
                yield rec.attrs
            finally:
                late = {k: v for k, v in rec.attrs.items() if k not in attrs}
                if late:
                    tm.set_metadata(**late)
    finally:
        rec.end = clock()
        _open.pop()
        _records.append(rec)


def records() -> List[Record]:
    """The closed spans still in the buffer, in the order they closed."""
    return list(_records)
