"""Named host spans of the serving path.

``span(name, **meta)`` wraps ``jax.profiler.TraceAnnotation`` under the
name ``serve.<name>``, with ``meta`` as the annotation's metadata.  Run
the server under ``jax.profiler`` (``start_trace`` / ``stop_trace``, or
``jax.profiler.trace``) and the ``serve.*`` spans appear in the trace on
the device trace's clock, next to the operations they dispatched.

While a profiler session runs, every span is also kept in memory, on the
``time.perf_counter`` clock that callers stamp requests and ticks with:
``recorded()`` returns the spans as :class:`Span` records (the newest
``MAX_RECORDS``).  With no session running a span is an idle annotation
and nothing is recorded, so the profiler is the only switch.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import NamedTuple

import jax

MAX_RECORDS = 65536
PREFIX = "serve."

_Annotation = jax.profiler.TraceAnnotation


class Span(NamedTuple):
    name: str                  # without the ``serve.`` prefix
    parent: str | None         # the enclosing span's name, if any
    t0: float                  # time.perf_counter() at entry
    t1: float                  # ... and at exit
    meta: dict


_records: deque[Span] = deque(maxlen=MAX_RECORDS)
_local = threading.local()     # per thread: the names of the open spans


class span:
    """Context manager: one ``serve.<name>`` span (see the module)."""

    __slots__ = ("name", "meta", "_ann", "_t0", "_parent")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta
        self._ann = _Annotation(PREFIX + name, **meta)
        self._t0 = None

    def __enter__(self) -> span:
        self._ann.__enter__()
        if _Annotation.is_enabled():
            stack = getattr(_local, "stack", None)
            if stack is None:
                stack = _local.stack = []
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._t0 is not None:
            t1 = time.perf_counter()
            _local.stack.pop()
            _records.append(Span(self.name, self._parent, self._t0, t1,
                                 self.meta))
        self._ann.__exit__(*exc)


def recorded() -> list[Span]:
    """A copy of the spans recorded so far, in the order they closed."""
    return list(_records)
