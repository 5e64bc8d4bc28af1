"""Wall-clock spans: where the host's time goes inside a serving step.

The rest of ``repro.obs`` watches the virtual clock.  This instrument watches
the real one.  ``span(name, **attrs)`` is a context manager that

  * stamps ``time.perf_counter_ns()`` at entry and exit into one process-wide
    ring of ``Record``s (name, start, end, the span open around it on the
    same thread, the thread, attrs), and
  * opens a ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
    trace shows the span on its host plane, on the clock of the device
    planes, beside the ops it dispatched.

``perf_counter`` is the clock of the benchmark's own stamps, so spans and
client stamps compare directly.  Parents are tracked per thread.

The recorder hands out raw records only (``records()``); turning them into
numbers is the reader's business.  The ring keeps the newest ``CAPACITY``
records; ``since_ns()`` says from when on it holds every span, so a reader
can tell that a window was evicted.

The switch is the observatory's: on while ``REPRO_OBS`` is not ``0``
(``observability_default()``, read at import and by ``refresh()``).  Off,
``span()`` returns a shared no-op and stamps nothing.
"""

from __future__ import annotations

import collections
import itertools
import threading
from time import perf_counter_ns
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

from repro.core.policy import observability_default

#: records the ring keeps; the oldest go first
CAPACITY = 1 << 17


class Record(NamedTuple):
    id: int
    #: id of the span open around this one (0 at a root)
    parent: int
    name: str
    start_ns: int
    end_ns: int
    thread: int
    attrs: dict


#: ``Record`` fields as plain tuples (cheaper to make); appends and copies
#: of a deque are atomic, so threads share it without a lock
_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_on = observability_default()
#: end of the newest record the ring has let go (0: none yet)
_dropped_end_ns = 0


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "start_ns", "end_ns",
                 "_note")

    def __init__(self, name: str, attrs: dict, start_ns: Optional[int]):
        self.name = name
        self.attrs = attrs
        self.start_ns = start_ns
        self.end_ns = None

    def end(self, end_ns: int, **attrs) -> None:
        """Close at ``end_ns`` (a stamp the caller already took) rather
        than at exit."""
        self.end_ns = end_ns
        self.attrs.update(attrs)

    def __enter__(self) -> "_Span":
        stack = _stack()
        self.parent = stack[-1] if stack else 0
        self.id = next(_ids)
        stack.append(self.id)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        if self.start_ns is None:
            self.start_ns = perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        global _dropped_end_ns
        end = perf_counter_ns() if self.end_ns is None else self.end_ns
        self._note.__exit__(None, None, None)
        _stack().pop()
        if len(_ring) == CAPACITY:
            _dropped_end_ns = max(_dropped_end_ns, _ring[0][4])
        _ring.append((self.id, self.parent, self.name, self.start_ns, end,
                      threading.get_ident(), self.attrs))
        return False


class _Noop:
    __slots__ = ()

    def end(self, end_ns: int, **attrs) -> None:
        pass

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


def span(name: str, *, start_ns: Optional[int] = None, **attrs):
    """A wall-clock span named ``name``; ``start_ns`` reuses a
    ``perf_counter_ns`` stamp the caller already took."""
    if not _on:
        return _NOOP
    return _Span(name, attrs, start_ns)


def records() -> list:
    """Every record the ring holds, oldest end first."""
    return [Record(*r) for r in _ring.copy()]


def since_ns() -> int:
    """The ring holds every span that started at or after this
    ``perf_counter_ns`` time (0: it has let none go)."""
    return _dropped_end_ns


def refresh() -> None:
    """Read the switch (``REPRO_OBS``) again."""
    global _on
    _on = observability_default()


def clear() -> None:
    """Forget every record."""
    global _dropped_end_ns
    _ring.clear()
    _dropped_end_ns = 0
