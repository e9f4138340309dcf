"""Host spans and counters of one ``ServeEngine.serve`` call.

A ``Timeline`` records what the serving loop's host did, on two clocks
at once:

- each ``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation``
  named ``serve.<name>`` (with ``attrs`` as its stats), so under the
  profiler the span sits on the host plane, on the device trace's clock;
- it also appends a ``Span`` on ``time.perf_counter()`` relative to the
  serve's ``t0``, the clock every ``RequestResult`` stamp uses, so the
  spans can be read with no profiler running.

``count(name, n)`` keeps counters.  One process-wide JAX monitoring
listener counts each XLA backend compile as ``compiles.<span>`` on the
timeline whose span is innermost open, which names the loop step that
recompiled.  Recording is always on: a ``TraceAnnotation`` costs about a
microsecond when no profiler runs.  Nothing here syncs the device or
pulls data to the host.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax

SPAN_PREFIX = "serve."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# (timeline, span name) of every span open in this process, innermost
# last: where the compile listener books a compile
_open: List[Tuple["Timeline", str]] = []


class Span(NamedTuple):
    name: str
    start_s: float      # perf_counter seconds after the timeline's t0
    end_s: float
    attrs: Dict

    @property
    def duration_s(self) -> float:
        return self.end_s - self.start_s


def _on_duration(event: str, duration: float, **_):
    if event == COMPILE_EVENT and _open:
        tl, name = _open[-1]
        tl.count(f"compiles.{name}")


@functools.lru_cache(maxsize=None)
def _listen():
    """Register the compile listener, once per process."""
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


class Timeline:
    """Spans and counters of one serve call."""

    def __init__(self, t0: Optional[float] = None):
        _listen()
        self.t0 = time.perf_counter() if t0 is None else t0
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[None]:
        start = time.perf_counter()
        _open.append((self, name))
        try:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **attrs):
                yield
        finally:
            _open.pop()
            self.spans.append(Span(name, start - self.t0,
                                   time.perf_counter() - self.t0, attrs))

    def count(self, name: str, n: int = 1):
        self.counters[name] = self.counters.get(name, 0) + n

    def named(self, *names: str) -> List[Span]:
        """The spans called any of ``names``, in the order they closed."""
        return [s for s in self.spans if s.name in names]

    def total_s(self, *names: str) -> float:
        """Host seconds inside the spans called any of ``names``."""
        return sum(s.duration_s for s in self.named(*names))
