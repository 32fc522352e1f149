"""Spans at the serving path's layer boundaries, on the profiler's clock.

One tracer serves the whole process (``TRACER``; the module-level
functions below are its methods), because the spans sit deep inside the
serving stack — scheduler, engine, stream fuser — and a process has one
profiler to line them up with.

* **Off** (the default): ``span(name, **attrs)`` still measures its own
  duration with two ``perf_counter_ns`` reads, since
  ``EnsembleResponse.timing`` is read from the stage spans.  It records
  nothing, enters no ``jax.profiler.TraceAnnotation`` and no listener is
  registered.
* **On** (``enable()``): every finished span becomes one record in a
  bounded ring of ``CAPACITY`` records::

      {"name", "id", "parent", "thread", "start_ns", "end_ns", "attrs"}

  Times are ``perf_counter_ns``; ``parent`` is the innermost span open on
  the same thread when the span began; ``attrs`` holds what the caller
  passed (``batch``, ``req``, ``rows``, ...) and compile counters.  Each
  nested span also enters ``jax.profiler.TraceAnnotation(name)``, so it
  shows in the same profiler trace as the device's operations.  One ``jax.monitoring`` listener adds
  each compile event of a thread to the innermost span open there:
  ``compile_requests_use_cache`` and ``cache_hits`` as counts,
  ``jaxpr_trace_duration``, ``jaxpr_to_mlir_module_duration`` and
  ``backend_compile_duration`` as seconds.
* **Clocks**: ``anchor()`` stores a pair (``time.time_ns()``,
  ``perf_counter_ns()``); one is taken at ``enable`` and one at ``dump``.
  The profiler stamps host events on the wall clock, so the pairs put ring
  times on the profiler's clock.
* ``dump(path)`` writes the anchors and then the ring as JSON lines.

``start(name, **attrs)`` opens a span that is not nested in the thread's
stack and is ended by ``Span.end()``, possibly on another thread (a request
from submit to its future being set); it takes no annotation.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from typing import List, Optional, Tuple

import jax

COUNTED_EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
                  "/jax/compilation_cache/cache_hits")
TIMED_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")
# records the ring keeps: over 400 batches of 8 that decode 32 tokens each
# (four spans a decode step, ~150 a batch)
CAPACITY = 65536


def _attr(event: str) -> str:
    return event.rsplit("/", 1)[1]


class Span:
    """One timed stretch; a context manager for spans nested on a thread."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "record", "_tracer",
                 "_nested", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict, nested: bool):
        self.name = name
        self.attrs = attrs
        self.start_ns = self.end_ns = 0
        self.record: Optional[dict] = None
        self._tracer = tracer
        self._nested = nested
        self._annotation = None

    def __enter__(self) -> "Span":
        self._tracer._begin(self)
        return self

    def __exit__(self, *exc) -> None:
        self.end()

    def set(self, **attrs) -> None:
        """Add attributes to the span's record (nothing while off)."""
        if self.record is not None:
            self.record["attrs"].update(attrs)

    def end(self) -> None:
        if self.end_ns:
            return
        self.end_ns = time.perf_counter_ns()
        if self.record is not None:
            self._tracer._end(self)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Tracer:
    def __init__(self):
        self._on = False
        self._ring: deque = deque(maxlen=1)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._anchors: List[Tuple[int, int]] = []

    def enable(self) -> None:
        """Start recording into a fresh ring of ``CAPACITY`` records."""
        if self._on:
            self.disable()
        with self._lock:
            self._ring = deque(maxlen=CAPACITY)
            self._anchors = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        self.anchor()
        self._on = True

    def disable(self) -> None:
        """Stop recording; the ring keeps what it holds until the next enable."""
        if not self._on:
            return
        self._on = False
        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)

    def anchor(self) -> Tuple[int, int]:
        """Store and return one (wall ns, perf_counter ns) pair."""
        pair = (time.time_ns(), time.perf_counter_ns())
        with self._lock:
            self._anchors.append(pair)
        return pair

    def anchors(self) -> List[Tuple[int, int]]:
        with self._lock:
            return list(self._anchors)

    def records(self) -> List[dict]:
        """The finished spans the ring holds, oldest first."""
        with self._lock:
            return list(self._ring)

    def dump(self, path: str) -> int:
        """Write the anchors, then one record per line; returns the records written."""
        self.anchor()
        anchors, recs = self.anchors(), self.records()
        with open(path, "w") as f:
            f.write(json.dumps({"anchors": anchors}) + "\n")
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
        return len(recs)

    def span(self, name: str, **attrs) -> Span:
        """A span nested in this thread's open spans; use it in ``with``."""
        return Span(self, name, attrs, nested=True)

    def start(self, name: str, **attrs) -> Span:
        """A span begun now, outside any thread's nesting; ``end()`` it."""
        sp = Span(self, name, attrs, nested=False)
        self._begin(sp)
        return sp

    # -- internals --------------------------------------------------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _begin(self, sp: Span) -> None:
        if self._on:
            rec = {"name": sp.name, "id": next(self._ids), "parent": None,
                   "thread": threading.get_ident(), "attrs": dict(sp.attrs)}
            if sp._nested:
                stack = self._stack()
                if stack:
                    rec["parent"] = stack[-1]["id"]
                stack.append(rec)
                sp._annotation = jax.profiler.TraceAnnotation(sp.name)
                sp._annotation.__enter__()
            sp.record = rec
        sp.start_ns = time.perf_counter_ns()

    def _end(self, sp: Span) -> None:
        rec = sp.record
        rec["start_ns"], rec["end_ns"] = sp.start_ns, sp.end_ns
        if sp._annotation is not None:
            sp._annotation.__exit__(None, None, None)
        if sp._nested:
            stack = self._stack()
            for k in range(len(stack) - 1, -1, -1):
                if stack[k] is rec:
                    del stack[k]
                    break
        with self._lock:
            self._ring.append(rec)

    def _innermost(self) -> Optional[dict]:
        stack = getattr(self._local, "stack", None)
        return stack[-1] if stack else None

    def _on_event(self, event: str, **kwargs) -> None:
        if event in COUNTED_EVENTS:
            rec = self._innermost()
            if rec is not None:
                key = _attr(event)
                rec["attrs"][key] = rec["attrs"].get(key, 0) + 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event in TIMED_EVENTS:
            rec = self._innermost()
            if rec is not None:
                key = _attr(event)
                rec["attrs"][key] = rec["attrs"].get(key, 0.0) + duration


TRACER = Tracer()
span = TRACER.span
start = TRACER.start
enable = TRACER.enable
disable = TRACER.disable
anchor = TRACER.anchor
anchors = TRACER.anchors
records = TRACER.records
dump = TRACER.dump
