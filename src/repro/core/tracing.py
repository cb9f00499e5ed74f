"""Spans and counters of the eGPU launch path, always on.

``span(name)`` marks one layer boundary. It writes a
``jax.profiler.TraceAnnotation`` of that name, so a profiler trace shows the
span on its host plane beside the device's operations, and it keeps the
finished span in a bounded in-memory ring, a flight recorder of the last
``RING`` spans that ``recent()`` reads back after a slow call. Spans nest
per thread: the first span a thread opens with none open is a root, and
every span inside it carries that root's id, one id per call.

``totals()`` gives, per name, the count and the self time of every span
closed since start-up (or ``reset()``): a span's duration less its child
spans' and less the JAX compile-path time spent inside it. That JAX time
comes from a ``jax.monitoring`` listener, registered when this module is
imported, under four names of its own (``JAX_EVENTS``), so the totals of
any set of names add up without counting a nanosecond twice.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax

RING = 65536                # finished spans the flight recorder keeps

# JAX's compile-path durations (names as of JAX 0.9), by the name their
# totals take
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}


class Span(NamedTuple):
    """One finished span; times are ``time.perf_counter_ns()``."""

    name: str
    start_ns: int
    end_ns: int
    parent: int | None      # the enclosing span's id; None for a root
    root: int               # the root's id, shared by every span of a call
    id: int


class _Thread(threading.local):
    """Each thread's open spans and its recent JAX events."""

    def __init__(self):
        self.stack: list[span] = []
        self.events = collections.deque(maxlen=4096)  # (start ns, ns)


_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_local = _Thread()
_lock = threading.Lock()
_totals: dict[str, tuple[int, int]] = {}    # name -> (count, self ns)


def _add(name: str, ns: int) -> None:
    with _lock:
        count, total = _totals.get(name, (0, 0))
        _totals[name] = (count + 1, total + ns)


class span:
    """``with span("egpu.launch"): ...`` -- one layer boundary."""

    __slots__ = ("name", "id", "parent", "root", "inner_ns", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        stack = _local.stack
        self.id = next(_ids)
        if stack:
            self.parent, self.root = stack[-1].id, stack[-1].root
        else:
            self.parent, self.root = None, self.id
        self.inner_ns = 0           # children's and JAX's time inside
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        ns = t1 - self._t0
        if stack:
            stack[-1].inner_ns += ns
        _ring.append(Span(self.name, self._t0, t1, self.parent, self.root,
                          self.id))
        _add(self.name, ns - self.inner_ns)


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    name = JAX_EVENTS.get(event)
    if name is None:
        return
    ns = int(duration_secs * 1e9)
    start = time.perf_counter_ns() - ns
    # one thread's events nest (a jit traced inside another's trace, a
    # cache load inside a compile) and arrive innermost first: take the
    # ones this event covers out of its own time
    inner = 0
    while _local.events and _local.events[-1][0] >= start:
        inner += _local.events.pop()[1]
    _local.events.append((start, ns))
    _add(name, ns - inner)
    if _local.stack:
        _local.stack[-1].inner_ns += ns - inner


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def recent() -> list[Span]:
    """The ring's finished spans, oldest first (children before their
    parent: a span is kept when it closes)."""
    return list(_ring)


def totals() -> dict[str, tuple[int, float]]:
    """``{name: (count, self seconds)}`` since start-up or ``reset()``."""
    with _lock:
        return {n: (c, ns / 1e9) for n, (c, ns) in _totals.items()}


def reset() -> None:
    """Empty the ring and the totals."""
    with _lock:
        _ring.clear()
        _totals.clear()
