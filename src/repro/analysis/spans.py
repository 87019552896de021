"""Host spans and self-time counters for the serving path.

:class:`region` marks one piece of host work on the serving thread: a
decode step's preparation, a prefill chunk, a page copy, a scheduler
call. It does two things:

* it opens a ``jax.profiler.TraceAnnotation`` under the region's name, so
  a captured trace shows the region on the profiler's host plane, on the
  same clock as the device's operations; keyword metadata (``pid``,
  ``step``, ``rows``, ...) lands in the event's stats, not in its name;
* given a ``counter`` — a ``(sink, field)`` pair — it adds the region's
  **self time** to ``sink.field``: its ``perf_counter`` duration less the
  duration of the regions nested inside it (a thread-local stack keeps
  the nesting). A page copy inside a decode step therefore counts once,
  as transfer time, and not again as decode time.

The fields live on :class:`HostTimes`, which
:class:`~repro.serving.router.RouterMetrics` extends: the router hands its
metrics to its engines, their pools, its transfer planes and its
scheduler, so every layer counts into the router's counters as the work
happens. A component built without a router counts into a private
:class:`HostTimes`. Counters only record: no decision reads them. They
are added to without a lock, so one sink belongs to one thread (the
router's serving thread; engines warmed up in parallel each hold their
own until the router is built).

Regions are always on. With no profiler session active one costs about a
microsecond (the annotation's enter and exit plus two clock reads), so
they stay per step, per chunk, per page and per scheduler call — never
per token or per slot, and never inside a jitted function.

``jax`` is imported on the first region, not here: :mod:`repro.core`
uses regions and stays importable without it.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from time import perf_counter

_tls = threading.local()
_annotation = None      # jax.profiler.TraceAnnotation, on first use


@dataclass
class HostTimes:
    """Seconds of serving-thread time by layer: the summed self time of
    the regions that name each field."""

    decode_host_s: float = 0.0     # engine.decode_prep + engine.decode_finish
    prefill_host_s: float = 0.0    # engine.admit + engine.chunk
    transfer_host_s: float = 0.0   # pool.copy_page_* + plane.advance
    sched_host_s: float = 0.0      # scheduler.* + router.apply_plan


class region:
    """``with region(name, counter=None, **meta):`` — see the module
    docstring."""

    __slots__ = ("_ann", "_counter", "_t0", "_nested")

    def __init__(self, name: str, counter: tuple | None = None, **meta):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._ann = _annotation(name, **meta)
        self._counter = counter

    def __enter__(self) -> "region":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        self._nested = 0.0
        self._ann.__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dt = perf_counter() - self._t0
        self._ann.__exit__(*exc)
        stack = _tls.stack
        stack.pop()
        if stack:
            stack[-1]._nested += dt
        if self._counter is not None:
            sink, field = self._counter
            setattr(sink, field, getattr(sink, field) + dt - self._nested)
        return False
