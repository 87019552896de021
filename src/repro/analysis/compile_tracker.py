"""Compile-cache interposer: recompile budgets for the hot-path jits.

The serving fast path only holds its latency numbers while every decode
step and prefill chunk dispatches from jit's compile cache.  One
mid-replay retrace stalls every live slot in the pump for the full
XLA compile; worse, it is *silent* — the replay still produces correct
tokens, just slowly.  This module makes "the replay compiled nothing
new" a checkable property:

* every hot-path jitted function registers here by name
  (``Engine.__init__`` does this when the tracker is armed);
* ``Engine.warmup()`` calls :meth:`CompileTracker.mark_warm` once it has
  run every bucket shape, snapshotting each function's per-jit cache
  size (``fn._cache_size()`` — the number of distinct lowerings jit
  holds for that callable);
* at end of replay the router asks :meth:`post_warmup_compiles`; any
  registered function whose cache grew past its warm snapshot compiled
  a shape warmup missed, and the replay fails loudly with the count.

The budget is enforced on the *per-function* jit caches rather than the
process-global backend-compile counter because eager ops (``jnp.argmax``
on a host int, debug prints, test scaffolding) legitimately trigger
backend compiles that are not hot-path retraces.  The global counter is
still useful for attribution, so when armed the tracker also registers
a ``jax.monitoring`` listener and keeps a phase-tagged event log of
every backend compile (see :meth:`phase`); the log says *when* a rogue
compile happened, the cache sizes say *which function* it hit.

Armed via ``REPRO_JITAUDIT=1`` (mirrors kvsan's ``REPRO_KVSAN``) or
programmatically with ``get_tracker().arm()``.  Unarmed, the only cost
an engine pays is one ``enabled()`` check in ``__init__``.
"""
from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass, field

ENV_VAR = "REPRO_JITAUDIT"

#: jax.monitoring event keys that mark one backend (XLA) compilation
_COMPILE_EVENTS = (
    "/jax/core/compile/backend_compile_duration",
)


def enabled() -> bool:
    """True when the compile tracker is armed via the environment."""
    return os.environ.get(ENV_VAR, "0") not in ("", "0")


@dataclass
class _Entry:
    fn: object
    #: cache size snapshotted by mark_warm (None until warmed)
    warm: int | None = None


@dataclass
class CompileEvent:
    """One backend compile observed by the monitoring listener."""

    phase: str
    event: str
    duration_s: float


class CompileTracker:
    """Process-wide registry of hot-path jits and their compile budgets."""

    def __init__(self) -> None:
        self._entries: dict[str, _Entry] = {}
        self._armed = False
        self._listener_installed = False
        self._phase = "startup"
        self.events: list[CompileEvent] = []

    # ------------------------------------------------------------- arming
    def arm(self) -> None:
        """Arm the tracker and install the backend-compile listener (once;
        jax.monitoring listeners cannot be unregistered individually, so
        the listener stays installed and checks ``_armed``)."""
        self._armed = True
        if self._listener_installed:
            return
        from jax import monitoring

        def _on_event(event: str, duration: float, **kw) -> None:
            if self._armed and any(event.startswith(e) for e in _COMPILE_EVENTS):
                self.events.append(CompileEvent(self._phase, event, duration))

        monitoring.register_event_duration_secs_listener(_on_event)
        self._listener_installed = True

    def disarm(self) -> None:
        self._armed = False

    @property
    def armed(self) -> bool:
        return self._armed

    # ------------------------------------------------------- registration
    def register(self, name: str, fn) -> None:
        """Track ``fn``'s jit cache under ``name``.

        Re-registering a name replaces the entry (fuzz rounds rebuild
        engines; the previous round's function is dead).  Registering the
        same object twice (the process-global chunk-prefill fn is shared
        across engines) is a no-op so an earlier warm snapshot survives.
        """
        prev = self._entries.get(name)
        if prev is not None and prev.fn is fn:
            return
        self._entries[name] = _Entry(fn)

    def registered(self) -> tuple[str, ...]:
        return tuple(self._entries)

    # ------------------------------------------------------------ budgets
    @staticmethod
    def _size(fn) -> int:
        size = getattr(fn, "_cache_size", None)
        return int(size()) if callable(size) else -1

    def cache_sizes(self) -> dict[str, int]:
        """Current per-function compile-cache entry counts."""
        return {name: self._size(e.fn) for name, e in self._entries.items()}

    def mark_warm(self, names: tuple[str, ...] | None = None) -> dict[str, int]:
        """Snapshot cache sizes as the warm baseline (all entries, or just
        ``names``); returns the snapshot.  Compiles past this baseline are
        budget violations."""
        snap: dict[str, int] = {}
        for name, e in self._entries.items():
            if names is not None and name not in names:
                continue
            e.warm = self._size(e.fn)
            snap[name] = e.warm
        return snap

    def post_warmup_compiles(self) -> dict[str, tuple[int, int]]:
        """``{name: (warm_size, current_size)}`` for every registered
        function whose compile cache grew after its warm snapshot.  Empty
        dict == budget held.  Functions never marked warm are skipped (no
        baseline to compare against)."""
        out: dict[str, tuple[int, int]] = {}
        for name, e in self._entries.items():
            if e.warm is None:
                continue
            cur = self._size(e.fn)
            if cur > e.warm:
                out[name] = (e.warm, cur)
        return out

    def marked(self) -> bool:
        """True once any registered function has a warm baseline."""
        return any(e.warm is not None for e in self._entries.values())

    # ------------------------------------------------------------- phases
    @contextlib.contextmanager
    def phase(self, label: str):
        """Tag backend-compile events with ``label`` for attribution."""
        prev, self._phase = self._phase, label
        try:
            yield
        finally:
            self._phase = prev

    def events_in(self, label: str) -> list[CompileEvent]:
        return [e for e in self.events if e.phase == label]

    # -------------------------------------------------------------- reset
    def reset(self) -> None:
        """Drop registrations, baselines and the event log (tests)."""
        self._entries.clear()
        self.events.clear()
        self._phase = "startup"


_TRACKER: CompileTracker | None = None


def get_tracker() -> CompileTracker:
    """The process-wide tracker (created on first use; armed from the
    environment so ``REPRO_JITAUDIT=1`` needs no other plumbing)."""
    global _TRACKER
    if _TRACKER is None:
        _TRACKER = CompileTracker()
    if enabled() and not _TRACKER.armed:
        _TRACKER.arm()
    return _TRACKER
