"""Agent sessions on the wall clock through the program's own router.

The driver runs :class:`repro.serving.router.MoriRouter` through the same
plan protocol as ``MoriRouter.replay``: scheduler events, ``apply_plan``,
the decode pump (``Engine.begin_submit`` / ``prefill_step`` / ``step``),
the ``PagePool`` and the transfer planes. It differs from ``replay`` in
two things only:

* the clock is the wall clock: tool calls are real waits, turns fall due
  at seeded wall times, and a resident slot steps whenever the pump
  visits it (decode is not paced by a recorded reasoning time);
* the traffic is :mod:`bench.sessions`.

The router has no public wall-clock entry, so the driver reaches these
private hooks of ``MoriRouter``, all in :meth:`Driver.__init__` and
:meth:`Driver.run`:

* ``_push``: set, so transfers run on the asynchronous planes (``replay``
  sets it) and planes report when their next page is due;
* ``_rs``: stands in for the replay state, where ``_submit_into_slot``
  reads each step's recorded reasoning time (here always 0);
* ``_complete_step``: replaced, to count the turn, answer the scheduler
  with the wall time and start the tool call;
* ``_record_ttft``: replaced, to time a turn's first token;
* ``_pending``, ``_dispatched``: a due turn enters ``_pending`` as
  ``replay``'s ``_issue`` does;
* ``_pump_replica``, ``_advance_planes``: one pump pass per replica and the
  planes' due pages, between the driver's own events.
"""
from __future__ import annotations

import heapq
import itertools
import time
from dataclasses import dataclass, field

from bench.check import SessionRecord, TurnRecord
from bench.sessions import Population, Session

#: longest idle sleep, so a plane or a freed slot is never left waiting
IDLE_SLEEP_S = 0.002


class _Unpaced:
    """Replay state as ``MoriRouter._submit_into_slot`` reads it
    (``state[pid]["trace"].steps[i].reasoning_wall_s``): always 0."""

    reasoning_wall_s = 0.0
    steps = property(lambda self: self)

    def __getitem__(self, key):
        return self


@dataclass
class TurnState:
    due: float
    prompt_len: int
    asked: int
    tokens: list[float] = field(default_factory=list)  # time of each token
    reloaded: bool = False


def _span(name: str, fn):
    """``fn`` inside a profiler host span named ``name``."""
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


class Driver:
    def __init__(self, router, pop: Population, *, spans: bool = False):
        from repro.serving.router import _ReplayState
        from repro.core.actions import Forward
        from repro.core.types import Tier

        self.router = r = router
        self.pop = pop
        self.t0 = time.perf_counter()
        self._events: list = []          # (due, seq, fn)
        self._seq = itertools.count()
        self._wakes: list[float] = []         # heap of plane/pump due times
        self.sessions: dict[str, Session] = {}
        self.records: dict[str, SessionRecord] = {}
        self.turns: dict[tuple[str, int], TurnState] = {}
        self.current: dict[str, tuple[str, int]] = {}
        self.lateness: list[tuple[float, float]] = []   # (due, late by)
        self.decode_steps: list[tuple] = []   # (start, end, rows, sum of context)
        self.chunks: list[tuple] = []         # (start, end, tokens, context end)
        self.admitted: list[float] = []       # times turns entered a slot
        self._reload_pids: set[str] = set()
        self.short_turns = 0     # turns served fewer tokens than asked
        # ---- private hooks of MoriRouter (see the module docstring) ----
        r._push = lambda eta, fn: heapq.heappush(self._wakes, eta)
        r._rs = _ReplayState(state=_Unpaced(), vocab_size=pop.vocab_size,
                             max_new_tokens=0)
        r._complete_step = self._retired
        r._record_ttft = self._first_token
        # -----------------------------------------------------------------
        for eng in r.engines:
            self._instrument(eng, spans)
        apply_plan = r.apply_plan

        def apply_and_note(plan):
            for act in plan:
                if isinstance(act, Forward) and act.source_tier in (Tier.CPU, Tier.SSD):
                    self._reload_pids.add(act.pid)
            apply_plan(plan)
        r.apply_plan = _span("router.apply_plan", apply_and_note) if spans else apply_and_note
        self._tick = (_span("scheduler.tick", r.sched.tick) if spans else r.sched.tick)
        self._spans = spans
        self._next_tick = r.sched.config.tick_interval_s

    # ---------------------------------------------------------------- clock
    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def at(self, due: float, fn) -> None:
        heapq.heappush(self._events, (due, next(self._seq), fn))

    # ------------------------------------------------------- instrumentation
    def _instrument(self, eng, spans: bool) -> None:
        step, prefill, begin = eng.step, eng.prefill_step, eng.begin_submit

        def timed_step(active=None):
            ids = [s for s in (list(eng.slots) if active is None else active)
                   if s in eng.slots]
            pids = [eng.slots[s].request.program_id for s in ids]
            ctx = sum(int(eng.lengths[s]) + 1 for s in ids)
            t = self.clock()
            out = step(active)
            end = self.clock()
            for pid in pids:
                self._token(pid, end)
            self.decode_steps.append((t, end, len(ids), ctx))
            return out

        def timed_prefill(job, token_budget=None):
            before = job.cursor
            t = self.clock()
            done = prefill(job, token_budget)
            self.chunks.append((t, self.clock(), job.cursor - before,
                                job.cached_tokens + job.cursor))
            return done

        def timed_begin(req):
            self.admitted.append(self.clock())
            return begin(req)

        eng.begin_submit = timed_begin
        eng.step = _span("engine.step", timed_step) if spans else timed_step
        eng.prefill_step = (_span("engine.prefill_step", timed_prefill)
                            if spans else timed_prefill)
        if spans:
            pool = eng.pool
            for name in ("copy_page_to_host", "copy_page_to_device",
                         "offload_page", "reload_page"):
                setattr(pool, name, _span(f"pool.{name}", getattr(pool, name)))

    # ------------------------------------------------------------- sessions
    def start(self) -> None:
        """Bring in the initial population at its seeded due times."""
        for shape, due in self.pop.initial:
            self._begin(self.pop.new_session(shape), due)

    def _begin(self, sess: Session, due: float) -> None:
        r = self.router
        self.sessions[sess.pid] = sess
        self.records[sess.pid] = SessionRecord(sess.pid, sess.tokens)
        r.sched.program_arrived(sess.pid, r.kv_bytes_per_token, self.clock(),
                                wire_bytes_per_token=r.wire_bytes_per_token)
        self.at(due, lambda now, s=sess: self._issue(s, now))

    def _issue(self, sess: Session, due: float) -> None:
        from repro.serving.engine import EngineRequest

        r = self.router
        now = self.clock()
        self.lateness.append((due, now - due))
        tokens, out = sess.prompt()
        key = (sess.pid, sess.turn)
        self.turns[key] = TurnState(due=due, prompt_len=len(tokens), asked=out)
        self.current[sess.pid] = key
        self._reload_pids.discard(sess.pid)
        r._pending[sess.pid] = (
            EngineRequest(sess.pid, tokens, max_new_tokens=out), sess.turn
        )
        r.apply_plan(r.sched.request_arrived(sess.pid, len(tokens), now))
        if sess.pid not in r._dispatched:
            r.metrics.gated_events += 1

    def _first_token(self, pid: str, step_idx: int) -> None:
        self._token(pid, self.clock())

    def _token(self, pid: str, t: float) -> None:
        ts = self.turns[self.current[pid]]
        ts.tokens.append(t)
        if len(ts.tokens) == 1 and pid in self._reload_pids:
            ts.reloaded = True

    def _retired(self, pid: str, step_idx: int, comp, end: float) -> None:
        r = self.router
        m = r.metrics
        now = self.clock()
        m.steps_completed += 1
        m.tokens_generated += len(comp.output_tokens)
        m.cached_tokens += comp.cached_tokens
        m.prefilled_tokens += comp.prefilled_tokens
        r.apply_plan(r.sched.request_completed(pid, len(comp.output_tokens), now))
        sess = self.sessions[pid]
        ts = self.turns[(pid, step_idx)]
        if len(comp.output_tokens) < max(ts.asked, 2):
            self.short_turns += 1
        self.records[pid].turns.append(
            TurnRecord(ts.prompt_len, list(comp.output_tokens), ts.reloaded)
        )
        tool_s, ended = sess.served(list(comp.output_tokens))
        if ended:
            r.apply_plan(r.sched.program_finished(pid, now))
            del self.sessions[pid]
            self._begin(self.pop.new_session(), now + tool_s)
        else:
            self.at(now + tool_s, lambda t, s=sess: self._issue(s, t))

    # ----------------------------------------------------------------- loop
    def run(self, until: float) -> None:
        """Serve until the clock reads ``until``."""
        r = self.router
        tick_s = r.sched.config.tick_interval_s
        idle = (_span("driver.idle", time.sleep) if self._spans else time.sleep)
        while True:
            now = self.clock()
            if now >= until:
                return
            while self._events and self._events[0][0] <= now:
                due, _, fn = heapq.heappop(self._events)
                fn(due)
            if now >= self._next_tick:
                r.apply_plan(self._tick(now))
                self._next_tick += tick_s
            r._advance_planes(now)
            acted = False
            for i in range(len(r.engines)):
                acted |= r._pump_replica(i, now, True)
            if acted:
                continue
            while self._wakes and self._wakes[0] <= now:
                heapq.heappop(self._wakes)
            nxt = min(until, self._next_tick)
            if self._events:
                nxt = min(nxt, self._events[0][0])
            if self._wakes:
                nxt = min(nxt, self._wakes[0])
            wait = min(nxt - self.clock(), IDLE_SLEEP_S)
            if wait > 0:
                idle(wait)

    def snapshot(self) -> dict:
        """The router's counters now."""
        import dataclasses

        m = self.router.metrics
        return {f.name: getattr(m, f.name) for f in dataclasses.fields(m)
                if isinstance(getattr(m, f.name), (int, float))}
