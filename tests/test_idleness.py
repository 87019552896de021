"""Unit + property tests for the windowed idleness metric (paper §4.2)."""
import math

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.idleness import IdlenessTracker
from repro.core.types import Status


def run_cycles(tracker, cycles, t0=0.0):
    """cycles: list of (reasoning_s, acting_s). Returns end time."""
    t = t0
    for reasoning, acting in cycles:
        tracker.transition(Status.REASONING, t)
        t += reasoning
        tracker.transition(Status.ACTING, t)
        t += acting
    return t


def test_idleness_basic_ratio():
    tr = IdlenessTracker(window=5)
    t = run_cycles(tr, [(1.0, 3.0)] * 5)
    assert math.isclose(tr.idleness(t), 0.75, rel_tol=1e-9)


def test_unknown_program_defaults_to_half():
    tr = IdlenessTracker(window=5)
    assert tr.idleness(0.0) == 0.5


def test_window_drops_stale_history():
    tr = IdlenessTracker(window=2)
    # two very idle cycles followed by two fully busy cycles
    t = run_cycles(tr, [(0.1, 100.0), (0.1, 100.0)])
    t = run_cycles(tr, [(5.0, 0.1), (5.0, 0.1)], t0=t)
    # window=2 only sees the busy cycles
    assert tr.idleness(t) < 0.05


def test_ongoing_long_tool_call_raises_idleness():
    """Paper: responsiveness — an in-progress long call grows in the window."""
    tr = IdlenessTracker(window=5)
    t = run_cycles(tr, [(2.0, 0.5)] * 5)  # busy phase: iota = 0.2
    busy_iota = tr.idleness(t)
    assert busy_iota < 0.25
    tr.transition(Status.REASONING, t)
    tr.transition(Status.ACTING, t + 1.0)  # enters a tool call at t+1
    assert tr.idleness(t + 1.0 + 60.0) > 0.8  # 60s in: clearly idle


def test_single_outlier_is_diluted():
    """Paper: robustness — one long call amid a busy phase is smoothed."""
    tr = IdlenessTracker(window=5)
    t = run_cycles(tr, [(2.0, 0.5)] * 4)
    t = run_cycles(tr, [(2.0, 6.0)], t0=t)  # one slow shell command
    # 4 cycles of 2/0.5 + 1 cycle of 2/6 -> iota = 8/18 ~ 0.44, not ~1
    assert tr.idleness(t) < 0.5


def test_gated_time_excluded():
    tr = IdlenessTracker(window=5)
    t = run_cycles(tr, [(1.0, 1.0)] * 3)
    before = tr.idleness(t)
    tr.transition(Status.GATED, t)
    # a long scheduler-imposed wait must not change the metric
    assert math.isclose(tr.idleness(t + 500.0), before, rel_tol=1e-9)


def test_resume_after_idle_phase_drops_quickly():
    tr = IdlenessTracker(window=5)
    t = run_cycles(tr, [(1.0, 120.0)])  # one idle-phase cycle
    assert tr.idleness(t) > 0.9
    t = run_cycles(tr, [(3.0, 0.2)] * 5, t0=t)  # burst of short calls
    assert tr.idleness(t) < 0.1  # window pushed the long call out


@given(
    cycles=st.lists(
        st.tuples(
            st.floats(0.01, 100.0, allow_nan=False),
            st.floats(0.01, 100.0, allow_nan=False),
        ),
        min_size=1,
        max_size=30,
    ),
    window=st.integers(1, 8),
)
@settings(max_examples=200, deadline=None)
def test_idleness_always_in_unit_interval(cycles, window):
    tr = IdlenessTracker(window=window)
    t = run_cycles(tr, cycles)
    iota = tr.idleness(t)
    assert 0.0 <= iota <= 1.0


@given(
    cycles=st.lists(
        st.tuples(st.floats(0.01, 50.0), st.floats(0.01, 50.0)),
        min_size=6,
        max_size=12,
    )
)
@settings(max_examples=100, deadline=None)
def test_idleness_matches_manual_window(cycles):
    """iota must equal Eq. (1) computed over exactly the last k cycles."""
    k = 5
    tr = IdlenessTracker(window=k)
    t = run_cycles(tr, cycles)
    last = cycles[-k:]
    acting = sum(a for _, a in last)
    reasoning = sum(r for r, _ in last)
    assert math.isclose(tr.idleness(t), acting / (reasoning + acting), rel_tol=1e-9)
