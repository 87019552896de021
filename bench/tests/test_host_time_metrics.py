"""Readers of the program's host-time counters, over a synthetic window."""
from __future__ import annotations

import pytest

from bench.run import metric_reader
from bench.window import Window

#: reader -> (counter, denominator counter)
READERS = {
    "decode_host_ms": ("decode_host_s", "pump_steps"),
    "prefill_host_ms": ("prefill_host_s", "prefill_chunks"),
    "prefill_host_ms.saturated": ("prefill_host_s", "prefill_chunks"),
    "transfer_exposed_ms_per_turn": ("transfer_host_s", "steps_completed"),
    "sched_ms_per_turn": ("sched_host_s", "steps_completed"),
}


def window(c0: dict, c1: dict) -> Window:
    return Window(t0=10.0, t1=20.0, setup_s=5.0, cfg={}, turns={}, counters0=c0,
                  counters1=c1, decode_steps=[], chunks=[], admitted=[])


def counters(**kw) -> dict:
    c = {"pump_steps": 0, "prefill_chunks": 0, "steps_completed": 0, "decode_host_s": 0.0,
         "prefill_host_s": 0.0, "transfer_host_s": 0.0, "sched_host_s": 0.0}
    c.update(kw)
    return c


def test_values_are_window_differences():
    c0 = counters(pump_steps=100, prefill_chunks=40, steps_completed=10, decode_host_s=1.0,
                  prefill_host_s=0.5, transfer_host_s=2.0, sched_host_s=0.25)
    c1 = counters(pump_steps=150, prefill_chunks=60, steps_completed=14, decode_host_s=1.4,
                  prefill_host_s=0.56, transfer_host_s=2.1, sched_host_s=0.254)
    w = window(c0, c1)
    assert metric_reader("decode_host_ms").read(w) == pytest.approx(8.0)
    assert metric_reader("prefill_host_ms").read(w) == pytest.approx(3.0)
    assert metric_reader("prefill_host_ms.saturated").read(w) == pytest.approx(3.0)
    assert metric_reader("transfer_exposed_ms_per_turn").read(w) == pytest.approx(25.0)
    assert metric_reader("sched_ms_per_turn").read(w) == pytest.approx(1.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_a_zero_denominator(name):
    counter, denominator = READERS[name]
    c1 = counters(**{counter: 3.0, denominator: 7})
    assert metric_reader(name).read(window(dict(c1), c1)) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_none_on_an_absent_counter(name):
    """A program without the counter (an earlier commit) reads nothing and
    raises nothing."""
    counter, denominator = READERS[name]
    c0, c1 = counters(), counters(**{denominator: 7})
    del c0[counter], c1[counter]
    assert metric_reader(name).read(window(c0, c1)) is None
