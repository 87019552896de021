"""Metric arithmetic over a window: all samples, waiting turns, per-step tokens."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench.driver import TurnState
from bench.run import metric_reader
from bench.window import Window, percentile

CFG = json.loads((Path(__file__).resolve().parents[1] / "configs" / "qwen1p5-0p5b.json").read_text())


def window(turns, **kw) -> Window:
    c = {k: 0 for k in ("pump_steps", "sum_live_slots", "slot_wait_s", "cached_tokens",
                        "prefilled_tokens", "steps_completed", "offload_bytes",
                        "reload_bytes")}
    d = dict(t0=10.0, t1=20.0, setup_s=5.0, cfg=CFG, turns=turns, counters0=c,
             counters1=dict(c), decode_steps=[], chunks=[], admitted=[])
    d.update(kw)
    return Window(**d)


def turn(due, *tokens):
    return TurnState(due=due, prompt_len=100, asked=4, tokens=list(tokens))


def test_percentile_uses_every_sample():
    xs = list(range(101))
    assert percentile(xs, 50) == 50.0 and percentile(xs, 90) == 90.0
    assert percentile([], 50) is None
    assert percentile([1.0, 2.0], 95) == pytest.approx(1.95)


def test_ttft_counts_landed_and_waiting_turns_only():
    turns = {
        ("a", 0): turn(2.0, 5.0, 6.0),       # first token before the window
        ("b", 0): turn(9.0, 12.0, 13.0),     # due before, lands in the window
        ("c", 0): turn(15.0, 15.5),          # lands in the window
        ("d", 0): turn(8.0),                 # still waiting: age 12 s at t1
        ("e", 0): turn(19.0, 21.0),          # first token after the window
        ("f", 0): turn(25.0),                # due after the window
    }
    w = window(turns)
    assert sorted(w.ttft_samples()) == pytest.approx([0.5, 1.0, 3.0, 12.0])
    assert metric_reader("ttft_p50_s").read(w) == pytest.approx(2.0)
    assert metric_reader("ttft_p90_s").read(w) == pytest.approx(np.percentile([0.5, 1, 3, 12], 90))


def test_tokens_counted_at_the_step_that_made_them():
    turns = {("a", 0): turn(1.0, 9.0, 10.0, 10.5, 11.0),
             ("b", 0): turn(18.0, 19.5, 20.0, 20.5)}
    w = window(turns)
    assert w.tokens_in_window() == 5          # 10.0, 10.5, 11.0, 19.5, 20.0
    assert metric_reader("tokens_per_s").read(w) == pytest.approx(0.5)
    # a gap counts where its later token lands in the window
    assert sorted(w.token_gaps()) == pytest.approx([0.5, 0.5, 0.5, 1.0])


def test_counter_metrics_are_window_differences():
    c0 = {"pump_steps": 100, "sum_live_slots": 150, "slot_wait_s": 2.0,
          "cached_tokens": 1000, "prefilled_tokens": 500, "steps_completed": 10,
          "offload_bytes": 0, "reload_bytes": 0}
    c1 = {"pump_steps": 120, "sum_live_slots": 210, "slot_wait_s": 5.0,
          "cached_tokens": 1900, "prefilled_tokens": 600, "steps_completed": 14,
          "offload_bytes": 4_000_000, "reload_bytes": 2_000_000}
    w = window({}, counters0=c0, counters1=c1, admitted=[9.0, 11.0, 12.0, 19.0])
    assert metric_reader("batch_occupancy").read(w) == pytest.approx(3.0)
    assert metric_reader("slot_wait_s_per_turn").read(w) == pytest.approx(1.0)
    assert metric_reader("cache_hit_rate").read(w) == pytest.approx(90.0)
    assert metric_reader("kv_moved_mb_per_turn").read(w) == pytest.approx(1.5)


def test_trace_metrics_read_nothing_without_a_trace():
    w = window({})
    for name in ("decode_step_ms", "prefill_chunk_ms", "paged_attention_roofline",
                 "mfu", "device_idle_share"):
        assert metric_reader(name).read(w) is None


def test_model_flops_count_weights_and_context():
    d, L, f, v = 1024, 24, 2816, 151936
    per_token = 2 * (L * (2 * d * d + 2 * d * d + 3 * d * f) + d * v)
    w = window({}, decode_steps=[(11.0, 11.1, 3, 300)], chunks=[(12.0, 12.1, 4, 10)])
    attn = 4 * L * d
    want = 3 * per_token + attn * 300 + 4 * per_token + attn * (4 * 10 - 6)
    assert w.model_flops() == pytest.approx(want)
