"""The trace reduction: on a constructed trace with known answers, and on
a small trace recorded on a TPU v5e by a traced run of this benchmark."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.reduce import WINDOW_SPAN, Trace, module_key, reduce

RECORDED = Path(__file__).resolve().parent / "data" / "v5e_trace_window.json"
MS = 1_000_000  # nanoseconds


def test_constructed_trace():
    tr = Trace(
        ops={"/device:TPU:0": [
            ("%fusion.1", 0 * MS, 30 * MS),     # starts before the window
            ("%paged_attention.6", 20 * MS, 40 * MS),   # overlaps fusion.1
            ("%copy.2", 60 * MS, 70 * MS),
            ("%fusion.1", 95 * MS, 130 * MS),   # ends after the window
        ]},
        modules={"/device:TPU:0": [
            ("jit__unknown(77)", 15 * MS, 40 * MS),
            ("jit__unknown(78)", 60 * MS, 70 * MS),
            ("jit__unknown(79)", 95 * MS, 130 * MS),   # not whole in the window
        ]},
        spans=[(WINDOW_SPAN, 10 * MS, 110 * MS), ("engine.step", 35 * MS, 58 * MS),
               ("pool.copy_page_to_host", 45 * MS, 55 * MS),
               ("router.apply_plan", 72 * MS, 90 * MS)],
    )
    r = reduce(tr)
    assert r.window_s == pytest.approx(0.1)
    # busy: [10, 40] + [60, 70] + [95, 110] = 55 ms
    assert r.busy_s == pytest.approx(0.055)
    assert r.op_s["%fusion.1"] == pytest.approx(0.020 + 0.015)
    assert r.op_s["%paged_attention.6"] == pytest.approx(0.020)
    assert [(m.key, m.ops) for m in r.modules] == [
        ("_unknown", {"paged_attention"}), ("_unknown", {"copy"})]
    assert r.modules[0].seconds == pytest.approx(0.025)
    # gaps, longest first: [70, 95] under apply_plan, [40, 60] under the
    # innermost span open at its middle (the page copy at 50 ms)
    assert r.idle_gaps == [("router.apply_plan", pytest.approx(0.025)),
                           ("pool.copy_page_to_host", pytest.approx(0.020))]
    assert r.top_ops(1) == [["%fusion.1", pytest.approx(0.035)]]


def test_no_window_or_no_device_reads_nothing():
    assert reduce(Trace(ops={"/device:TPU:0": [("a", 0, 1)]})) is None
    assert reduce(Trace(spans=[(WINDOW_SPAN, 0, 10)])) is None


def test_module_key():
    assert module_key("jit__paged_decode_impl(123456)") == "_paged_decode_impl"
    assert module_key("jit_step") == "step"


def test_recorded_v5e_trace():
    """0.3 s of a traced qwen1p5-0p5b agent-fits run with private prompts
    on one v5e: one decode step and one prefill chunk, both jitted partials."""
    r = reduce(Trace.from_json(RECORDED.read_text()))
    assert r.devices == 1
    assert r.window_s == pytest.approx(0.300018292)
    assert r.busy_s == pytest.approx(0.289375601)
    steps = [(m.seconds, "paged_attention" in m.ops) for m in r.modules
             if m.key == "_unknown"]
    assert steps == [(pytest.approx(0.165308993), True),
                     (pytest.approx(0.124060757), False)]
    assert r.idle_gaps[0] == ("engine.step", pytest.approx(0.003873416))
    total_gaps = sum(s for _, s in r.idle_gaps)
    assert total_gaps <= r.window_s - r.busy_s + 1e-9
    assert r.top_ops(1)[0][0].startswith("%")


def test_step_readers_on_the_recorded_trace():
    import json

    from bench.run import metric_reader
    from bench.window import Window

    cfg = json.loads((RECORDED.parents[2] / "configs" / "qwen1p5-0p5b.json").read_text())
    w = Window(t0=0.0, t1=1.0, setup_s=0.0, cfg=cfg, turns={}, counters0={},
               counters1={}, decode_steps=[], chunks=[], admitted=[],
               reduced=reduce(Trace.from_json(RECORDED.read_text())))
    assert metric_reader("decode_step_ms").read(w) == pytest.approx(165.308993)
    assert metric_reader("prefill_chunk_ms").read(w) == pytest.approx(124.060757)
    assert metric_reader("device_idle_share").read(w) == pytest.approx(
        100 * (1 - 0.289375601 / 0.300018292))
