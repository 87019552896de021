"""Program spans and host-time counters on the serving path.

A tiny ``Engine`` behind ``MoriRouter`` pumps decode steps, prefills in
chunks and moves pages while a ``jax.profiler`` trace is captured; the
capture must hold every program span under a name the benchmark's trace
reduction reads, with its metadata in the event stats, and the router's
host-time counters must read what the spans saw.
"""
from __future__ import annotations

import dataclasses
import subprocess
import sys
import types
from pathlib import Path

import pytest

from repro.analysis import spans
from repro.analysis.spans import HostTimes, region

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))     # the benchmark package (bench.reduce, bench.driver)

HOST_FIELDS = ("decode_host_s", "prefill_host_s", "transfer_host_s", "sched_host_s")

#: every span the program opens on this path
PROGRAM_SPANS = {
    "engine.decode_prep", "engine.decode_wait", "engine.decode_finish",
    "engine.admit", "engine.chunk", "engine.chunk_wait",
    "pool.copy_page_to_host", "pool.copy_page_to_device", "plane.advance",
    "router.apply_plan",
    "scheduler.program_arrived", "scheduler.request_arrived",
    "scheduler.request_completed", "scheduler.program_finished",
    "scheduler.tick", "scheduler.on_slot_freed", "scheduler.on_transfer_complete",
}


# ------------------------------------------------------------ the helper
def test_nested_region_time_is_not_counted_in_its_parent(monkeypatch):
    now = [0.0]
    monkeypatch.setattr(spans, "perf_counter", lambda: now[0])
    t = HostTimes()
    with region("test.outer", (t, "decode_host_s")):
        now[0] += 2.0
        with region("test.copy", (t, "transfer_host_s"), page=3):
            now[0] += 5.0
            with region("test.copy", (t, "transfer_host_s")):
                now[0] += 1.0
        with region("test.wait"):       # counted nowhere, still nested
            now[0] += 7.0
        now[0] += 3.0
    assert t.decode_host_s == 5.0
    assert t.transfer_host_s == 6.0
    assert t.prefill_host_s == t.sched_host_s == 0.0


def test_region_closes_on_an_exception():
    t = HostTimes()
    with pytest.raises(ValueError):
        with region("test.outer", (t, "sched_host_s")):
            raise ValueError
    assert t.sched_host_s > 0.0
    assert spans._tls.stack == []


def test_core_imports_no_jax():
    code = ("import sys, repro.core, repro.analysis.spans; "
            "sys.exit('jax' in sys.modules)")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": str(ROOT / "src"), "PATH": ""})


# ------------------------------------------------- the serving path, traced
def _corpus():
    from repro.core.types import ProgramTrace, RequestRecord

    # pbig's second turn overflows a ~130-token GPU tier while p1 sits in
    # a long tool call: p1 is offloaded, then reloaded for its next turn
    return [
        ProgramTrace("pbig", [
            RequestRecord(50, 4, 1.0, reasoning_wall_s=1.0),
            RequestRecord(120, 4, 3.0, reasoning_wall_s=1.0),
            RequestRecord(126, 4, 0.0, reasoning_wall_s=1.0),
        ]),
        ProgramTrace("p1", [
            RequestRecord(60, 4, 20.0, reasoning_wall_s=1.0),
            RequestRecord(76, 4, 0.0, reasoning_wall_s=1.0),
        ]),
    ]


def _router(cfg, params, *, gpu_tokens: int):
    from repro.core import SchedulerConfig
    from repro.kernels import kv_quant
    from repro.serving import Engine, MoriRouter

    kvb = kv_quant.token_wire_bytes(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, "bf16")
    engine = Engine(cfg, params, page_tokens=8, n_device_pages=256, n_host_pages=64,
                    max_slots=4, max_seq=256)
    return MoriRouter([engine], scheduler="mori", gpu_capacity_bytes=gpu_tokens * kvb,
                      config=SchedulerConfig(tick_interval_s=1.0),
                      chunked_prefill=True, prefill_token_budget=32)


@pytest.fixture(scope="module")
def model():
    from repro.configs import get_config
    from repro.models import Model, materialize

    cfg = get_config("qwen1.5-0.5b").reduced()
    return cfg, materialize(Model(cfg).describe(), seed=0)


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One replay that moves pages, under the profiler: the router, its
    metrics, the capture reduced by the benchmark, and each span's stats."""
    import jax
    from jax.profiler import ProfileData

    from bench.reduce import load

    cfg, params = model
    router = _router(cfg, params, gpu_tokens=130)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        m = router.replay(_corpus(), vocab_size=cfg.vocab_size, max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
    path = str(next(d.rglob("*.xplane.pb")))
    stats = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in PROGRAM_SPANS:
                        stats.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return types.SimpleNamespace(router=router, m=m, trace=load(path), stats=stats)


def test_every_program_span_is_in_the_capture(traced):
    from bench.reduce import _SPAN_NAME

    assert traced.m.offloaded_pages > 0 and traced.m.reloaded_pages > 0
    assert traced.m.prefill_chunks > traced.m.steps_completed   # several chunks a turn
    names = {n for n, _, _ in traced.trace.spans}
    assert PROGRAM_SPANS <= names
    assert all(_SPAN_NAME.match(n) for n in PROGRAM_SPANS)


def test_decode_step_spans_fall_in_order(traced):
    by_step = {}
    for name in ("engine.decode_prep", "engine.decode_wait", "engine.decode_finish"):
        for s, e, st in traced.stats[name]:
            by_step.setdefault(st["step"], {})[name] = (s, e)
    assert len(by_step) == traced.router.engines[0].steps
    for parts in by_step.values():
        prep, wait, fin = (parts[k] for k in ("engine.decode_prep", "engine.decode_wait",
                                               "engine.decode_finish"))
        assert prep[0] <= wait[0] <= wait[1] <= prep[1] <= fin[0] <= fin[1]
    rows = [st["rows"] for _, _, st in traced.stats["engine.decode_prep"]]
    assert sum(rows) == traced.m.sum_live_slots


def test_span_metadata_is_in_the_stats(traced):
    pids = {"pbig", "p1"}
    for name in ("engine.admit", "engine.chunk", "scheduler.request_arrived",
                 "scheduler.program_arrived"):
        assert {st["pid"] for _, _, st in traced.stats[name]} == pids, name
    assert all("page" in st for _, _, st in traced.stats["pool.copy_page_to_host"])
    tokens = sum(st["tokens"] for _, _, st in traced.stats["engine.chunk"])
    assert tokens == traced.m.prefilled_tokens


def test_counters_read_the_spans(traced):
    m = traced.m
    assert all(getattr(m, f) > 0.0 for f in HOST_FIELDS)
    # every layer counted into the router's metrics, none into its own
    r = traced.router
    eng = r.engines[0]
    for owner in (r.sched, eng, eng.pool, r.planes[0]):
        assert owner.host_times is m
    # the decode counter is the self time of the prep and finish spans
    # (less what ran nested in them), so it cannot exceed their sum
    spanned = sum(e - s for name in ("engine.decode_prep", "engine.decode_finish")
                  for s, e, _ in traced.stats[name]) / 1e9
    waited = sum(e - s for s, e, _ in traced.stats["engine.decode_wait"]) / 1e9
    assert 0.0 < m.decode_host_s <= spanned - waited + 1e-3


def test_transfer_time_counts_only_page_moves(model):
    cfg, params = model
    router = _router(cfg, params, gpu_tokens=10_000)
    m = router.replay(_corpus(), vocab_size=cfg.vocab_size, max_new_tokens=4)
    assert m.offloaded_pages == m.reloaded_pages == 0
    assert m.transfer_host_s == 0.0
    assert m.decode_host_s > 0.0 and m.sched_host_s > 0.0


def test_counters_are_in_the_benchmark_snapshot(traced):
    from bench.driver import Driver

    snap = Driver.snapshot(types.SimpleNamespace(router=traced.router))
    fields = {f.name for f in dataclasses.fields(traced.m)}
    assert set(HOST_FIELDS) <= set(snap) <= fields
    assert snap["transfer_host_s"] == traced.m.transfer_host_s > 0.0
