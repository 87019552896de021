"""One run of one benchmark cell, on the accelerator it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``bench/configs``) and
a traffic mix (``bench/traffic``). The run builds the program's serving
path from the configuration (``repro.launch.serve``: pool sized from the
chip, engines warmed, the MORI router), with weights drawn on the device
from the seed; brings the mix's session population to its steady state
(the ramp); measures ``--seconds`` of agent sessions on the wall clock
(:mod:`bench.driver`); then checks a seeded sample of the served tokens
against the float32 reference (:mod:`bench.check`).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window is profiled and they are its per-layer
metrics. The last line of standard output is the result as one JSON
object; the last lines of standard error are the compared numbers beside
their limits. Without a TPU, or with fewer chips than the cell asks for,
the run prints no result and exits with 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: sessions compared with the reference: at least this many served tokens,
#: at most this many sessions
CHECK_MIN_TOKENS = 300
CHECK_MAX_SESSIONS = 16


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench/run.py: no workload {name!r} in BENCHMARK.json")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``; a name ``<reader>.<cells>`` that has no
    file of its own is the same reading as ``<reader>`` in other cells,
    where it moves another end-to-end metric."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries this cell reports in this kind of run."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in bench[key] if cell in m.get("workloads", [cell])]


def _check_tree(params, mcfg) -> None:
    """The seeded weights must have exactly the program's layout."""
    import jax

    from repro.models import Model
    from repro.models.params import is_leaf

    want = jax.tree.map(lambda l: (tuple(l.shape), str(jax.numpy.dtype(l.dtype))),
                        Model(mcfg).describe(), is_leaf=is_leaf)
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != have:
        raise ValueError(f"seeded weights do not match the program's layout: "
                         f"{have} != {want}")


def _warm_page_copies(engine) -> None:
    """Run one page through the host tier and back, so the eager copies of
    the transfer path compile in set-up and not in the window."""
    pool = engine.pool
    page = engine._scratch_pages[0]
    hp = pool.copy_page_to_host(page)
    dp = pool.copy_page_to_device(hp)
    pool.free_host(hp)
    pool.free_device(dp)
    pool.block_table_view()[0].block_until_ready()


def run_cell(*, cfg: dict, mix: dict, limits: dict, metrics: list[dict],
             seed: int, seconds: float, trace: bool, devices: list,
             sizes=None, chip_peaks: dict | None = None, control: bool = False,
             t_start: float = T_START) -> tuple[dict, list[str]]:
    """Build, ramp, measure and check one cell; returns the result object
    and the lines of compared numbers. ``sizes`` and ``chip_peaks`` replace
    the chip's sizing and peak rates (CPU tests). With ``control`` the
    reference in fp8 takes the program's place in the comparison: the gap
    compared is that of the tokens the control puts first at every position
    of the program's sessions, under the same limit (``bench/control.py``)."""
    os.environ["REPRO_JITAUDIT"] = "1"
    import jax

    from bench import arch, check
    from bench.driver import Driver
    from bench.peaks import peaks
    from bench.reduce import WINDOW_SPAN, load, reduce
    from bench.sessions import Mix, Population
    from bench.window import Window
    from repro.analysis import compile_tracker
    from repro.launch import serve

    tracker = compile_tracker.get_tracker()
    dev = devices[0]
    adapter = arch.adapter(cfg)
    mcfg = adapter.model_config(cfg)
    with tracker.phase("setup"):
        params = adapter.params(cfg, seed, dev)
        _check_tree(params, mcfg)
        if sizes is None:
            sizes, _ = serve.size_engine(mcfg, dev)
        engines = serve.build_engines(mcfg, params, len(devices), sizes)
        del params
        serve.warmup(engines)
        [_warm_page_copies(e) for e in engines]
        router = serve.build_router(engines)
        mx = Mix.from_dict(mix)
        pop = Population(mx, sizes.max_seq, cfg["vocab_size"], seed)
        drv = Driver(router, pop, spans=trace)
        drv.start()
        drv.run(mx.ramp_s)
    setup_s = time.perf_counter() - t_start
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    c0, t0 = drv.snapshot(), drv.clock()
    with tracker.phase("window"), jax.profiler.TraceAnnotation(WINDOW_SPAN):
        drv.run(t0 + seconds)
    t1 = t0 + seconds
    jax.block_until_ready([e.pool.block_table_view() for e in engines])
    c1 = drv.snapshot()
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        xplanes = list(Path(tdir).rglob("*.xplane.pb"))
        reduced = reduce(load(str(xplanes[0]))) if xplanes else None
        shutil.rmtree(tdir, ignore_errors=True)
    in_window = len(tracker.events_in("window"))
    retraced = tracker.post_warmup_compiles()
    used = [next(iter(e.pool.k.sharding.device_set)) for e in engines]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)

    w = Window(t0=t0, t1=t1, setup_s=setup_s, cfg=cfg, turns=drv.turns,
               counters0=c0, counters1=c1, decode_steps=drv.decode_steps,
               chunks=drv.chunks, admitted=drv.admitted,
               peaks=chip_peaks or peaks(dev.device_kind), reduced=reduced)
    out_metrics = {}
    for m in metrics:
        v = metric_reader(m["name"]).read(w)
        if v is not None:
            out_metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    ttft = w.ttft_samples()
    late = [x for due, x in drv.lateness if w.inside(due)]
    short = drv.short_turns
    records = list(drv.records.values())
    notes = [
        f"window {seconds:.1f} s after a {setup_s:.1f} s set-up "
        f"(ramp {mx.ramp_s:.1f} s); turns: {len(ttft)} timed, "
        f"{int(w.delta('steps_completed'))} finished; tokens "
        f"{w.tokens_in_window()}; decode steps {int(w.delta('pump_steps'))}",
        f"driver lateness against due times in the window: n {len(late)}, "
        f"median {(sorted(late)[len(late) // 2] if late else 0):.6f} s, "
        f"max {(max(late) if late else 0):.6f} s",
    ] + [f"program departs from the configuration: {d}"
         for d in adapter.departures(cfg)]

    # the program's state goes before the reference runs on the chip
    del drv, router, engines, pop, w
    gc.collect()
    picked = check.sample(records, seed, CHECK_MIN_TOKENS, CHECK_MAX_SESSIONS)
    g = (check.gaps(cfg, seed, picked, pad_to=sizes.max_seq, control=control)
         if picked
         else {"widest_gap": float("inf"), "control_widest_gap": float("inf"),
               "compared_tokens": 0,
               "compared_sessions": 0, "reloaded_turns_compared": 0})
    gap = g["control_widest_gap"] if control else g["widest_gap"]
    compared = {
        "widest_gap": (gap, "<=", limits["widest_gap"]),
        "compared_tokens": (g["compared_tokens"], ">=", limits["compared_tokens"]),
        "short_turns": (short, "<=", 0),
        "hot_path_retraces": (sum(c - w_ for w_, c in retraced.values()), "<=", 0),
        "compiles_in_window": (in_window, "<=", 0),
    }
    correct = all((v <= lo) if op == "<=" else (v >= lo)
                  for v, op, lo in compared.values())
    notes.append(f"reference check: {g['compared_sessions']} sessions, "
                 f"{g['reloaded_turns_compared']} turns with reloaded pages")
    if control:
        notes.append(f"control: the fp8 reference in the program's place; the "
                     f"program's own widest_gap {g['widest_gap']}")
    lines = notes + [f"{k} {v} (limit {op} {lo})" for k, (v, op, lo) in compared.items()]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_mem)}
    result = {"correct": bool(correct), "attempted": len(ttft), "failed": short,
              "metrics": out_metrics, "device": device}
    if trace and reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10),
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps[:10]],
        }
    result["compared"] = {k: {"value": v, "limit": f"{op} {lo}"}
                          for k, (v, op, lo) in compared.items()}
    return result, lines


def main(argv: list[str] | None = None, control: bool = False) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    # the checkout's own compile cache, at a fixed path (the program's
    # init_compile_cache honours the variable)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    from repro.launch.compile_cache import init_compile_cache

    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench/run.py: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench/run.py: {args.workload} needs {cell['chips']} chips, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    result, lines = run_cell(
        cfg=load_json(BENCH / "configs" / f"{cell['config']}.json"),
        mix=load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        limits=load_json(BENCH / "limits" / f"{cell['config']}.json"),
        metrics=cell_metrics(bench, cell["name"], bool(args.trace)),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devs[: cell["chips"]], control=control,
    )
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
