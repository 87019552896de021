"""Bring-up check of the MORI serving path on TPU at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas, one process

One chip: builds qwen1.5-0.5b at its published widths (random weights from
a seed) through ``repro.launch.serve`` — one engine sized from the chip's
HBM, warmed, behind the MORI router — replays a seeded agent corpus whose
contexts overflow the radix cache, so pages are offloaded to the host tier
and reloaded, and checks the tokens served for a probe prompt against a
float32 forward of the same weights on the same chip.

``--chips 4``: four such replicas behind one router, a clean replay and a
replay that drains one replica mid-decode (zero tokens may be lost), and
the probe on every replica (identical tokens, checked against float32).

Every line but the last describes the run; the last is one JSON object
naming the device. The script fails, printing no such line, when JAX finds
no TPU or any phase fails. No number it prints is a speed result.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import init_compile_cache  # noqa: E402

ARCH = "qwen1.5-0.5b"
#: the agent corpus: first contexts ~48 tokens, then tool results large
#: enough that each program's context reaches the engine's max_seq within
#: four steps (the replay scales every trace to a 48-token first step)
PROGRAMS = {1: 32, 4: 12}
MAX_NEW_TOKENS = 4
#: drain replica 1 while every program's first step is still decoding
FAIL_AT_S, RECOVER_AT_S = 0.5, 20.0
#: probe: prompt tokens, tokens served, padded length of the reference
PROBE_PROMPT, PROBE_NEW, PROBE_PAD = 200, 16, 256
#: logit gap (top-1 minus top-2 of the float32 reference) above which the
#: served token must be the reference's top-1. Random weights give logits
#: of unit scale; 0.125 is 4 bf16 ulps at magnitude 4-8, and the bf16
#: forward's own error is printed beside it.
MARGIN_BOUND = 0.125
#: steps of the probe that must clear MARGIN_BOUND for the check to count
MIN_COMPARED = 4


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def smoke_corpus(n_programs: int):
    from repro.traces import TraceGenConfig, generate_corpus

    return generate_corpus(n_programs, seed=7, cfg=TraceGenConfig(
        min_steps=4, mean_steps=4, max_steps=4,
        initial_context_mean=2000, max_context=1_000_000,
        short_result_tokens=(30_000, 40_000),
        long_result_tokens=(30_000, 40_000),
    ))


def serve_probe(engine, prompt: list[int]) -> list[int]:
    """Greedy tokens for ``prompt`` through the engine's own chunked
    prefill and paged decode (the warmed serving shapes)."""
    from repro.serving import EngineRequest

    job = engine.begin_submit(
        EngineRequest("probe", list(prompt), max_new_tokens=PROBE_NEW)
    )
    while not engine.prefill_step(job):
        pass
    done = engine.run_to_completion()
    require(len(done) == 1, "probe did not complete")
    return list(done[0].output_tokens)


def check_probe(cfg, params, prompt: list[int], served: list[int]) -> dict:
    """Teacher-forced reference: at step i, ``Model.prefill`` in float32
    (``default_matmul_precision("highest")``) over prompt + served[:i].
    Where the reference's top-1/top-2 gap exceeds MARGIN_BOUND the served
    token must be its top-1; below it (a near tie) the served token must
    be within MARGIN_BOUND of the top-1. The same forward with the bf16
    serving weights gives the max logit error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Model

    model = Model(cfg)

    @jax.jit
    def logits_at(p, tokens, idx):
        return model.prefill(p, {"tokens": tokens}, logit_index=idx)[0][0]

    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seq = np.zeros((1, PROBE_PAD), np.int32)
    seq[0, :PROBE_PROMPT] = prompt
    seq[0, PROBE_PROMPT:PROBE_PROMPT + PROBE_NEW - 1] = served[:-1]
    tokens = jnp.asarray(seq)
    compared, near_ties, max_err, worst_gap = 0, 0, 0.0, None
    with jax.default_matmul_precision("highest"):
        for i, tok in enumerate(served):
            idx = jnp.int32(PROBE_PROMPT - 1 + i)
            ref = np.asarray(logits_at(p32, tokens, idx), np.float64)
            bf = np.asarray(logits_at(params, tokens, idx), np.float64)
            max_err = max(max_err, float(np.abs(bf - ref).max()))
            top2 = np.sort(ref)[-2:]
            gap = float(top2[1] - top2[0])
            if gap > MARGIN_BOUND:
                require(tok == int(ref.argmax()),
                        f"probe step {i}: served {tok}, float32 top-1 "
                        f"{int(ref.argmax())} with gap {gap:.4f}")
                compared += 1
            else:
                below = float(ref.max() - ref[tok])
                require(below <= MARGIN_BOUND,
                        f"probe step {i}: served {tok} is {below:.4f} below "
                        "the float32 top-1 at a near tie")
                near_ties += 1
            worst_gap = gap if worst_gap is None else min(worst_gap, gap)
    require(compared >= MIN_COMPARED,
            f"only {compared} probe steps clear the {MARGIN_BOUND} margin")
    return {"compared_steps": compared, "near_tie_steps": near_ties,
            "max_logit_error_bf16_vs_f32": max_err,
            "smallest_gap": worst_gap}


def step_memory(engine) -> dict:
    """memory_analysis() of the engine's own decode step and chunk-prefill
    step, lowered at their widest warmup shapes, and whether the Pallas
    paged-attention kernel is in the compiled decode step."""
    specs = engine.warmup_specs(prefill_chunks=True)
    out = {}
    for kind in ("paged_decode", "chunk_prefill"):
        spec = [s for s in specs if s.kind == kind][-1]
        compiled = getattr(engine, spec.fn_name).lower(
            *spec.make_args()
        ).compile()
        m = compiled.memory_analysis()
        out[kind] = {
            "shape": spec.name,
            "argument_bytes": m.argument_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
        }
        if kind == "paged_decode":
            out["kernel_in_decode_step"] = "tpu_custom_call" in compiled.as_text()
    return out


def lost_tokens(clean: dict, faulted: dict) -> int:
    """Tokens of the clean replay that the drained replay dropped or
    changed."""
    return sum(
        len(toks) - sum(a == b for a, b in zip(toks, faulted.get(pid, [])))
        for pid, toks in clean.items()
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    cache_dir = init_compile_cache()
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    require(len(jax.devices()) >= args.chips,
            f"--chips {args.chips} but JAX sees {len(jax.devices())} devices")

    # arm the recompile budget before the engines exist (they register
    # their hot-path jits at construction when it is armed)
    os.environ["REPRO_JITAUDIT"] = "1"
    from repro.analysis import compile_tracker
    from repro.launch import serve

    tracker = compile_tracker.get_tracker()
    cache_hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None
    )

    def compile_s(phase: str) -> float:
        return sum(e.duration_s for e in tracker.events_in(phase))

    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")
    t0 = time.perf_counter()
    with tracker.phase("build"):
        cfg, router, sizing = serve.build_serving(
            ARCH, published=True, replicas=args.chips
        )
    build_s = time.perf_counter() - t0
    engines = router.engines
    eng = engines[0]
    pool = eng.pool
    print(f"config {cfg.name}: layers {cfg.num_layers}, d_model {cfg.d_model}, "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} kv, head_dim "
          f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"qkv_bias {cfg.qkv_bias}")
    print(f"pool sizing: bytes_limit {sizing['bytes_limit']}, budget "
          f"{sizing['budget']}, predicted step bytes {sizing['step_bytes']}, "
          f"bytes/page {sizing['bytes_per_page']}")
    device_bytes = pool.n_device_pages * pool.page_bytes
    print(f"pool: {pool.n_device_pages} device pages ({gb(device_bytes)}; "
          f"radix cache {eng.radix_device_pages}, decode reserve "
          f"{eng.decode_reserve_pages}), {pool.n_host_pages} host pages "
          f"({gb(pool.n_host_pages * pool.host_page_bytes)}), "
          f"{pool.page_bytes} bytes/page; max_slots {eng.max_slots}, "
          f"max_seq {eng.max_seq}")
    for i, e in enumerate(engines):
        weights = {
            d for a in jax.tree.leaves(e.params) for d in a.sharding.device_set
        }
        pools = e.pool.k.sharding.device_set | e.pool.v.sharding.device_set
        print(f"replica {i}: weights on {sorted(map(str, weights))}, pool on "
              f"{sorted(map(str, pools))}")
        require(weights == pools and len(weights) == 1,
                f"replica {i} is spread over {weights | pools}")
    replica_devices = [
        next(iter(e.pool.k.sharding.device_set)) for e in engines
    ]
    require(len(set(replica_devices)) == len(engines),
            f"replicas share devices: {replica_devices}")
    warm = tracker.cache_sizes()
    print(f"build (sizing + warmup): {build_s:.1f} s wall, "
          f"{compile_s('build'):.1f} s compiling "
          f"({len(tracker.events_in('build'))} compiles, {len(cache_hits)} "
          f"persistent-cache hits); warm jit caches {warm}")

    with tracker.phase("aot"):
        mem = step_memory(eng)
    print(f"decode step memory_analysis: {mem['paged_decode']}")
    print(f"chunk-prefill step memory_analysis: {mem['chunk_prefill']}")
    print(f"tpu_custom_call in the engine's compiled decode step: "
          f"{mem['kernel_in_decode_step']}")
    require(mem["kernel_in_decode_step"],
            "the Pallas paged-attention kernel is not in the decode step")
    for kind in ("paged_decode", "chunk_prefill"):
        need = mem[kind]["argument_bytes"] + mem[kind]["temp_bytes"]
        require(need <= sizing["bytes_limit"],
                f"{kind} needs {need} bytes, the chip has "
                f"{sizing['bytes_limit']}")

    corpus = smoke_corpus(PROGRAMS[args.chips])
    n_steps = sum(len(t.steps) for t in corpus)
    replays = [("clean", None)]
    if args.chips > 1:
        from repro.sim.engine import FaultPlan

        replays.append(("drain replica 1", [FaultPlan(
            replica=1, fail_at=FAIL_AT_S, recover_at=RECOVER_AT_S
        )]))
    logs = {}
    for label, faults in replays:
        if faults is not None:
            router = serve.build_router(engines)
        t0 = time.perf_counter()
        with tracker.phase("replay"):
            m = router.replay(corpus, vocab_size=cfg.vocab_size,
                              max_new_tokens=MAX_NEW_TOKENS, faults=faults)
        wall = time.perf_counter() - t0
        logs[label] = router.output_log
        print(f"replay ({label}): {len(corpus)} programs, {m.steps_completed}"
              f"/{n_steps} requests answered, {m.tokens_generated} tokens, "
              f"{wall:.2f} s wall; cached {m.cached_tokens} / prefilled "
              f"{m.prefilled_tokens} tokens, offloaded {m.offloaded_pages} "
              f"pages, reloaded {m.reloaded_pages} pages, {m.pump_steps} "
              f"decode steps (occupancy {m.mean_batch_occupancy:.2f}), "
              f"{m.prefill_chunks} prefill chunks, drains {m.drain_events}, "
              f"requeued {m.requeued_slots}, placement {m.placement_reasons}")
        require(m.steps_completed == n_steps, "requests went unanswered")
        require(m.tokens_generated == n_steps * MAX_NEW_TOKENS,
                "tokens went missing")
        if args.chips == 1:
            require(m.offloaded_pages > 0 and m.reloaded_pages > 0,
                    "the replay never offloaded and reloaded pages")
        if faults is not None:
            require(m.drain_events == 1 and m.requeued_slots > 0,
                    "the drain caught no replica mid-decode")
    grew = tracker.post_warmup_compiles()
    print(f"hot-path compiles after warmup: {sum(c - w for w, c in grew.values())}"
          f" {grew}; backend compiles during replay: "
          f"{len(tracker.events_in('replay'))} ({compile_s('replay'):.2f} s, "
          "eager page copies and samplers)")
    require(not grew, f"hot-path jits compiled after warmup: {grew}")
    if args.chips > 1:
        lost = lost_tokens(logs["clean"], logs["drain replica 1"])
        print(f"drain: {lost} tokens lost against the clean replay")
        require(lost == 0, f"the drain lost {lost} tokens")

    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(2, cfg.vocab_size, PROBE_PROMPT)]
    with tracker.phase("check"):
        served = [serve_probe(e, prompt) for e in engines]
        digest = hashlib.sha256(json.dumps(served[0]).encode()).hexdigest()[:16]
        print(f"probe tokens (replica 0): {served[0]} sha256[:16] {digest}")
        require(all(s == served[0] for s in served),
                f"replicas served different probe tokens: {served}")
        ref = check_probe(cfg, eng.params, prompt, served[0])
    print(f"float32 reference check: {ref} (bound {MARGIN_BOUND})")
    peak = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in replica_devices}
    print(f"peak memory_stats: {peak}")
    phases = {p: round(compile_s(p), 2)
              for p in ("build", "aot", "replay", "check")}
    print(f"compile seconds by phase: {phases}; persistent-cache hits "
          f"{len(cache_hits)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
