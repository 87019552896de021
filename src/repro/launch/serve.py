"""Serving launcher: MORI router over DP replicas of the real JAX engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --replicas 2 --programs 8 --snapshot /tmp/mori_state.json

By default the config is ``.reduced()`` (d_model 256) with fixed small
engine sizes, which runs on a CPU. ``--published`` builds the config at
its published widths and sizes each engine from its accelerator: the
device-page count is the largest pool whose compiled decode and
chunk-prefill steps fit the device's HBM (:func:`size_engine`). One
replica per device; a fleet with fewer accelerators than replicas is an
error. ``--snapshot`` persists the control plane each run; ``--resume``
restores it first (programs re-enter via the Waiting queue — MORI's
recompute path doubles as crash recovery).
"""
from __future__ import annotations

import argparse
import dataclasses
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax

from repro.configs import get_config
from repro.core.scheduler import SchedulerConfig
from repro.dist import make_replica_set
from repro.launch.compile_cache import init_compile_cache
from repro.models import NULL_CTX, Model, materialize
from repro.models.config import ModelConfig
from repro.serving import Engine, MoriRouter
from repro.serving.engine import (
    _chunk_prefill_fn,
    chunk_step_args,
    decode_step_args,
    paged_decode_jit,
)
from repro.serving.state_io import restore_snapshot, save_snapshot
from repro.traces import TraceGenConfig, generate_corpus

#: share of the device's HBM (``memory_stats()["bytes_limit"]``) kept out
#: of the pool: eager page copies outside the compiled steps, the
#: sampler's small arrays, allocator fragmentation
HBM_MARGIN = 0.10


@dataclasses.dataclass(frozen=True)
class EngineSizes:
    """Everything an :class:`Engine` is sized by. ``n_device_pages`` is
    the radix cache; the engine adds its decode reserve on top."""

    page_tokens: int
    n_device_pages: int
    n_host_pages: int
    max_slots: int
    max_seq: int
    table_bucket_pages: int
    prefill_chunk_tokens: int
    prefill_bucket_tokens: int

    @property
    def decode_reserve_pages(self) -> int:
        return self.max_slots * (-(-self.max_seq // self.page_tokens) + 1)


#: CPU-sized engines for the reduced config
REDUCED_SIZES = EngineSizes(
    page_tokens=16, n_device_pages=72, n_host_pages=160, max_slots=3,
    max_seq=384, table_bucket_pages=4, prefill_chunk_tokens=64,
    prefill_bucket_tokens=32,
)
#: published-width engines before the pool is sized. 2048-token contexts
#: in 32-page table buckets: warmup compiles 4 decode shapes and 5
#: chunk-prefill shapes (one 256-token chunk bucket per prefix bucket);
#: 16 slots reserve 16 x 129 pages of the pool for decode state.
PUBLISHED_SIZES = EngineSizes(
    page_tokens=16, n_device_pages=0, n_host_pages=0, max_slots=16,
    max_seq=2048, table_bucket_pages=32, prefill_chunk_tokens=256,
    prefill_bucket_tokens=256,
)


def build_config(arch: str, published: bool) -> ModelConfig:
    cfg = get_config(arch)
    return cfg if published else cfg.reduced()


def _step_bytes(compiled) -> int:
    """Device bytes a compiled step needs while it runs: its arguments
    (weights and pool included), its temporaries, and whatever output
    does not reuse a donated argument."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def size_engine(cfg: ModelConfig, device, bytes_limit: int | None = None):
    """Size the device pool of one :data:`PUBLISHED_SIZES` replica on
    ``device`` from compiled steps: each step's footprint is linear in the
    pool's page count, so two abstract compiles per step (decode at the
    widest table bucket, chunk prefill at the widest prefix bucket) give
    its line, and the pool is the largest page count under
    ``(1 - HBM_MARGIN)`` of the device's ``bytes_limit`` (read from
    ``device.memory_stats()`` unless given, as it must be for a described,
    unattached chip). The host tier holds as many pages as the whole
    device pool, so everything the cache spills has a host page.

    Returns ``(sizes, report)``; ``report`` holds the budget, each step's
    bytes per page and its predicted footprint at the chosen size."""
    limit = int(bytes_limit or device.memory_stats()["bytes_limit"])
    budget = int(limit * (1 - HBM_MARGIN))
    sharding = jax.sharding.SingleDeviceSharding(device)
    base = PUBLISHED_SIZES
    T = base.page_tokens
    widest = -(-base.max_seq // T)
    widest = -(-widest // base.table_bucket_pages) * base.table_bucket_pages
    decode = paged_decode_jit(Model(cfg), NULL_CTX)
    chunk = _chunk_prefill_fn(cfg)

    def footprint(n: int) -> dict[str, int]:
        d = decode.lower(*decode_step_args(
            cfg, n_pages=n, page_tokens=T, max_slots=base.max_slots,
            table_pages=widest, sharding=sharding,
        )).compile()
        c = chunk.lower(*chunk_step_args(
            cfg, n_pages=n, page_tokens=T, prefix_pages=widest,
            chunk_tokens=base.prefill_bucket_tokens, sharding=sharding,
        )).compile()
        return {"decode": _step_bytes(d), "chunk_prefill": _step_bytes(c)}

    n0, n1 = 256, 512                               # two probe pool sizes
    f0, f1 = footprint(n0), footprint(n1)
    lines = {}                                      # step -> (bytes/page, fixed)
    for k in f0:
        per = (f1[k] - f0[k]) / (n1 - n0)
        lines[k] = (per, f0[k] - per * n0)
    total = min(int((budget - fixed) // per) for per, fixed in lines.values())
    cache = total - base.decode_reserve_pages
    if cache < base.max_slots:
        raise RuntimeError(
            f"{cfg.name} leaves no radix cache on {device.device_kind}: "
            f"{total} pages fit {budget} bytes, the decode reserve takes "
            f"{base.decode_reserve_pages}"
        )
    sizes = dataclasses.replace(base, n_device_pages=cache, n_host_pages=total)
    report = {
        "bytes_limit": limit,
        "budget": budget,
        "pool_pages": total,
        "step_bytes": {
            k: int(fixed + per * total) for k, (per, fixed) in lines.items()
        },
        "bytes_per_page": {k: per for k, (per, _) in lines.items()},
    }
    return sizes, report


def build_engines(cfg: ModelConfig, params, n_replicas: int,
                  sizes: EngineSizes) -> list[Engine]:
    """One engine per replica, each on its own devices. One rules object
    is shared by all replicas (repro.dist invariant): a program migrated
    between replicas lands on a byte-identical layout."""
    replica_set = make_replica_set(n_replicas, num_kv_heads=cfg.num_kv_heads)
    engines = []
    for placement in replica_set:
        print(f"replica {placement.replica_id}: "
              f"{[str(d) for d in placement.mesh.devices.flat]}")
        engines.append(Engine(
            cfg, params, page_tokens=sizes.page_tokens,
            n_device_pages=sizes.n_device_pages,
            n_host_pages=sizes.n_host_pages, max_slots=sizes.max_slots,
            max_seq=sizes.max_seq, placement=placement,
            table_bucket_pages=sizes.table_bucket_pages,
            prefill_chunk_tokens=sizes.prefill_chunk_tokens,
            prefill_bucket_tokens=sizes.prefill_bucket_tokens,
        ))
    return engines


def warmup(engines: list[Engine]) -> None:
    """Compile every decode and chunk-prefill shape of every replica, one
    thread per replica: each replica's executables are its own (one
    device assignment each), and XLA compiles them in parallel."""
    with ThreadPoolExecutor(len(engines)) as pool:
        list(pool.map(lambda e: e.warmup(prefill_chunks=True), engines))


def build_router(engines: list[Engine], scheduler: str = "mori",
                 gpu_pages: int | None = None,
                 cpu_pages: int | None = None) -> MoriRouter:
    """The MORI router over ``engines``, admitting through chunked prefill
    (the warmed, bucketed submit path). The scheduler's per-replica tier
    budgets default to the pool's radix cache and host tier."""
    pool = engines[0].pool
    return MoriRouter(
        engines,
        scheduler=scheduler,
        gpu_capacity_bytes=(
            None if gpu_pages is None else pool.page_bytes * gpu_pages  # lint: kv008-ok (GPU budget at device format)
        ),
        cpu_capacity_bytes=(
            None if cpu_pages is None else pool.host_page_bytes * cpu_pages
        ),
        config=SchedulerConfig(tick_interval_s=1.0),
        chunked_prefill=True,
    )


def build_serving(arch: str, *, published: bool, replicas: int,
                  scheduler: str = "mori", gpu_pages: int | None = None,
                  cpu_pages: int | None = None):
    """Config, weights (random, from seed 0), sized and warmed engines,
    and the router. Returns ``(cfg, router, report)``; ``report`` is the
    sizing report (``None`` for the fixed reduced sizes)."""
    cfg = build_config(arch, published)
    params = materialize(Model(cfg).describe(), seed=0)
    sizes, report = REDUCED_SIZES, None
    if published:
        sizes, report = size_engine(cfg, jax.devices()[0])
    engines = build_engines(cfg, params, replicas, sizes)
    del params                      # each engine holds its replica's copy
    warmup(engines)
    return cfg, build_router(engines, scheduler, gpu_pages, cpu_pages), report


def main() -> None:
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--published", action="store_true",
                    help="published widths, engines sized from the "
                         "accelerator's HBM (default: reduced, CPU-sized)")
    ap.add_argument("--scheduler", default="mori",
                    choices=["mori", "ta+o", "ta", "smg"])
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--programs", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=4)
    ap.add_argument("--gpu-pages", type=int, default=0,
                    help="scheduler GPU budget in pages per replica "
                         "(default: the pool's radix cache)")
    ap.add_argument("--cpu-pages", type=int, default=0,
                    help="scheduler host budget in pages per replica "
                         "(default: the pool's host tier)")
    ap.add_argument("--snapshot", default="")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg, router, report = build_serving(
        args.arch, published=args.published, replicas=args.replicas,
        scheduler=args.scheduler, gpu_pages=args.gpu_pages or None,
        cpu_pages=args.cpu_pages or None,
    )
    if report is not None:
        print(f"pool sizing: {report}")
    if args.resume and args.snapshot and Path(args.snapshot).exists():
        counters = restore_snapshot(router, args.snapshot)
        print(f"resumed control plane: {counters}")

    corpus = generate_corpus(
        args.programs, seed=1,
        cfg=TraceGenConfig(
            min_steps=4, mean_steps=7, max_steps=9,
            initial_context_mean=900, max_context=2400,
            long_median_s=45.0, busy_calls_mean=3.0, idle_calls_mean=3.0,
        ),
    )
    print(f"serving {len(corpus)} programs on {args.replicas} replicas "
          f"({args.scheduler})")
    m = router.replay(corpus, vocab_size=cfg.vocab_size,
                      max_new_tokens=args.max_new_tokens)
    print(f"steps {m.steps_completed}  tokens {m.tokens_generated}  "
          f"hit {m.cache_hit_rate:.1%}  offl {m.offloaded_pages}  "
          f"reload {m.reloaded_pages}  gated {m.gated_events}")
    print(f"decode dispatches {m.pump_steps}  batch occupancy "
          f"{m.mean_batch_occupancy:.2f} (peak {m.peak_live_slots})  "
          f"slot wait {m.slot_wait_s:.1f}s  overlap steps "
          f"{m.overlap_decode_steps}")
    if args.snapshot:
        save_snapshot(router, args.snapshot)
        print(f"control plane snapshot -> {args.snapshot}")


if __name__ == "__main__":
    main()
