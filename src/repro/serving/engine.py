"""A real (small-scale) JAX inference engine with paged KV + typed eviction.

This is the execution plane the MORI scheduler drives in the real system:

* paged two-tier KV storage (:class:`repro.serving.kvpool.PagePool`),
* RadixAttention-style prefix reuse via :class:`TypedRadixTree` — a new
  request whose prefix is cached skips prefill for those pages (chunked
  prefill over the radix prefix),
* **block-table decode** (default): the pool *is* the decode state.
  Continuous-batching decode runs the paged-attention kernel straight off
  the ``PagePool`` through per-slot block tables; each step appends the
  new token's KV into the slot's tail page in place. Prefill (``submit()``,
  or ``begin_submit()`` + ``prefill_step()`` in budgeted chunks) writes
  suffix KV directly into pool pages (cached prefix pages are
  *referenced*, never copied) and ``_finish`` hands the already-resident
  full pages to the radix tree — the dense-slot path's
  gather → concatenate → slot-write → write-back round trip is gone,
  and a program's KV never exists anywhere but the pool,
* ``dense_slots=True`` compatibility knob: the pre-block-table decode
  path (JetStream-style fixed slot buffers), kept token-identical to the
  paged path by a golden test and used as the benchmark baseline,
* engine-level eviction that follows the scheduler's typed labels
  (paper §4.3.2): GPU evicts inactive->idle->busy, host evicts
  inactive->busy->idle, LRU within type,
* program-level offload / reload / discard entry points used by the
  MORI router.

Scale note: this engine serves *reduced* configs end-to-end on CPU (tests,
examples). Paper-scale timing experiments live in ``repro.sim``; production
mesh lowering in ``repro.launch.dryrun``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import compile_tracker
from repro.analysis.spans import region
from repro.core.radix_tree import TypedRadixTree
from repro.core.types import Tier, TypeLabel
from repro.dist import ReplicaPlacement
from repro.models import NULL_CTX, Model, ShardCtx
from repro.models.config import ModelConfig
from repro.models.params import sharding_tree


@dataclass
class EngineRequest:
    program_id: str
    tokens: list[int]            # full accumulated context (token ids)
    max_new_tokens: int = 16


@dataclass
class Completion:
    program_id: str
    output_tokens: list[int]
    cached_tokens: int           # tokens served from the radix cache
    prefilled_tokens: int        # tokens actually prefilled
    reloaded_pages: int


@dataclass
class PrefillJob:
    """A resumable chunked prefill: the two-phase twin of ``submit``.

    ``Engine.begin_submit`` reserves the decode slot, enters the
    radix-matched prefix pages and stages the suffix pages; each
    ``Engine.prefill_step`` call then prefills one page-aligned,
    budget-bounded chunk into the staged pages. The decode pump runs
    chunks between decode steps so a long prefill never stalls the
    whole batch. ``first_token`` is set by the final chunk, at which
    point the job's slot is installed for decode.
    """

    request: EngineRequest
    slot_id: int
    suffix: list[int]            # tokens past the radix-cached prefix
    cached_tokens: int
    reloaded_pages: int
    prefix_pages: list[int]      # radix device pages (referenced, pinned)
    prefix_nodes: list
    new_pages: list[int]         # staged suffix pages (allocated up front)
    cursor: int = 0              # suffix tokens prefilled so far
    chunks_run: int = 0
    first_token: int | None = None
    kvsan_hold: int | None = None   # sanitizer hold token on new_pages

    @property
    def done(self) -> bool:
        return self.first_token is not None

    @property
    def remaining(self) -> int:
        return len(self.suffix) - self.cursor


@dataclass
class WarmupSpec:
    """One shape ``Engine.warmup`` precompiles — and, equivalently, one
    audit target for :mod:`repro.analysis.jitaudit`.

    ``make_args`` is lazy on purpose: the decode/chunk fns donate the
    pool arrays, so each spec must read ``pool.block_table_view()`` (or
    the dense slot buffers) *at call time*, after the previous spec's
    donation was re-adopted.  ``probe_group`` names the structural
    equivalence class: any two specs in a group must trace to the same
    primitive sequence (the jitaudit shape-branch probe pairs
    consecutive group members).
    """

    name: str
    kind: str                    # "dense" | "paged_decode" | "chunk_prefill"
    fn_name: str                 # engine attribute holding the jitted fn
    make_args: object            # () -> positional argument tuple
    donate_argnums: tuple = ()
    bucket: dict = field(default_factory=dict)
    probe_group: str = ""


def greedy_token(logits):
    """Deterministic greedy sampling shared by every sample site (dense
    decode, paged decode, monolithic and chunked prefill).

    The KV cache is bf16 while logits are f32, so two token-identical
    paths that materialize the context differently (dense slots vs paged
    gather, bf16 vs int8 pages) can produce logits differing by ~1 bf16
    ulp — enough to flip an f32 argmax between two near-tied candidates.
    Rounding the logits to bf16 first collapses those sub-ulp differences
    into *exact* ties, and ``jnp.argmax`` breaks exact ties by lowest
    index on every backend — so the sampled token is a deterministic
    function of the context, not of which code path computed it."""
    return jnp.argmax(
        logits.astype(jnp.bfloat16).astype(jnp.float32), axis=-1
    )


def _chunk_prefill_impl(model, ctx, params, k_pages, v_pages, prefix_idx,
                        write_idx, tokens, prefix_valid, pos0, take,
                        logit_idx):
    """One chunk of prefill, pool-in/pool-out (jit body; the pool is
    donated and carried through the layer scan, so each layer's page
    writes are in place). ``prefix_idx`` is padded to a page bucket
    (garbage tail masked via ``prefix_valid``); ``write_idx`` is padded
    with a scratch page; chunk KV past ``take`` is zeroed so the written
    tail page holds the same bytes however the suffix was chunked."""
    logits, k_pages, v_pages = model.prefill_paged(
        params, k_pages, v_pages, tokens, prefix_idx, write_idx,
        prefix_valid, pos0, take, logit_idx, ctx=ctx,
    )
    return logits[0], k_pages, v_pages


def _chunk_prefill_impl_q(model, ctx, params, k_pages, v_pages, k_scale,
                          v_scale, prefix_idx, write_idx, tokens,
                          prefix_valid, pos0, take, logit_idx):
    """Int8-resident twin of :func:`_chunk_prefill_impl`: the prefix gather
    dequantizes through the scale sidecars and the chunk write quantizes
    each written page (payload + sidecar updated together, all donated)."""
    logits, k_pages, v_pages, k_scale, v_scale = model.prefill_paged(
        params, k_pages, v_pages, tokens, prefix_idx, write_idx,
        prefix_valid, pos0, take, logit_idx, k_scale, v_scale, ctx=ctx,
    )
    return logits[0], k_pages, v_pages, k_scale, v_scale


def _paged_decode_impl(model, ctx, params, k_pages, v_pages, tokens, lengths,
                       tables, tail_pages, tail_offsets):
    """One block-table decode step, pool-in/pool-out (jit body; the engine
    donates the pool so the tail-page append is an in-place update)."""
    logits, k_pages, v_pages = model.decode_paged(
        params, k_pages, v_pages, tokens, lengths, tables,
        tail_pages, tail_offsets, ctx=ctx,
    )
    return greedy_token(logits), k_pages, v_pages


def _paged_decode_impl_q(model, ctx, params, k_pages, v_pages, k_scale,
                         v_scale, tokens, lengths, tables, tail_pages,
                         tail_offsets):
    """Int8-resident decode step: scale sidecars ride in and out (the
    tail-page requantize may grow them)."""
    logits, k_pages, v_pages, k_scale, v_scale = model.decode_paged(
        params, k_pages, v_pages, tokens, lengths, tables,
        tail_pages, tail_offsets, k_scale, v_scale, ctx=ctx,
    )
    return greedy_token(logits), k_pages, v_pages, k_scale, v_scale


def paged_decode_jit(model, ctx, quantized: bool = False):
    """The engine's jitted decode step (pool donated)."""
    if quantized:
        return jax.jit(
            functools.partial(_paged_decode_impl_q, model, ctx),
            donate_argnums=(1, 2, 3, 4),
        )
    return jax.jit(
        functools.partial(_paged_decode_impl, model, ctx), donate_argnums=(1, 2)
    )


def _abstract_weights_and_pool(cfg: ModelConfig, n_pages: int,
                               page_tokens: int, sharding):
    from repro.models.params import is_leaf

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(
        lambda l: sds(l.shape, l.dtype), Model(cfg).describe(), is_leaf=is_leaf
    )
    pool = sds((cfg.num_layers, n_pages, page_tokens,
                cfg.num_kv_heads * cfg.head_dim), jnp.bfloat16)
    return params, pool, sds


def decode_step_args(cfg: ModelConfig, *, n_pages: int, page_tokens: int,
                     max_slots: int, table_pages: int, sharding=None) -> tuple:
    """Abstract arguments of one bf16 paged decode step: the weights, a pool
    of ``n_pages`` pages and one table bucket. Lowering the step with them
    needs no device memory, so a pool size can be checked against HBM
    before the pool exists (and compiled for a chip that is not attached)."""
    params, pool, sds = _abstract_weights_and_pool(
        cfg, n_pages, page_tokens, sharding
    )
    rows = sds((max_slots,))
    return (params, pool, pool, rows, rows, sds((max_slots, table_pages)),
            rows, rows)


def chunk_step_args(cfg: ModelConfig, *, n_pages: int, page_tokens: int,
                    prefix_pages: int, chunk_tokens: int,
                    sharding=None) -> tuple:
    """Abstract arguments of one bf16 chunk-prefill step (the twin of
    :func:`decode_step_args`) at one (prefix-page, chunk) bucket."""
    params, pool, sds = _abstract_weights_and_pool(
        cfg, n_pages, page_tokens, sharding
    )
    scalar = sds(())
    return (params, pool, pool, sds((prefix_pages,)),
            sds((-(-chunk_tokens // page_tokens),)), sds((1, chunk_tokens)),
            scalar, scalar, scalar, scalar)


@functools.lru_cache(maxsize=None)
def _chunk_prefill_fn(cfg: ModelConfig, quantized: bool = False):
    """Process-global jitted chunk prefill, keyed on the (hashable) model
    config and the pool's device format. Sharing the jit cache across
    Engine instances is the point: chunk shapes are bucketed, so every
    engine in the process reuses the same few compiles instead of paying a
    fresh trace per submit the way monolithic variable-shape prefill
    does."""
    model = Model(cfg)
    if quantized:
        fn = functools.partial(_chunk_prefill_impl_q, model, NULL_CTX)
        return jax.jit(fn, donate_argnums=(1, 2, 3, 4))
    fn = functools.partial(_chunk_prefill_impl, model, NULL_CTX)
    return jax.jit(fn, donate_argnums=(1, 2))


#: per-process engine ids for compile-tracker names (stable within a run)
_ENGINE_IDS = iter(range(1 << 30))


@dataclass
class _Slot:
    request: EngineRequest
    slot_id: int
    length: int                  # current context length (incl. generated)
    produced: list[int] = field(default_factory=list)
    cached_tokens: int = 0
    prefilled_tokens: int = 0
    reloaded_pages: int = 0
    # block-table decode state (paged mode): page ids covering positions
    # [i*T, (i+1)*T); entries below ``owned_from`` are shared radix pages
    # (read-only, pinned), entries from ``owned_from`` on are slot-owned
    table: list[int] = field(default_factory=list)
    owned_from: int = 0
    # the radix nodes backing table[:owned_from] — refcount-held for the
    # slot's lifetime so eviction/offload can never recycle a device page
    # a live block table still points at (they may belong to ANOTHER
    # program sharing the prefix, which tree.pin(pid) does not cover)
    prefix_nodes: list = field(default_factory=list)


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        page_tokens: int = 16,
        n_device_pages: int = 256,
        n_host_pages: int = 256,
        max_slots: int = 4,
        max_seq: int = 512,
        placement: ReplicaPlacement | None = None,
        dense_slots: bool = False,
        table_bucket_pages: int = 4,
        prefill_bucket_tokens: int = 32,
        prefill_chunk_tokens: int = 64,
        offload_format: str = "bf16",
        device_format: str = "bf16",
    ):
        assert cfg.family in ("dense", "moe", "vlm") and not cfg.local_global_alternating, (
            "the real engine serves dense-cache families; see DESIGN.md"
        )
        assert not (dense_slots and device_format == "int8"), (
            "device_format='int8' packs the paged pool; the dense-slot "
            "compatibility path has no page-granular scale sidecars"
        )
        self.cfg = cfg
        self.model = Model(cfg)
        self.placement = placement
        if placement is not None:
            # pin the replica's weight copy to its mesh slice under the
            # shared rules so every replica compiles identical layouts
            self.ctx = ShardCtx(placement.mesh, placement.rules)
            p_sh = sharding_tree(
                self.model.describe(), placement.mesh, placement.rules
            )
            params = jax.tree.map(jax.device_put, params, p_sh)
        else:
            self.ctx = NULL_CTX
        self.params = params
        self.page_tokens = page_tokens
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.dense_slots = dense_slots
        # suffix prefill pads to this bucket so jit compiles once per bucket
        # (not once per context length); causality keeps outputs identical
        self.prefill_bucket = max(1, prefill_bucket_tokens)
        # default per-call token budget for prefill_step (page-aligned there)
        self.prefill_chunk_tokens = max(1, prefill_chunk_tokens)
        self.pages_per_slot = -(-max_seq // page_tokens)
        # Paged mode stores decode state IN the pool, so the device pool is
        # provisioned with the HBM the dense slot buffers used to occupy:
        # pages_per_slot per slot plus one scratch page per slot (inactive
        # batch rows write their garbage token there, mirroring the dense
        # path's harmless writes into unused slot rows). The reserve is
        # excluded from the router's radix-capacity accounting via
        # ``decode_reserve_pages``.
        self.decode_reserve_pages = (
            0 if dense_slots else max_slots * (self.pages_per_slot + 1)
        )
        self.radix_device_pages = n_device_pages  # cache budget (sans reserve)
        from repro.serving.kvpool import PagePool

        self.pool = PagePool(
            layers=cfg.num_layers,
            kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim,
            page_tokens=page_tokens,
            n_device_pages=n_device_pages + self.decode_reserve_pages,
            n_host_pages=n_host_pages,
            offload_format=offload_format,
            device_format=device_format,
            # the pool lives on the replica's own devices, beside its
            # weights (default placement would put every pool on device 0)
            sharding=(
                None if placement is None
                else jax.sharding.NamedSharding(
                    placement.mesh, jax.sharding.PartitionSpec()
                )
            ),
        )
        self.quantized = self.pool.quantized_device
        # host-time counters (the router replaces them with its metrics)
        self.host_times = self.pool.host_times
        self.tree = TypedRadixTree(page_tokens)
        if self.pool._san is not None:
            # give the sanitizer the node graph (pin checks) and the live
            # block-table / scratch references (hold + leak checks)
            self.pool._san.tree = self.tree
            self.pool._san.add_reachable_cb(self._kvsan_reachable)
        self.lengths = np.zeros(max_slots, np.int32)
        self.last_token = np.zeros(max_slots, np.int32)
        # token whose KV currently occupies position lengths[sid]-1 — what a
        # step NOT advancing this slot must re-feed so its row's write is an
        # idempotent rewrite of the existing tail KV (last_token's KV is not
        # written yet; feeding it unpaced would corrupt the tail position)
        self._tail_token = np.zeros(max_slots, np.int32)
        self.slots: dict[int, _Slot] = {}
        self._free_slots = list(range(max_slots))
        if dense_slots:
            L, KH, HD = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
            self.slot_k = jnp.zeros((L, max_slots, max_seq, KH, HD), jnp.bfloat16)
            self.slot_v = jnp.zeros_like(self.slot_k)
            self._decode_fn = jax.jit(self._decode_impl, donate_argnums=(1, 2))
        else:
            self._scratch_pages = [
                self.pool.alloc_device() for _ in range(max_slots)
            ]
            self._table_bucket = table_bucket_pages
            # an int8 step rewrites tail-page scales alongside the payload,
            # so the sidecars are donated (and re-adopted) too
            self._paged_decode_fn = paged_decode_jit(
                self.model, self.ctx, self.quantized
            )
            # chunked prefill: the process-global callable shares compiles
            # across engines; placement engines need their own ShardCtx
            if placement is None:
                self._chunk_fn = _chunk_prefill_fn(cfg, self.quantized)
            elif self.quantized:
                self._chunk_fn = jax.jit(
                    functools.partial(_chunk_prefill_impl_q, self.model, self.ctx),
                    donate_argnums=(1, 2, 3, 4),
                )
            else:
                self._chunk_fn = jax.jit(
                    functools.partial(_chunk_prefill_impl, self.model, self.ctx),
                    donate_argnums=(1, 2),
                )
        # metrics
        self.steps = 0
        self.evicted_pages = {"gpu": 0, "cpu": 0}
        # compile tracker (REPRO_JITAUDIT=1 only): register the hot-path
        # jits so post-warmup recompiles are attributable and gateable
        self._audit_id = next(_ENGINE_IDS)
        if compile_tracker.enabled():
            tracker = compile_tracker.get_tracker()
            for name, fn in self.jit_functions().items():
                tracker.register(name, fn)

    # ------------------------------------------------------- compile plane
    def jit_functions(self) -> dict:
        """The hot-path jitted callables by tracker name.  The process-
        global chunk-prefill fn keeps a shared name (one compile cache,
        one budget); per-engine fns are suffixed so multi-replica routers
        track each replica's cache."""
        if self.dense_slots:
            return {f"engine{self._audit_id}.decode_fn": self._decode_fn}
        out = {
            f"engine{self._audit_id}.paged_decode_fn": self._paged_decode_fn,
        }
        if self.placement is None:
            out["chunk_prefill_fn[shared]"] = self._chunk_fn
        else:
            out[f"engine{self._audit_id}.chunk_prefill_fn"] = self._chunk_fn
        return out

    # ------------------------------------------------------------- kvsan
    def _kvsan_reachable(self):
        """Live page references outside the radix tree, for the sanitizer:
        per-slot scratch pages and every resident block table."""
        out = []
        for p in getattr(self, "_scratch_pages", []):
            out.append(("dev", p, "scratch"))
        for slot in self.slots.values():
            pid = slot.request.program_id
            for p in slot.table:
                out.append(("dev", p, f"block table of {pid}"))
        return out

    def _san_scope(self, tag: str) -> None:
        if self.pool._san is not None:
            self.pool._san.set_scope(tag)

    # ------------------------------------------------------------ admission
    def has_slot(self) -> bool:
        return bool(self._free_slots)

    def free_slot_count(self) -> int:
        """Decode slots currently available for ``submit`` — the real
        occupancy signal the scheduler's slot probe reads."""
        return len(self._free_slots)

    def warmup_specs(self, prefill_chunks: bool = False) -> list[WarmupSpec]:
        """Every shape the serving hot path can dispatch, as lazy-argument
        specs — the single source of truth shared by :meth:`warmup` (which
        executes them) and :mod:`repro.analysis.jitaudit` (which traces
        them without executing).

        Paged decode emits one spec per table bucket (tables pad to
        ``table_bucket_pages``); chunked prefill one per (prefix-page
        bucket x chunk bucket) pair up to ``prefill_chunk_tokens``; the
        dense path a single shape.  A replay that stays inside these specs
        never compiles after warmup — the compile tracker's budget.
        """
        if self.dense_slots:
            def dense_args():
                return (
                    self.params, self.slot_k, self.slot_v,
                    jnp.zeros(self.max_slots, jnp.int32),
                    jnp.ones(self.max_slots, jnp.int32),
                )

            return [WarmupSpec(
                name="decode_fn", kind="dense", fn_name="_decode_fn",
                make_args=dense_args, donate_argnums=(1, 2),
                bucket={"max_slots": self.max_slots,
                        "max_seq": self.max_seq},
                probe_group=f"engine{self._audit_id}/dense",
            )]
        scratch = np.asarray(self._scratch_pages, np.int32)
        n_buckets = -(-self.pages_per_slot // self._table_bucket)
        specs: list[WarmupSpec] = []

        quantized = self.quantized
        decode_donate = (1, 2, 3, 4) if quantized else (1, 2)

        def decode_args(p_pad: int):
            def make():
                tables = np.repeat(scratch[:, None], p_pad, axis=1)
                k_pages, v_pages = self.pool.block_table_view()
                sidecars = ()
                if quantized:
                    sidecars = self.pool.scale_view()
                return (
                    self.params, k_pages, v_pages, *sidecars,
                    jnp.zeros(self.max_slots, jnp.int32),
                    jnp.ones(self.max_slots, jnp.int32),
                    jnp.asarray(tables), jnp.asarray(scratch),
                    jnp.zeros(self.max_slots, jnp.int32),
                )

            return make

        for i in range(1, n_buckets + 1):
            p_pad = i * self._table_bucket
            specs.append(WarmupSpec(
                name=f"paged_decode_fn[pages={p_pad}]", kind="paged_decode",
                fn_name="_paged_decode_fn", make_args=decode_args(p_pad),
                donate_argnums=decode_donate, bucket={"table_pages": p_pad},
                probe_group=f"engine{self._audit_id}/paged_decode",
            ))
        if not prefill_chunks:
            return specs
        T = self.page_tokens
        cap = max(T, (self.prefill_chunk_tokens // T) * T)
        cap_pad = -(-cap // self.prefill_bucket) * self.prefill_bucket
        sp = int(scratch[0])

        chunk_donate = (1, 2, 3, 4) if quantized else (1, 2)

        def chunk_args(p_pad: int, c_pad: int):
            def make():
                w_pad = -(-c_pad // T)
                k_pages, v_pages = self.pool.block_table_view()
                sidecars = ()
                if quantized:
                    sidecars = self.pool.scale_view()
                return (
                    self.params, k_pages, v_pages, *sidecars,
                    jnp.asarray([sp] * p_pad, jnp.int32),
                    jnp.asarray([sp] * w_pad, jnp.int32),
                    jnp.zeros((1, c_pad), jnp.int32),
                    jnp.int32(0), jnp.int32(0),
                    jnp.int32(c_pad), jnp.int32(c_pad - 1),
                )

            return make

        for pb in range(n_buckets + 1):
            p_pad = pb * self._table_bucket
            # the prefix gather exists only when prefix pages do, so the
            # pb==0 bucket is deliberately a different traced program —
            # keep it in its own structural probe group
            group = "prefix" if p_pad else "no-prefix"
            for c_pad in range(self.prefill_bucket, cap_pad + 1,
                               self.prefill_bucket):
                specs.append(WarmupSpec(
                    name=f"chunk_prefill_fn[prefix_pages={p_pad},"
                         f"chunk={c_pad}]",
                    kind="chunk_prefill", fn_name="_chunk_fn",
                    make_args=chunk_args(p_pad, c_pad),
                    donate_argnums=chunk_donate,
                    bucket={"prefix_pages": p_pad, "chunk_tokens": c_pad},
                    probe_group=(
                        f"engine{self._audit_id}/chunk_prefill/{group}"
                    ),
                ))
        return specs

    def warmup(self, prefill_chunks: bool = False) -> None:
        """Precompile every decode-step shape before admitting traffic.

        The block-table path compiles once per table bucket (tables are
        padded to ``table_bucket_pages``); running each bucket here on the
        per-slot scratch pages means serving never hits a jit stall when a
        batch first crosses a bucket boundary. The dense path has a single
        shape. Must run on an idle engine (the dummy step writes garbage
        KV into scratch pages / slot position 0, both overwritten by the
        first real submit).

        ``prefill_chunks=True`` additionally compiles the chunked-prefill
        shapes (every prefix-page bucket x every chunk bucket up to the
        default ``prefill_chunk_tokens``) by running dummy chunks against
        scratch pages.

        When the compile tracker is armed (``REPRO_JITAUDIT=1``) the
        post-warmup cache sizes are snapshotted as this engine's compile
        budget: any later growth is a retrace warmup missed, and the
        router fails the replay on it."""
        assert not self.slots, "warmup must run on an idle engine"
        for spec in self.warmup_specs(prefill_chunks=prefill_chunks):
            out = getattr(self, spec.fn_name)(*spec.make_args())
            if spec.kind == "dense":
                _, self.slot_k, self.slot_v = out
            elif self.quantized:
                self.pool.adopt(out[1], out[2], out[3], out[4])
            else:
                self.pool.adopt(out[1], out[2])
        if compile_tracker.enabled():
            compile_tracker.get_tracker().mark_warm(
                tuple(self.jit_functions())
            )

    def submit(self, req: EngineRequest) -> int:
        """Admit one request whole: radix match -> reload -> prefill.

        On the paged engine this is :meth:`begin_submit` and one
        :meth:`prefill_step` over the whole suffix, so every prefill into
        the pool runs the same compiled chunk step. The dense-slot engine
        prefills the suffix over the gathered cached prefix and copies the
        context into its slot."""
        if not self.dense_slots:
            job = self.begin_submit(req)
            T = self.page_tokens
            done = self.prefill_step(job, -(-len(job.suffix) // T) * T)
            assert done, "a whole-suffix budget prefills in one chunk"
            return job.slot_id
        assert self._free_slots, "no free decode slots"
        assert len(req.tokens) + req.max_new_tokens <= self.max_seq
        pid = req.program_id
        self._san_scope(f"submit:{pid}")

        with region("engine.admit", (self.host_times, "prefill_host_s"), pid=pid):
            reloaded = self._reload_prefix(req.tokens)
            nodes = self.tree.match_prefix(req.tokens)
            cached = len(nodes) * self.page_tokens
            pages = [n.device_page for n in nodes]
            suffix = req.tokens[cached:]
            assert suffix, "request must extend its cached prefix"
            # the program's own nodes stay pinned until _finish
            self.tree.pin(pid)

            prefix = None
            if pages:
                pk, pv = self.pool.read_device_pages(pages)
                prefix = {"k": pk[:, None], "v": pv[:, None]}       # [L,1,Sp,KH,HD]

            pad = (-len(suffix)) % self.prefill_bucket
            batch = {"tokens": jnp.asarray([suffix + [0] * pad], jnp.int32)}
            logits, cache = self.model.prefill(
                self.params, batch, ctx=self.ctx, prefix=prefix,
                logit_index=len(suffix) - 1,
            )
            with region("engine.chunk_wait", pid=pid):
                first_token = int(greedy_token(logits[0]))

            sid = self._free_slots.pop()
            length = len(req.tokens)
            k_ctx = cache["k"][:, 0, : len(suffix)]                 # [L,Ssuf,KH,HD]
            v_ctx = cache["v"][:, 0, : len(suffix)]
            if prefix is not None:
                k_ctx = jnp.concatenate([prefix["k"][:, 0], k_ctx], axis=1)
                v_ctx = jnp.concatenate([prefix["v"][:, 0], v_ctx], axis=1)
            self.slot_k = self.slot_k.at[:, sid, :length].set(k_ctx)
            self.slot_v = self.slot_v.at[:, sid, :length].set(v_ctx)
            self.slots[sid] = _Slot(
                request=req,
                slot_id=sid,
                length=length,
                produced=[first_token],
                cached_tokens=cached,
                prefilled_tokens=len(suffix),
                reloaded_pages=reloaded,
            )
            self.lengths[sid] = length
            self.last_token[sid] = first_token
            self._tail_token[sid] = req.tokens[-1]  # prefill wrote its KV last
            return sid

    # --------------------------------------------------- chunked prefill
    def begin_submit(self, req: EngineRequest) -> PrefillJob:
        """Phase one of a chunked submit: radix match -> reload -> reserve.

        Reserves a decode slot (occupancy is visible to the scheduler's
        slot probe for the whole prefill), pins the matched prefix chain,
        and stages every suffix page up front so ``prefill_step`` can
        scatter chunk KV with a fixed-shape write. No model compute runs
        here. On allocation failure all state is rolled back and the
        RuntimeError propagates, mirroring ``submit``.
        """
        assert not self.dense_slots, "chunked prefill requires the paged engine"
        assert self._free_slots, "no free decode slots"
        assert len(req.tokens) + req.max_new_tokens <= self.max_seq
        pid = req.program_id
        self._san_scope(f"begin_submit:{pid}")

        with region("engine.admit", (self.host_times, "prefill_host_s"), pid=pid):
            reloaded = self._reload_prefix(req.tokens)
            nodes = self.tree.match_prefix(req.tokens)
            cached = len(nodes) * self.page_tokens
            pages = [n.device_page for n in nodes]
            suffix = req.tokens[cached:]
            assert suffix, "request must extend its cached prefix"

            self.tree.pin(pid)
            self.tree.acquire_nodes(nodes)
            sid = self._free_slots.pop()
            T = self.page_tokens
            new_pages: list[int] = []
            try:
                for _ in range(len(pages), -(-len(req.tokens) // T)):
                    new_pages.append(self._alloc_decode_page())
            except RuntimeError:
                for page in new_pages:
                    self.pool.free_device(page)
                self.tree.release_nodes(nodes)
                self.tree.unpin(pid)
                self._free_slots.append(sid)
                raise
            hold = None
            if self.pool._san is not None:
                # the staged suffix pages belong to this job until the final
                # chunk installs them into a slot's block table
                hold = self.pool._san.add_hold(
                    "dev", new_pages, f"prefill job:{pid}"
                )
            return PrefillJob(
                request=req,
                slot_id=sid,
                suffix=suffix,
                cached_tokens=cached,
                reloaded_pages=reloaded,
                prefix_pages=pages,
                prefix_nodes=nodes,
                new_pages=new_pages,
                kvsan_hold=hold,
            )

    def prefill_step(self, job: PrefillJob, token_budget: int | None = None) -> bool:
        """Run ONE bucketed prefill chunk of at most ``token_budget`` tokens
        (page-aligned; default ``prefill_chunk_tokens``). Returns True when
        the final chunk lands, at which point ``job.first_token`` is set and
        the slot is installed for decode.

        Shape discipline is what makes this fast: the chunk pads to
        ``prefill_bucket`` tokens and the page-gathered prefix pads to the
        table bucket (tail masked via ``prefix_valid``), so the jitted
        chunk fn compiles once per (prefix-bucket, chunk-bucket) pair and
        is shared process-wide (``submit`` on the paged engine is one
        such chunk over the whole suffix).
        """
        assert not job.done, "prefill job already completed"
        assert job.remaining > 0, "prefill job was cancelled"
        T = self.page_tokens
        budget = self.prefill_chunk_tokens if token_budget is None else token_budget
        cap = max(T, (budget // T) * T)          # page-aligned chunk ceiling
        take = min(job.remaining, cap)
        pid = job.request.program_id
        with region("engine.chunk", (self.host_times, "prefill_host_s"), pid=pid, tokens=take):
            c_pad = -(-take // self.prefill_bucket) * self.prefill_bucket
            scratch = self._scratch_pages[job.slot_id]

            # prefix for this chunk: radix pages + suffix pages already written
            # (the cursor is page-aligned on every chunk but the last)
            prefix_pages = job.prefix_pages + job.new_pages[: job.cursor // T]
            p_real = len(prefix_pages)
            p_pad = -(-p_real // self._table_bucket) * self._table_bucket
            prefix_idx = prefix_pages + [scratch] * (p_pad - p_real)

            # staged pages this chunk writes, padded to the bucketed width with
            # the slot's scratch page (pad lanes scatter zeros — harmless)
            w0 = job.cursor // T
            w_real = -(-take // T)
            w_pad = -(-c_pad // T)
            write_idx = job.new_pages[w0 : w0 + w_real]
            write_idx = write_idx + [scratch] * (w_pad - len(write_idx))

            chunk = job.suffix[job.cursor : job.cursor + take]
            tokens = jnp.asarray([chunk + [0] * (c_pad - take)], jnp.int32)
            pos0 = job.cached_tokens + job.cursor    # absolute chunk start
            k_pages, v_pages = self.pool.block_table_view()
            sidecars = self.pool.scale_view() if self.quantized else ()
            out = self._chunk_fn(
                self.params, k_pages, v_pages, *sidecars,
                jnp.asarray(prefix_idx, jnp.int32),
                jnp.asarray(write_idx, jnp.int32),
                tokens,
                jnp.int32(pos0),                     # prefix_valid == chunk start
                jnp.int32(pos0),
                jnp.int32(take),
                jnp.int32(take - 1),                 # final-chunk logit position
            )
            logits = out[0]
            self.pool.adopt(*out[1:])
            job.cursor += take
            job.chunks_run += 1
            if job.cursor < len(job.suffix):
                return False
            with region("engine.chunk_wait", pid=pid):
                job.first_token = int(greedy_token(logits))
            self._install_job(job)
            return True

    def _install_job(self, job: PrefillJob) -> None:
        """Final chunk landed: install the job's slot for decode."""
        req = job.request
        sid = job.slot_id
        length = len(req.tokens)
        if job.kvsan_hold is not None:
            # ownership moves to the slot's block table (registered via
            # the engine's reachability callback)
            self.pool._san.drop_hold(job.kvsan_hold)
            job.kvsan_hold = None
        self.slots[sid] = _Slot(
            request=req,
            slot_id=sid,
            length=length,
            produced=[job.first_token],
            cached_tokens=job.cached_tokens,
            prefilled_tokens=len(job.suffix),
            reloaded_pages=job.reloaded_pages,
            table=list(job.prefix_pages) + list(job.new_pages),
            owned_from=len(job.prefix_pages),
            prefix_nodes=job.prefix_nodes,
        )
        self.lengths[sid] = length
        self.last_token[sid] = job.first_token
        self._tail_token[sid] = req.tokens[-1]

    def cancel_prefill(self, job: PrefillJob) -> None:
        """Abort a mid-flight prefill job: free the staged pages, release
        the pinned prefix chain and return the reserved slot. Partially
        written pages go back to the free list (pages are always fully
        rewritten before anything attends over them)."""
        assert not job.done, "job already installed; retire via decode"
        self._san_scope(f"cancel_prefill:{job.request.program_id}")
        if job.kvsan_hold is not None:
            self.pool._san.drop_hold(job.kvsan_hold)
            job.kvsan_hold = None
        for page in job.new_pages:
            self.pool.free_device(page)
        self.tree.release_nodes(job.prefix_nodes)
        self.tree.unpin(job.request.program_id)
        self._free_slots.append(job.slot_id)
        self.lengths[job.slot_id] = 0
        job.cursor = len(job.suffix)  # poison: no further prefill_step

    def _reload_prefix(self, tokens: list[int]) -> int:
        """Promote host-resident prefix pages to the device, best-effort.

        Stops at the first failed reload: pages past the break point cannot
        extend the *device-resident* prefix chain, so reloading them would
        burn scarce device pages (and evictions) for zero cached-token
        benefit. The chain is refcount-pinned while it streams so
        ``_ensure_device_page`` can never evict a later chain node to make
        room for an earlier one, and a fully-exhausted pool degrades to a
        shorter cached prefix instead of failing the submit.
        """
        chain = self.tree.match_prefix_any_tier(tokens)
        self.tree.acquire_nodes(chain)
        n = 0
        try:
            for node in chain:
                if node.device_page is not None:
                    continue
                try:
                    self._ensure_device_page()
                except RuntimeError:
                    break            # pool exhausted and nothing evictable
                dp = self.pool.reload_page(node.host_page)
                if dp is None:
                    break
                node.host_page = None
                node.device_page = dp
                n += 1
        finally:
            self.tree.release_nodes(chain)
        return n

    def _alloc_decode_page(self) -> int:
        """One device page for decode state (evicting cold cache if needed).

        Decode-state pages are funded by the pool's decode reserve, so the
        radix-cache budget is NOT consulted here — a cache legitimately
        sitting at its budget must not lose a warm page to every tail-page
        rollover; eviction only kicks in when the pool is genuinely out of
        free pages."""
        self._ensure_device_page(cache_page=False)
        page = self.pool.alloc_device()
        if page is None:
            raise RuntimeError("device pool exhausted and nothing evictable")
        return page

    # -------------------------------------------------------------- decode
    def _decode_impl(self, params, slot_k, slot_v, tokens, lengths):
        cache = {"k": slot_k, "v": slot_v}
        logits, new_cache = self.model.decode(
            params, cache, tokens, lengths, ctx=self.ctx
        )
        return greedy_token(logits), new_cache["k"], new_cache["v"]

    def step(self, active: "list[int] | None" = None) -> list[Completion]:
        """One continuous-batching decode step across the active slots.

        ``active`` selects which resident slots advance this step (default:
        all of them) — the router's decode pump uses it to pace each slot on
        its own virtual-time deadline while still issuing ONE batched decode
        call. Masked slots stay in the batch but their state is untouched:
        their lengths are not bumped and their row re-feeds the token whose
        KV already occupies the tail position (``_tail_token``), so the
        kernel's write is an idempotent rewrite of existing KV and the
        sampled token for those rows is discarded. Active rows are computed
        independently per batch row, so their tokens are identical whether
        the masked rows are present or not.

        Submitting a new request between steps is safe while other slots are
        mid-decode: the jitted decode donates the pool arrays, but
        ``pool.adopt`` reinstates the committed buffers before ``step``
        returns, so ``submit``'s pool reads/writes never see a donated
        (invalidated) buffer and its freshly-written pages are disjoint from
        every live block table.
        """
        if not self.slots:
            return []
        if active is None:
            active_ids = list(self.slots)
        else:
            active_ids = [sid for sid in active if sid in self.slots]
            if not active_ids:
                return []
        self.steps += 1
        n = self.steps
        counter = (self.host_times, "decode_host_s")
        with region("engine.decode_prep", counter, step=n, rows=len(active_ids)):
            active_set = set(active_ids)
            toks_np = self.last_token.copy()
            for sid in self.slots:
                if sid in active_set:
                    # this step writes last_token's KV at the new tail position
                    self._tail_token[sid] = self.last_token[sid]
                    self.lengths[sid] += 1  # the decoded token extends the ctx
                else:
                    # masked: rewrite the existing tail KV instead of clobbering
                    # it with the (not-yet-written) last token's
                    toks_np[sid] = self._tail_token[sid]
            toks = jnp.asarray(toks_np, jnp.int32)
            lens = jnp.asarray(np.maximum(self.lengths, 1), jnp.int32)
            if self.dense_slots:
                next_tok, self.slot_k, self.slot_v = self._decode_fn(
                    self.params, self.slot_k, self.slot_v, toks, lens
                )
            else:
                next_tok = self._paged_step(toks, lens)
            with region("engine.decode_wait", step=n):
                next_tok = np.asarray(next_tok)
        done: list[Completion] = []
        with region("engine.decode_finish", counter, step=n):
            for sid, slot in list(self.slots.items()):
                if sid not in active_set:
                    continue
                slot.length = int(self.lengths[sid])
                tok = int(next_tok[sid])
                slot.produced.append(tok)
                self.last_token[sid] = tok
                if len(slot.produced) >= slot.request.max_new_tokens:
                    done.append(self._finish(slot))
        return done

    def slot_progress(self) -> dict[int, tuple[str, int, int]]:
        """Per-slot decode progress: ``{slot_id: (pid, produced, budget)}``.
        Introspection for tests and operators (the pump paces decode from
        its own virtual-clock deadlines; this is the engine-truth view to
        check that bookkeeping against)."""
        return {
            sid: (
                slot.request.program_id,
                len(slot.produced),
                slot.request.max_new_tokens,
            )
            for sid, slot in self.slots.items()
        }

    def _paged_step(self, toks, lens):
        """Block-table decode: append KV to tail pages, attend via tables."""
        T = self.page_tokens
        for sid, slot in self.slots.items():
            pos = int(self.lengths[sid]) - 1    # this step's write position
            if pos // T == len(slot.table):     # tail page rolled over
                slot.table.append(self._alloc_decode_page())
        san = self.pool._san
        if san is not None:
            san.set_scope(f"step#{self.steps}")
            for sid, slot in self.slots.items():
                san.check_table(
                    slot.table, int(self.lengths[sid]) - 1,
                    slot.request.program_id,
                )
        # tables are padded to a bucketed page count so jit recompiles at
        # most pages_per_slot / bucket times per engine, while short
        # contexts still attend over far fewer positions than max_seq
        p_used = max(len(s.table) for s in self.slots.values())
        p_pad = -(-p_used // self._table_bucket) * self._table_bucket
        B = self.max_slots
        tables = np.zeros((B, p_pad), np.int32)
        tail_pages = np.zeros(B, np.int32)
        tail_offsets = np.zeros(B, np.int32)
        for sid in range(B):
            slot = self.slots.get(sid)
            if slot is None:
                # inactive batch row: attend over (and write to) its private
                # scratch page — never a live page
                tables[sid, :] = self._scratch_pages[sid]
                tail_pages[sid] = self._scratch_pages[sid]
            else:
                tables[sid, : len(slot.table)] = slot.table
                pos = int(self.lengths[sid]) - 1
                tail_pages[sid] = slot.table[pos // T]
                tail_offsets[sid] = pos % T
        k_pages, v_pages = self.pool.block_table_view()
        sidecars = self.pool.scale_view() if self.quantized else ()
        out = self._paged_decode_fn(
            self.params, k_pages, v_pages, *sidecars, toks, lens,
            jnp.asarray(tables), jnp.asarray(tail_pages),
            jnp.asarray(tail_offsets),
        )
        self.pool.adopt(*out[1:])
        return out[0]

    def _finish(self, slot: _Slot) -> Completion:
        """Persist the slot's full pages into the radix tree, free the slot.

        Paged mode hands the already-resident pages over by id (zero copy,
        and — unlike the dense path — persistence can never fail for lack
        of free pages: the pages exist by construction). Dense mode copies
        slot data back into freshly-allocated pool pages.
        """
        req = slot.request
        self._san_scope(f"finish:{req.program_id}")
        all_tokens = req.tokens + slot.produced[:-1]  # last token has no KV yet
        T = self.page_tokens
        n_full = len(all_tokens) // T
        have = len(self.tree.match_prefix(all_tokens))
        # retire the slot FIRST: the duplicate/tail frees below release
        # pages its block table still lists, and the sanitizer (rightly)
        # treats freeing a page under a live table as an eviction bug
        self.slots.pop(slot.slot_id)
        self._free_slots.append(slot.slot_id)
        self.lengths[slot.slot_id] = 0
        if self.dense_slots:
            new_pages = []
            for p in range(have, n_full):
                self._ensure_device_page()
                page = self.pool.alloc_device()
                if page is None:
                    break
                lo, hi = p * T, (p + 1) * T
                self.pool.write_device_page(
                    page,
                    self.slot_k[:, slot.slot_id, lo:hi],
                    self.slot_v[:, slot.slot_id, lo:hi],
                )
                new_pages.append(page)
            covered = (have + len(new_pages)) * T
        else:
            # duplicates of pages another program inserted first, plus the
            # partially-filled tail page, go back to the free list; the
            # rest transfer ownership to the tree in place
            new_pages = slot.table[have:n_full]
            for p in range(slot.owned_from, have):
                self.pool.free_device(slot.table[p])
            if len(all_tokens) % T and n_full < len(slot.table):
                self.pool.free_device(slot.table[n_full])
            covered = n_full * T
            self.tree.release_nodes(slot.prefix_nodes)
        self.tree.unpin(req.program_id)  # release the pages pinned at submit
        self.tree.insert_chain(
            all_tokens[:covered], new_pages, req.program_id, TypeLabel.BUSY
        )
        # budget enforcement happens where the cache GROWS: handing decode
        # pages to the tree may push it past radix_device_pages, so trim
        # back (typed order, LRU — fresh BUSY pages are the last victims).
        # Decode-state allocations deliberately never evict; see
        # _alloc_decode_page.
        while self._cache_over_budget() and self._evict_one_cache_page():
            pass
        return Completion(
            program_id=req.program_id,
            output_tokens=slot.produced,
            cached_tokens=slot.cached_tokens,
            prefilled_tokens=slot.prefilled_tokens,
            reloaded_pages=slot.reloaded_pages,
        )

    def run_to_completion(self, max_steps: int = 10_000) -> list[Completion]:
        out = []
        for _ in range(max_steps):
            out.extend(self.step())
            if not self.slots:
                break
        return out

    # ---------------------------------------------- typed eviction machinery
    def _cache_over_budget(self) -> bool:
        """Paged mode: is the radix cache at/over its device-page budget?

        The pool is over-provisioned by ``decode_reserve_pages`` for decode
        state, so raw free count no longer signals cache pressure — cache-
        growing allocations (reloads) evict back to ``radix_device_pages``
        so the cache cannot squat on the decode reserve indefinitely.
        (Walks the tree; only consulted on cache-growing allocs, which sit
        behind a host-side page copy anyway.)"""
        if self.dense_slots:
            return False
        return self.tree.stats()["device_pages"] >= self.radix_device_pages

    def _ensure_device_page(self, cache_page: bool = True) -> None:
        """Free one device page if the pool is exhausted (typed order) or
        a *cache-growing* allocation would push the radix cache past its
        budgeted share of the pool (``cache_page=False`` for decode-state
        pages, which the decode reserve funds)."""
        over_budget = cache_page and self._cache_over_budget()
        if self.pool.device_free_count() > 0 and not over_budget:
            return
        if self._evict_one_cache_page():
            return
        if self.pool.device_free_count() > 0:
            # over cache budget but every cached page is pinned by live
            # decodes: degrade into the reserve headroom rather than fail
            return
        raise RuntimeError("device pool exhausted and nothing evictable")

    def _evict_one_cache_page(self) -> bool:
        """Spill the best victim page to host (typed order); False if every
        cached page is pinned."""
        for node in self.tree.evictable("gpu"):
            dp = node.device_page
            hp = self.pool.offload_page(dp)  # spill to host if possible
            if hp is not None:
                node.device_page = None
                node.host_page = hp
            else:
                node.device_page = None
                self.pool.free_device(dp)
                self.tree._gc(node)
            self.evicted_pages["gpu"] += 1
            return True
        return False

    def _ensure_host_page(self) -> None:
        if self.pool.host_free_count() > 0:
            return
        for node in self.tree.evictable("cpu"):
            self.pool.free_host(self.tree.evict(node, "cpu"))
            self.evicted_pages["cpu"] += 1
            return

    # --------------------------------------------- MORI program-level verbs
    def offload_program(self, pid: str) -> int:
        """GPU -> host for all of the program's device pages. Returns count."""
        self._san_scope(f"offload_program:{pid}")
        n = 0
        for node in reversed(self.tree.program_nodes(pid)):  # leaves first
            if node.device_page is not None and node.refcount == 0:
                self._ensure_host_page()
                hp = self.pool.offload_page(node.device_page)
                if hp is None:
                    break
                node.device_page = None
                node.host_page = hp
                n += 1
        return n

    def reload_program(self, pid: str) -> int:
        """Host -> GPU for all of the program's pages. Returns count.

        The chain is refcount-held while it streams (mirroring
        ``_reload_prefix``): with the cache at its budget, the budget
        eviction inside ``_ensure_device_page`` would otherwise pick the
        just-reloaded, LRU-stale nodes of this very program as victims —
        a reload that silently undoes itself while billing full PCIe
        traffic."""
        self._san_scope(f"reload_program:{pid}")
        nodes = self.tree.program_nodes(pid)
        self.tree.acquire_nodes(nodes)
        n = 0
        try:
            for node in nodes:
                if node.device_page is None and node.host_page is not None:
                    self._ensure_device_page()
                    dp = self.pool.reload_page(node.host_page)
                    if dp is None:
                        break
                    node.host_page = None
                    node.device_page = dp
                    n += 1
        finally:
            self.tree.release_nodes(nodes)
        return n

    def discard_program(self, pid: str, tier: Tier) -> None:
        """Free the program's pages on ``tier``, leaves first. A node that
        a live block table holds, or that another chain hangs from on that
        tier (a shared prompt), keeps its page, as eviction does: freeing
        it would leave a hole at which every later match of that chain
        stops, while the chain's pages below it stay in the tree. The
        program is released once none of its nodes holds a page that is
        its own, i.e. a page no other chain hangs from on the page's tier
        (the test discard itself applies, on either tier, so a page kept
        on the other tier is still found by a later discard there)."""
        self._san_scope(f"discard_program:{pid}:{tier.value}")
        for node in reversed(self.tree.program_nodes(pid)):
            if node.refcount > 0 or not node.is_leaf_on(tier.value):
                continue
            if tier is Tier.GPU and node.device_page is not None:
                self.pool.free_device(node.device_page)
                node.device_page = None
            if tier is Tier.CPU and node.host_page is not None:
                self.pool.free_host(node.host_page)
                node.host_page = None
            self.tree._gc(node)
        if not any(
            (n.device_page is not None and n.is_leaf_on(Tier.GPU.value))
            or (n.host_page is not None and n.is_leaf_on(Tier.CPU.value))
            for n in self.tree.program_nodes(pid)
        ):
            self.tree.release_program(pid)

    def set_label(self, pid: str, label: TypeLabel) -> None:
        self.tree.restamp(pid, label)

    def abort_request(self, pid: str) -> EngineRequest | None:
        """Tear down a mid-decode slot without persisting its KV — the
        failover path: the router requeues the returned request and a
        healthy replica re-prefills the identical context, so no tokens
        are lost. Slot-owned pages (prefix duplicates, decode tail) go
        back to the free list; the shared prefix chain keeps its pages
        and just drops this slot's holds."""
        slot = next(
            (s for s in self.slots.values() if s.request.program_id == pid), None
        )
        if slot is None:
            return None
        self._san_scope(f"abort_request:{pid}")
        # retire the slot FIRST (same reachability rationale as _finish)
        self.slots.pop(slot.slot_id)
        self._free_slots.append(slot.slot_id)
        self.lengths[slot.slot_id] = 0
        if not self.dense_slots:
            for page in slot.table[slot.owned_from:]:
                self.pool.free_device(page)
            self.tree.release_nodes(slot.prefix_nodes)
        self.tree.unpin(pid)
        return slot.request
