"""Chunked prefill pinned to the monolithic path, bit for bit.

``Engine.begin_submit`` + ``prefill_step`` split a submit into page-sized
chunks the decode pump interleaves with decode steps. The contract this
battery enforces: chunking changes *when* prefill compute runs, never
what it produces —

* property battery: random suffix lengths × chunk budgets × warm/cold
  radix prefixes produce the same prefill as a monolithic ``submit`` up
  to the one thing bucketed padding may legally change — XLA reduction
  reassociation, bounded here at 2 bf16 ulp on pool pages and an
  argmax pick inside the monolithic logit tie set (see ``_race``);
* bucket edges: suffix exactly a ``prefill_bucket`` multiple (zero pad),
  suffix shorter than one chunk, and a chunk cursor that crosses into a
  partial tail page all line up with the monolithic path;
* job lifecycle: ``begin_submit`` holds real slot occupancy for the whole
  prefill (schedulers probing the engine see the slot as taken),
  ``cancel_prefill`` rolls every resource back, and a finished job's slot
  decodes like any submitted slot;
* the pump: a chunked replay is token-identical to the monolithic pump,
  records TTFT from the submit event to the first token, and beats
  monolithic mean TTFT on a contention corpus (chunk shapes are bucketed
  and jitted once process-wide; monolithic eager prefill re-dispatches
  per context length).
"""
from __future__ import annotations

import hashlib
import itertools
import json
import random
from pathlib import Path

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core import SchedulerConfig
from repro.core.types import ProgramTrace, RequestRecord
from repro.models import Model, materialize
from repro.serving import Engine, EngineRequest, MoriRouter

_pid = itertools.count()
_shared: dict = {}


def _cfg_params():
    if "setup" not in _shared:
        cfg = get_config("qwen1.5-0.5b").reduced()
        params = materialize(Model(cfg).describe(), seed=0)
        _shared["setup"] = (cfg, params)
    return _shared["setup"]


def _engine_pair():
    """One monolithic + one chunked engine, shared across the property
    examples (identical request sequences keep their radix trees, pools
    and jit caches in lockstep, so warm-prefix examples come for free).
    Module-level rather than a fixture: hypothesis refuses
    function-scoped fixtures in ``@given`` tests."""
    if "pair" not in _shared:
        cfg, params = _cfg_params()

        def mk():
            return Engine(cfg, params, page_tokens=8, n_device_pages=512,
                          n_host_pages=64, max_slots=2, max_seq=512,
                          prefill_bucket_tokens=16)

        _shared["pair"] = (cfg, mk(), mk())
    return _shared["pair"]


@pytest.fixture(scope="module")
def setup():
    return _cfg_params()


def _mono_logits(eng, tokens):
    """The full final-position logit row exactly as ``Engine.submit``
    computes it (same radix match, same pad math), captured *before* the
    submit consumes the request."""
    import jax.numpy as jnp

    nodes = eng.tree.match_prefix(list(tokens))
    cached = len(nodes) * eng.page_tokens
    suffix = list(tokens)[cached:]
    prefix = None
    if nodes:
        pk, pv = eng.pool.read_device_pages([n.device_page for n in nodes])
        prefix = {"k": pk[:, None], "v": pv[:, None]}
    pad = (-len(suffix)) % eng.prefill_bucket
    batch = {"tokens": jnp.asarray([suffix + [0] * pad], jnp.int32)}
    logits, _ = eng.model.prefill(eng.params, batch, ctx=eng.ctx,
                                  prefix=prefix, logit_index=len(suffix) - 1)
    return np.asarray(logits[0])


def _race(cfg, mono, chunked, tokens, budget, max_new_tokens=3,
          strict=True):
    """Submit ``tokens`` monolithically on ``mono`` and chunked (with the
    given per-chunk token budget) on ``chunked``; assert both paths
    compute the same prefill.

    ``strict=True`` demands full bit-identity: same first token, pool
    pages byte-equal, decoded streams equal — the fixed-input edge tests
    hold this on any one machine, like the golden replays do.

    ``strict=False`` is the property-battery contract, exact about what
    chunking is allowed to change: bucketed padding reassociates XLA's
    f32 reductions (the padded kv total differs from the monolithic
    shape), so bf16 KV may legally move by an ulp — and a 1-ulp wiggle
    on a near-zero element flips its sign, while a wiggle on two
    logits tied at the bf16 top flips the argmax. The relaxed
    assertions are still tight: pages allclose at bf16 resolution, the
    chunked first token's *monolithic* logit within a few ulp of the
    monolithic max (a genuinely wrong token — shifted positions, stale
    prefix — misses by hundreds), and any run whose pages and first
    token agree exactly must decode the identical stream.
    """
    pid = f"prop-{next(_pid)}"
    req = EngineRequest(pid, list(tokens), max_new_tokens=max_new_tokens)

    logits = None if strict else _mono_logits(mono, tokens)
    sid = mono.submit(EngineRequest(pid, list(tokens),
                                    max_new_tokens=max_new_tokens))
    job = chunked.begin_submit(req)
    steps = 0
    while not chunked.prefill_step(job, budget):
        steps += 1
        assert steps < 1000, "prefill never converged"
    assert job.done and job.chunks_run == steps + 1

    m_slot, c_slot = mono.slots[sid], chunked.slots[job.slot_id]
    assert c_slot.cached_tokens == m_slot.cached_tokens
    assert c_slot.prefilled_tokens == m_slot.prefilled_tokens
    assert len(c_slot.table) == len(m_slot.table)

    mk_, mv_ = mono.pool.read_device_pages(m_slot.table)
    ck_, cv_ = chunked.pool.read_device_pages(c_slot.table)
    mk_, ck_ = np.asarray(mk_, np.float32), np.asarray(ck_, np.float32)
    mv_, cv_ = np.asarray(mv_, np.float32), np.asarray(cv_, np.float32)
    bit_equal = np.array_equal(mk_, ck_) and np.array_equal(mv_, cv_)
    tokens_equal = c_slot.produced[0] == m_slot.produced[0]

    if strict:
        assert bit_equal, "pool pages diverged"
        assert tokens_equal
    else:
        # a couple of bf16 ulp of slack (eps = 2^-8 rel); anything past
        # that is a real divergence, not reassociation
        assert np.allclose(mk_, ck_, rtol=0.03, atol=0.03)
        assert np.allclose(mv_, cv_, rtol=0.03, atol=0.03)
        # the chunked first token must sit in the monolithic argmax tie
        # set (up to the same reassociation noise: a few bf16 ulp)
        best = float(logits.max())
        got = float(logits[job.first_token])
        assert got >= best - max(0.1, 0.04 * abs(best)), (
            f"first token {job.first_token} has monolithic logit {got}, "
            f"max is {best}"
        )

    m_out = {c.program_id: c.output_tokens for c in mono.run_to_completion()}
    c_out = {c.program_id: c.output_tokens for c in chunked.run_to_completion()}
    if strict or (bit_equal and tokens_equal):
        assert m_out == c_out
    return job


class TestChunkedEqualsMonolithic:
    @given(
        suffix_len=st.integers(1, 70),
        budget=st.integers(0, 48),
        warm_pages=st.integers(0, 3),
        seed=st.integers(0, 10**6),
    )
    @settings(max_examples=6, deadline=None)
    def test_property_token_and_page_identity(self, suffix_len,
                                              budget, warm_pages, seed):
        """Random (suffix length, chunk budget, warm-prefix depth) draws:
        chunked prefill must be indistinguishable from monolithic in
        tokens and in pool bytes, warm or cold radix."""
        cfg, mono, chunked = _engine_pair()
        rng = random.Random(seed)
        vocab = cfg.vocab_size
        prefix = [rng.randrange(2, vocab) for _ in range(8 * warm_pages)]
        if warm_pages:
            # warm the radix on both engines with a request sharing the
            # page-aligned prefix; its continuation (token 1, never drawn
            # below) keeps the match from extending past the prefix pages
            pid = f"warm-{next(_pid)}"
            for eng in (mono, chunked):
                eng.submit(EngineRequest(pid, prefix + [1, 1, 1],
                                         max_new_tokens=1))
                eng.run_to_completion()
        tokens = prefix + [rng.randrange(2, vocab) for _ in range(suffix_len)]
        job = _race(cfg, mono, chunked, tokens, budget, strict=False)
        if warm_pages:
            assert job.cached_tokens == 8 * warm_pages

    def test_suffix_exactly_a_bucket_multiple(self):
        """prefill_bucket=16: a 32-token suffix pads by zero in the
        monolithic path (engine.py submit pad math) and chunks evenly —
        both edges of the bucket arithmetic at once."""
        cfg, mono, chunked = _engine_pair()
        tokens = [((7 * i) % (cfg.vocab_size - 2)) + 2 for i in range(32)]
        job = _race(cfg, mono, chunked, tokens, budget=16)
        assert job.chunks_run == 2

    def test_suffix_shorter_than_one_chunk(self):
        """A 3-token suffix (< page_tokens < budget) must run as a single
        sub-page chunk with a zero-padded tail page."""
        cfg, mono, chunked = _engine_pair()
        tokens = [5, 9, 13]
        job = _race(cfg, mono, chunked, tokens, budget=64)
        assert job.chunks_run == 1

    def test_chunk_cursor_crosses_partial_tail_page(self):
        """page_tokens=8, suffix=17, budget=8: chunks of 8+8+1, the last
        landing a single token in a fresh tail page. The cursor stays
        page-aligned on every chunk except the final one."""
        cfg, mono, chunked = _engine_pair()
        tokens = [((3 * i) % (cfg.vocab_size - 2)) + 2 for i in range(17)]
        job = _race(cfg, mono, chunked, tokens, budget=8)
        assert job.chunks_run == 3

    def test_tiny_budget_is_page_clamped(self):
        """A budget below page_tokens still makes progress: chunks clamp
        up to one full page, never to zero."""
        cfg, mono, chunked = _engine_pair()
        tokens = [((11 * i) % (cfg.vocab_size - 2)) + 2 for i in range(20)]
        job = _race(cfg, mono, chunked, tokens, budget=1)
        assert job.chunks_run == 3          # 8 + 8 + 4


class TestPrefillJobLifecycle:
    def test_begin_submit_holds_slot_occupancy(self, setup):
        """The reserved slot is real occupancy from begin_submit on: a
        1-slot engine refuses a second admission mid-prefill, and frees
        the slot only when the job's decode retires — the contract the
        scheduler's slot probe (core/scheduler.attach_slot_probe) relies
        on for gating."""
        cfg, params = setup
        eng = Engine(cfg, params, page_tokens=8, n_device_pages=64,
                     n_host_pages=64, max_slots=1, max_seq=256)
        job = eng.begin_submit(
            EngineRequest("occ", list(range(2, 40)), max_new_tokens=2))
        with pytest.raises(AssertionError, match="no free decode slots"):
            eng.begin_submit(
                EngineRequest("occ2", list(range(50, 80)), max_new_tokens=2))
        with pytest.raises(AssertionError, match="no free decode slots"):
            eng.submit(
                EngineRequest("occ3", list(range(90, 120)), max_new_tokens=2))
        while not eng.prefill_step(job, 16):
            pass
        assert job.slot_id in eng.slots     # installed for decode
        eng.run_to_completion()
        # pipeline drained and the program retired: slot is free again
        eng.submit(EngineRequest("occ4", list(range(150, 180)),
                                 max_new_tokens=2))
        eng.run_to_completion()

    def test_cancel_prefill_rolls_everything_back(self, setup):
        """Cancelling mid-flight returns the slot, frees the staged pages
        and unpins the prefix; the poisoned job refuses further chunks."""
        cfg, params = setup
        eng = Engine(cfg, params, page_tokens=8, n_device_pages=64,
                     n_host_pages=64, max_slots=1, max_seq=256)
        free_pages = eng.pool.device_free_count()
        job = eng.begin_submit(
            EngineRequest("cx", list(range(2, 40)), max_new_tokens=2))
        eng.prefill_step(job, 8)            # one chunk in flight
        eng.cancel_prefill(job)
        assert eng.pool.device_free_count() == free_pages
        with pytest.raises(AssertionError, match="cancelled"):
            eng.prefill_step(job, 8)
        # the slot and pages are genuinely reusable
        sid = eng.submit(EngineRequest("cy", list(range(2, 40)),
                                       max_new_tokens=2))
        assert sid == job.slot_id
        eng.run_to_completion()

    def test_chunked_rejects_dense_engine(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, dense_slots=True, max_slots=1, max_seq=256)
        with pytest.raises(AssertionError, match="paged engine"):
            eng.begin_submit(
                EngineRequest("d", list(range(2, 20)), max_new_tokens=2))


def _contention_corpus():
    """Four programs with aligned windows and growing contexts: every
    submit after the first sees a different suffix length, which is
    exactly where monolithic eager prefill pays per-shape dispatch and
    bucketed chunks do not."""
    busy = [
        ProgramTrace(f"p{i}", [
            RequestRecord(48 + 4 * i, 4, 1.0, reasoning_wall_s=2.0),
            RequestRecord(60 + 4 * i, 4, 1.0, reasoning_wall_s=2.0),
            RequestRecord(72 + 4 * i, 4, 0.0, reasoning_wall_s=2.0),
        ])
        for i in range(3)
    ]
    idle = ProgramTrace("p3", [
        RequestRecord(64, 4, 30.0, reasoning_wall_s=2.0),
        RequestRecord(80, 4, 0.0, reasoning_wall_s=2.0),
    ])
    return busy + [idle]


class TestChunkedPump:
    def test_pump_replay_token_identical_and_ttft_faster(self, setup):
        """The full router path: a chunked pump replay over a contention
        corpus (mid-window joins, one long tool call) generates exactly
        the monolithic pump's tokens, counts its chunks, and lands a
        strictly lower mean TTFT — the point of chunking: the first token
        of a join is never hostage to one monolithic prefill."""
        cfg, params = setup
        logs, ttft = {}, {}
        for chunked in (False, True):
            engine = Engine(cfg, params, page_tokens=8, n_device_pages=256,
                            n_host_pages=64, max_slots=4, max_seq=512)
            router = MoriRouter(
                [engine], scheduler="mori",
                config=SchedulerConfig(tick_interval_s=1.0),
                sync_transfers=True, chunked_prefill=chunked,
                prefill_token_budget=32 if chunked else None,
            )
            m = router.replay(_contention_corpus(),
                              vocab_size=cfg.vocab_size, max_new_tokens=4)
            assert m.steps_completed == 11
            s = m.ttft_s
            assert s["n"] == 11 and s["p50"] <= s["p95"]
            logs[chunked], ttft[chunked] = router.output_log, s["mean"]
            if chunked:
                assert m.prefill_chunks > 0
        assert logs[False] == logs[True]
        assert ttft[True] < ttft[False]

    def test_chunked_requires_the_pump(self, setup):
        cfg, params = setup
        engine = Engine(cfg, params, page_tokens=8, n_device_pages=64,
                        n_host_pages=64, max_slots=2, max_seq=256)
        with pytest.raises(ValueError, match="decode pump"):
            MoriRouter([engine], scheduler="mori", serial_decode=True,
                       chunked_prefill=True)

    def test_chunked_requires_paged_engines(self, setup):
        cfg, params = setup
        engine = Engine(cfg, params, dense_slots=True, max_slots=2,
                        max_seq=256)
        with pytest.raises(ValueError, match="paged"):
            MoriRouter([engine], scheduler="mori", chunked_prefill=True)


GOLDEN = Path(__file__).parent / "data" / "golden_chunked_replay.json"
SERIAL_GOLDEN = Path(__file__).parent / "data" / "golden_serial_replay.json"
#: the serial-replay golden may only move when the *replay harness*
#: changes, never when an execution-path PR lands. Last regeneration:
#: the multi-replica failover PR made context synthesis per-program
#: (order-independent), so synthesized corpus token values shifted; the
#: serialized execution order itself is re-verified against the pump by
#: test_decode_pump's equivalence battery
SERIAL_GOLDEN_SHA256 = (
    "33c4a8903f4900afb710282d56708b357c9a743f28fcf351bcbf10eb7a76b469"
)


class TestChunkedGolden:
    def test_chunked_pump_replay_matches_golden(self, setup):
        """Pinned capture: a 4-program generated pressure corpus (async
        transfers, 2-slot engine, mid-window joins under gating) replayed
        through the chunked pump reproduces the golden token streams,
        step count and chunk count exactly."""
        cfg, params = setup
        golden = json.loads(GOLDEN.read_text())

        from repro.core.types import TransferCost
        from repro.traces import TraceGenConfig, generate_corpus

        tg = TraceGenConfig(
            min_steps=3, mean_steps=4, max_steps=4,
            initial_context_mean=700, max_context=1800,
            long_median_s=20.0, busy_calls_mean=2.0, idle_calls_mean=2.0,
        )
        corpus = generate_corpus(4, seed=5, cfg=tg)
        engine = Engine(cfg, params, page_tokens=8, n_device_pages=96,
                        n_host_pages=96, max_slots=2, max_seq=320)
        router = MoriRouter(
            [engine], scheduler="mori", gpu_capacity_bytes=500_000,
            config=SchedulerConfig(tick_interval_s=2.0),
            chunked_prefill=True, prefill_token_budget=64,
            xfer_cost=TransferCost(pcie_bytes_per_s=2e5),
        )
        m = router.replay(corpus, vocab_size=cfg.vocab_size,
                          max_new_tokens=4)
        assert router.output_log == golden["chunked_pump"]
        assert m.steps_completed == golden["chunked_pump_steps"]
        assert m.prefill_chunks == golden["chunked_pump_chunks"]
        assert m.gated_events >= 1          # joins really were mid-window

    def test_serial_golden_pinned(self):
        """The serial-replay golden capture file is byte-pinned: neither
        chunked prefill nor any later execution-path change may move it
        (test_decode_pump re-runs the replay itself; this pins the
        capture file). Regenerating it is only legitimate alongside a
        deliberate replay-harness change — see the note at
        SERIAL_GOLDEN_SHA256."""
        digest = hashlib.sha256(SERIAL_GOLDEN.read_bytes()).hexdigest()
        assert digest == SERIAL_GOLDEN_SHA256
