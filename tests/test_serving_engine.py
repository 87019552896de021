"""Real-engine tests: paged KV + radix reuse correctness, typed eviction
under pressure, MORI router integration (deliverable b/c)."""
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import SchedulerConfig
from repro.core.types import Tier, TypeLabel
from repro.models import Model, materialize
from repro.serving import Engine, EngineRequest, MoriRouter, snapshot_state
from repro.traces import TraceGenConfig, generate_corpus


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = Model(cfg)
    params = materialize(model.describe(), seed=0)
    return cfg, model, params


def make_engine(cfg, params, **kw):
    kw.setdefault("page_tokens", 8)
    kw.setdefault("n_device_pages", 64)
    kw.setdefault("n_host_pages", 64)
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_seq", 256)
    return Engine(cfg, params, **kw)


class TestEngineCorrectness:
    def test_decode_matches_direct_forward(self, setup):
        cfg, model, params = setup
        eng = make_engine(cfg, params)
        ctx = list(range(2, 60))
        eng.submit(EngineRequest("p", ctx, max_new_tokens=4))
        out = eng.run_to_completion()[0].output_tokens
        # greedy reference: iterative full prefill
        ref = []
        cur = list(ctx)
        for _ in range(4):
            logits, _ = model.prefill(params, {"tokens": jnp.asarray([cur], jnp.int32)})
            t = int(jnp.argmax(logits[0]))
            ref.append(t)
            cur.append(t)
        assert out == ref

    def test_prefix_cache_reduces_prefill(self, setup):
        cfg, _, params = setup
        eng = make_engine(cfg, params)
        ctx = list(range(2, 50))
        eng.submit(EngineRequest("p", ctx, max_new_tokens=4))
        c1 = eng.run_to_completion()[0]
        assert c1.cached_tokens == 0
        ctx2 = ctx + c1.output_tokens[:-1] + [99, 98, 97]
        eng.submit(EngineRequest("p", ctx2, max_new_tokens=4))
        c2 = eng.run_to_completion()[0]
        assert c2.cached_tokens >= 40  # most of the prefix reused
        assert c2.prefilled_tokens < len(ctx2) - 32

    def test_chunked_prefill_equals_fresh_prefill(self, setup):
        """A cached-prefix submit must produce the same first token as an
        engine with a cold cache — prefix-conditioned attention correctness."""
        cfg, _, params = setup
        warm = make_engine(cfg, params)
        cold = make_engine(cfg, params)
        ctx = list(range(2, 42))
        warm.submit(EngineRequest("p", ctx, max_new_tokens=3))
        w1 = warm.run_to_completion()[0]
        ctx2 = ctx + w1.output_tokens[:-1] + [1000, 1001, 1002, 1003]
        warm.submit(EngineRequest("p", ctx2, max_new_tokens=3))
        cold.submit(EngineRequest("q", ctx2, max_new_tokens=3))
        wout = warm.run_to_completion()[0]
        cout = cold.run_to_completion()[0]
        assert wout.cached_tokens > 0 and cout.cached_tokens == 0
        assert wout.output_tokens == cout.output_tokens

    def test_shared_prefix_across_programs(self, setup):
        cfg, _, params = setup
        eng = make_engine(cfg, params)
        base = list(range(2, 34))
        eng.submit(EngineRequest("a", base + [50, 51], max_new_tokens=3))
        eng.run_to_completion()
        eng.submit(EngineRequest("b", base + [60, 61], max_new_tokens=3))
        c = eng.run_to_completion()[0]
        assert c.cached_tokens == 32  # the shared full pages


class TestTypedEvictionUnderPressure:
    def test_device_exhaustion_spills_to_host(self, setup):
        cfg, _, params = setup
        eng = make_engine(cfg, params, n_device_pages=12, n_host_pages=48)
        for i in range(4):
            ctx = list(range(1000 * i, 1000 * i + 56))
            eng.submit(EngineRequest(f"p{i}", ctx, max_new_tokens=3))
            eng.run_to_completion()
        st = eng.pool.stats()
        assert st.offload_bytes > 0  # typed eviction spilled pages to host
        assert eng.evicted_pages["gpu"] > 0

    def test_idle_labelled_evicted_before_busy(self, setup):
        cfg, _, params = setup
        eng = make_engine(cfg, params, n_device_pages=16, n_host_pages=64)
        eng.submit(EngineRequest("busy", list(range(0, 56)), max_new_tokens=3))
        eng.run_to_completion()
        eng.submit(EngineRequest("idle", list(range(500, 556)), max_new_tokens=3))
        eng.run_to_completion()
        eng.set_label("idle", TypeLabel.IDLE)
        eng.set_label("busy", TypeLabel.BUSY)
        # force evictions: a third program needs pages
        eng.submit(EngineRequest("new", list(range(900, 956)), max_new_tokens=3))
        eng.run_to_completion()
        busy_dev = sum(
            n.device_page is not None for n in eng.tree.program_nodes("busy")
        )
        idle_dev = sum(
            n.device_page is not None for n in eng.tree.program_nodes("idle")
        )
        assert busy_dev >= idle_dev  # idle-labelled pages went first

    def test_offload_reload_preserves_cache(self, setup):
        cfg, _, params = setup
        eng = make_engine(cfg, params)
        ctx = list(range(2, 50))
        eng.submit(EngineRequest("p", ctx, max_new_tokens=4))
        c1 = eng.run_to_completion()[0]
        n_off = eng.offload_program("p")
        assert n_off > 0
        assert all(n.device_page is None for n in eng.tree.program_nodes("p"))
        n_rel = eng.reload_program("p")
        assert n_rel == n_off
        ctx2 = ctx + c1.output_tokens[:-1] + [40, 41]
        eng.submit(EngineRequest("p", ctx2, max_new_tokens=3))
        c2 = eng.run_to_completion()[0]
        assert c2.cached_tokens >= 40  # cache survived the roundtrip

    def test_discard_frees_everything(self, setup):
        cfg, _, params = setup
        eng = make_engine(cfg, params)
        eng.submit(EngineRequest("p", list(range(2, 50)), max_new_tokens=3))
        eng.run_to_completion()
        before = eng.pool.device_free_count()
        eng.discard_program("p", Tier.GPU)
        assert eng.pool.device_free_count() > before
        assert eng.tree.program_nodes("p") == []

    @pytest.mark.parametrize("order", [(Tier.GPU, Tier.CPU), (Tier.CPU, Tier.GPU)])
    def test_discard_keeps_a_shared_prefix(self, setup, order):
        """Discarding one program, on both tiers in either order, must not
        free the pages of a prompt that another program's cached chain
        hangs from: the other program's next turn matches its whole
        history and finishes, and the discarded program is released."""
        cfg, _, params = setup
        eng = make_engine(cfg, params)
        prompt = list(range(2, 18))                      # two shared pages
        ctx = {"a": prompt + list(range(100, 120)),
               "b": prompt + list(range(200, 220))}
        for pid in ctx:
            eng.submit(EngineRequest(pid, ctx[pid], max_new_tokens=3))
            out = eng.run_to_completion()[0].output_tokens
            ctx[pid] += out[:-1]
        for tier in order:
            eng.discard_program("a", tier)
        assert eng.tree.program_nodes("a") == []
        shared = eng.tree.match_prefix(prompt)
        assert len(shared) == 2 and all(n.device_page is not None for n in shared)
        eng.submit(EngineRequest("b", ctx["b"] + [7, 8, 9], max_new_tokens=3))
        done = eng.run_to_completion()[0]
        assert done.cached_tokens == len(ctx["b"]) // 8 * 8


class TestRouterIntegration:
    def test_replay_with_mori(self, setup):
        cfg, _, params = setup
        engines = [
            make_engine(cfg, params, n_device_pages=96, n_host_pages=96, max_seq=384)
            for _ in range(2)
        ]
        router = MoriRouter(engines, scheduler="mori")
        tg = TraceGenConfig(
            min_steps=3, mean_steps=5, max_steps=5,
            initial_context_mean=600, max_context=2000,
        )
        corpus = generate_corpus(4, seed=0, cfg=tg)
        m = router.replay(corpus, vocab_size=cfg.vocab_size, max_new_tokens=4)
        assert m.steps_completed >= 12
        assert m.cache_hit_rate > 0.5  # program-aware pinning pays off
        snap = snapshot_state(router)
        # all programs finished and freed; no decode slot left resident
        assert [r["gpu_used"] for r in snap["replicas"]] == [0, 0]
        assert all(r["slots"] == [] for r in snap["replicas"])

    def test_replay_under_pressure_offloads(self, setup):
        cfg, _, params = setup
        engines = [
            make_engine(
                cfg, params, n_device_pages=40, n_host_pages=120,
                max_slots=2, max_seq=320,
            )
        ]
        router = MoriRouter(
            engines,
            scheduler="mori",
            # scheduler budget below the engine pool: overflow must trigger
            # demotions (and real page offloads) well before the pool fails
            gpu_capacity_bytes=700_000,
            config=SchedulerConfig(tick_interval_s=2.0),
        )
        tg = TraceGenConfig(
            min_steps=4, mean_steps=6, max_steps=6,
            initial_context_mean=900, max_context=2200,
            long_median_s=30.0, busy_calls_mean=2.0, idle_calls_mean=2.0,
        )
        corpus = generate_corpus(5, seed=2, cfg=tg)
        m = router.replay(corpus, vocab_size=cfg.vocab_size, max_new_tokens=4)
        assert m.steps_completed >= 15
        # memory pressure forced real page movement through the tiers
        assert m.offloaded_pages + engines[0].evicted_pages["gpu"] > 0
