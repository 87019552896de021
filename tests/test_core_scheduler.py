"""Unit + property tests for MORI's three-tier scheduler (paper §4.3),
driven through the PlacementPlan protocol."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from _plan_driver import Driver
from repro.core import (
    Discard,
    Forward,
    MoriScheduler,
    Offload,
    SCHEDULERS,
    SchedulerConfig,
    Status,
    Tier,
    TierCapacity,
    TypeLabel,
)


def make(gpu=1000, cpu=1000, replicas=1, ssd=0, **cfg):
    d = Driver(
        MoriScheduler(replicas, TierCapacity(gpu, cpu, ssd), SchedulerConfig(**cfg))
    )
    return d, d


def drive_step(s, pid, input_tokens, output_tokens, t_start, reason_s, tool_s):
    """One full inference+tool cycle; returns end time."""
    s.request_arrived(pid, input_tokens, t_start)
    s.notify_inference_started(pid, t_start)
    s.request_completed(pid, output_tokens, t_start + reason_s)
    return t_start + reason_s + tool_s


class TestPlacementBasics:
    def test_new_program_admitted_to_gpu(self):
        s, ad = make()
        s.program_arrived("a", 1, 0.0)
        s.request_arrived("a", 100, 0.0)
        assert s.programs["a"].tier is Tier.GPU
        fwd = ad.of_kind(Forward)[0]
        assert (fwd.pid, fwd.replica) == ("a", 0)
        assert fwd.recompute and fwd.source_tier is Tier.WAITING

    def test_resident_program_forwarded_without_recompute(self):
        s, ad = make()
        s.program_arrived("a", 1, 0.0)
        t = drive_step(s, "a", 100, 10, 0.0, 1.0, 1.0)
        s.request_arrived("a", 120, t)
        fwd = ad.of_kind(Forward)[-1]
        assert (fwd.pid, fwd.replica) == ("a", 0)
        assert not fwd.recompute and fwd.source_tier is Tier.GPU

    def test_gpu_capacity_respected_on_admission(self):
        s, _ = make(gpu=100)
        s.program_arrived("a", 1, 0.0)
        s.request_arrived("a", 80, 0.0)
        s.program_arrived("b", 1, 0.0)
        s.request_arrived("b", 50, 0.0)  # doesn't fit alongside a
        assert s.programs["a"].tier is Tier.GPU
        assert s.programs["b"].tier is Tier.WAITING
        assert s.programs["b"].has_pending

    def test_action_ids_strictly_increase(self):
        s, ad = make()
        for i in range(3):
            s.program_arrived(f"p{i}", 1, 0.0)
            s.request_arrived(f"p{i}", 20 + i, 0.0)
        ids = [a.action_id for a in ad.actions]
        assert ids == sorted(ids) and len(set(ids)) == len(ids)


class TestDemotion:
    def test_growth_overflow_demotes_most_idle_acting(self):
        s, ad = make(gpu=200, cpu=1000)
        s.program_arrived("idle", 1, 0.0)
        s.program_arrived("busy", 1, 0.0)
        # interleave so both are observed at comparable wall-clock times:
        # "idle" spends ~50s per tool call, "busy" ~0.2s
        t_idle, t_busy = 0.0, 0.0
        for _ in range(5):
            t_idle = drive_step(
                s, "idle", s.programs["idle"].context_tokens + 10, 5, t_idle, 1.0, 50.0
            )
        while t_busy < t_idle - 2.0:
            t_busy = drive_step(
                s, "busy", s.programs["busy"].context_tokens + 1, 1, t_busy, 1.0, 0.2
            )
        now = max(t_idle, t_busy) - 1.0
        # both acting; shrink GPU so only one fits
        s.replicas[0].capacity = TierCapacity(
            max(s.programs["busy"].kv_bytes, s.programs["idle"].kv_bytes) + 5, 1000
        )
        s.tick(now)
        assert s.programs["idle"].tier is Tier.CPU  # most idle demoted
        assert s.programs["busy"].tier is Tier.GPU
        off = ad.of_kind(Offload)[-1]
        assert (off.pid, off.replica, off.dst_tier) == ("idle", 0, Tier.CPU)
        # the offload is ledger-tracked until the runtime acknowledges it
        assert s.ledger.open_offload("idle") is not None
        ad.ack_all(now)
        assert s.ledger.open_offload("idle") is None

    def test_demotion_to_waiting_when_cpu_full(self):
        s, ad = make(gpu=200, cpu=0)
        s.program_arrived("a", 1, 0.0)
        drive_step(s, "a", 150, 10, 0.0, 1.0, 100.0)
        s.replicas[0].capacity = TierCapacity(50, 0)
        s.tick(10.0)
        assert s.programs["a"].tier is Tier.WAITING
        assert any(
            d.pid == "a" and d.replica == 0 and d.tier is Tier.GPU
            for d in ad.of_kind(Discard)
        )

    def test_reasoning_program_demoted_lazily(self):
        s, _ = make(gpu=100, cpu=1000)
        s.program_arrived("a", 1, 0.0)
        s.request_arrived("a", 90, 0.0)
        s.notify_inference_started("a", 0.0)  # reasoning now
        s.replicas[0].capacity = TierCapacity(10, 1000)
        s.tick(1.0)
        # still on GPU (mid-step), but marked for lazy demotion
        assert s.programs["a"].tier is Tier.GPU
        assert s.programs["a"].lazy_demote
        s.request_completed("a", 5, 2.0)
        assert s.programs["a"].tier is Tier.CPU

    def test_second_tick_counts_pending_lazy_demotions(self):
        """Regression: a demote pass that runs while an earlier pass's
        lazy-demote victim is still mid-step must count that victim's
        pending bytes — the old code re-counted the same overflow and
        demoted extra Acting programs whose eviction was never needed."""
        s, ad = make(gpu=1000, cpu=1000)
        s.program_arrived("p0", 1, 0.0)
        s.request_arrived("p0", 60, 0.0)
        s.notify_inference_started("p0", 0.0)   # long step: reasoning
        s.program_arrived("q", 1, 0.0)
        s.request_arrived("q", 30, 0.0)
        s.notify_inference_started("q", 0.0)
        s.replicas[0].capacity = TierCapacity(80, 1000)
        s.tick(1.0)
        # 90 used > 80: p0 (mid-step) marked for lazy demotion; its 60
        # pending bytes already resolve the overflow, so q is untouched
        assert s.programs["p0"].lazy_demote
        assert not s.programs["q"].lazy_demote
        s.request_completed("q", 0, 2.0)        # q finishes its step: Acting
        plan = s.tick(3.0)                      # second pass, p0 still mid-step
        # the pending lazy demotion covers the overflow: q must NOT be
        # demoted (the bug double-counted and evicted it here)
        assert s.programs["q"].tier is Tier.GPU
        assert not s.programs["q"].lazy_demote
        assert s.programs["q"].metrics.demotions == 0
        assert not [o for o in plan.of_kind(Offload) if o.pid == "q"]
        # p0's step finally ends: the deferred demotion fires, q keeps GPU
        s.request_completed("p0", 0, 4.0)
        assert s.programs["p0"].tier is Tier.CPU
        assert s.programs["q"].tier is Tier.GPU
        assert [o.pid for o in ad.of_kind(Offload)] == ["p0"]
        s.replicas[0].check()
        assert s.replicas[0].gpu_used <= 80

    def test_cpu_admission_control_spills_busiest_to_waiting(self):
        s, _ = make(gpu=1000, cpu=100)
        for pid, tool_s in [("busyish", 1.0), ("idler", 80.0)]:
            s.program_arrived(pid, 1, 0.0)
            t = 0.0
            for _ in range(3):
                t = drive_step(s, pid, s.programs[pid].context_tokens + 20, 10, t, 1.0, tool_s)
        # force both to CPU then shrink CPU
        s.replicas[0].capacity = TierCapacity(0, 100)
        s.tick(100.0)
        s.replicas[0].capacity = TierCapacity(0, s.programs["idler"].kv_bytes)
        s.tick(101.0)
        assert s.programs["idler"].tier is Tier.CPU  # CPU retains the idle one
        assert s.programs["busyish"].tier is Tier.WAITING


class TestPromotion:
    def test_cpu_promotion_preserves_affinity_and_reloads(self):
        s, ad = make(gpu=300, cpu=1000, replicas=2)
        s.program_arrived("a", 1, 0.0)
        t = drive_step(s, "a", 100, 10, 0.0, 1.0, 60.0)
        home = s.programs["a"].replica
        s.replicas[home].capacity = TierCapacity(0, 1000)
        s.tick(30.0)  # demote to CPU
        assert s.programs["a"].tier is Tier.CPU
        ad.ack_all(30.0)  # offload transfer lands
        s.replicas[home].capacity = TierCapacity(300, 1000)
        s.request_arrived("a", 130, t)  # tool done -> pending
        s.tick(t + 1.0)
        assert s.programs["a"].tier is Tier.GPU
        assert s.programs["a"].replica == home  # affinity preserved
        fwd = ad.of_kind(Forward)[-1]
        assert fwd.source_tier is Tier.CPU and not fwd.recompute
        # the reload moves only the KV materialized before the offload, not
        # the new input tokens that arrived while the program sat on CPU
        assert fwd.nbytes == s.programs["a"].materialized_bytes
        assert fwd.nbytes < s.programs["a"].kv_bytes

    def test_swap_idle_gpu_resident_for_busy_returner(self):
        s, _ = make(gpu=100, cpu=1000)
        # "idle" occupies all of GPU and sits in a long tool call
        s.program_arrived("idle", 1, 0.0)
        t = 0.0
        for _ in range(3):
            t = drive_step(s, "idle", s.programs["idle"].context_tokens + 30, 2, t, 0.5, 90.0)
        # "busy" cycles fast but was evicted to CPU earlier
        s.program_arrived("busy", 1, 0.0)
        s.waiting.remove(s.programs["busy"])
        s.programs["busy"].context_tokens = 50
        s.replicas[0].cpu_admit(s.programs["busy"])
        tb = 270.0  # recent busy cycles, ending just before the request
        for _ in range(4):
            s.programs["busy"].tracker.transition(Status.REASONING, tb)
            s.programs["busy"].tracker.transition(Status.ACTING, tb + 2.0)
            tb += 2.2
        s.request_arrived("busy", 50, 280.0)
        s.tick(281.0)
        assert s.programs["busy"].tier is Tier.GPU  # swapped in
        assert s.programs["idle"].tier is Tier.CPU  # swapped out

    def test_new_arrivals_admitted_smallest_first(self):
        s, _ = make(gpu=100, cpu=0, eager_promote=False)
        for pid, ctx in [("big", 70), ("small", 20), ("mid", 40)]:
            s.program_arrived(pid, 1, 0.0)
            s.request_arrived(pid, ctx, 0.0)
        s.tick(1.0)
        tiers = {p: s.programs[p].tier for p in ("small", "mid", "big")}
        assert tiers["small"] is Tier.GPU
        assert tiers["mid"] is Tier.GPU  # 20+40 <= 100
        assert tiers["big"] is Tier.WAITING


class TestLabels:
    def test_labels_follow_tiers(self):
        s, ad = make(gpu=100, cpu=1000)
        s.program_arrived("a", 1, 0.0)
        drive_step(s, "a", 90, 5, 0.0, 1.0, 60.0)
        s.tick(5.0)
        assert s.programs["a"].label is TypeLabel.BUSY
        s.replicas[0].capacity = TierCapacity(10, 1000)
        s.tick(70.0)
        assert s.programs["a"].label is TypeLabel.IDLE
        s.replicas[0].capacity = TierCapacity(10, 0)
        s.tick(71.0)
        assert s.programs["a"].label is TypeLabel.INACTIVE


class TestMultiReplica:
    def test_waiting_promotion_goes_to_most_available(self):
        s, _ = make(gpu=100, cpu=100, replicas=3, eager_promote=False)
        s.program_arrived("filler", 1, 0.0)
        s.request_arrived("filler", 60, 0.0)
        s.tick(0.5)
        filled = s.programs["filler"].replica
        s.program_arrived("x", 1, 1.0)
        s.request_arrived("x", 50, 1.0)
        s.tick(1.5)
        assert s.programs["x"].replica != filled

    def test_finished_program_frees_capacity_everywhere(self):
        s, _ = make(gpu=100, cpu=100, replicas=2)
        s.program_arrived("a", 1, 0.0)
        drive_step(s, "a", 80, 10, 0.0, 1.0, 1.0)
        rep = s.programs["a"].replica
        s.program_finished("a", 5.0)
        assert s.replicas[rep].gpu_used == 0
        assert "a" not in s.programs

    def test_replica_failure_discards_and_requeues(self):
        s, ad = make(gpu=200, cpu=200, replicas=2)
        s.program_arrived("a", 1, 0.0)
        drive_step(s, "a", 80, 10, 0.0, 1.0, 30.0)
        rep = s.programs["a"].replica
        plan = s.replica_failed(rep, 5.0)
        assert any(
            d.pid == "a" and d.tier is Tier.GPU for d in plan.of_kind(Discard)
        )
        assert s.programs["a"].tier is Tier.WAITING
        assert len(s.ledger.in_flight(replica=rep)) == 0


@given(
    seed=st.integers(0, 10_000),
    n_programs=st.integers(2, 8),
    gpu=st.integers(50, 400),
    cpu=st.integers(0, 400),
)
@settings(max_examples=60, deadline=None)
def test_property_capacity_invariants_under_random_workload(seed, n_programs, gpu, cpu):
    """After any event sequence: per-tier byte accounting is exact, no
    program is in two tiers, and GPU/CPU never exceed capacity after a tick
    (modulo lazily-demoted reasoning programs)."""
    import random

    rng = random.Random(seed)
    s, _ = make(gpu=gpu, cpu=cpu)
    t = 0.0
    active = {}
    for i in range(n_programs):
        pid = f"p{i}"
        s.program_arrived(pid, 1, t)
        active[pid] = 10 + rng.randrange(40)
    for _ in range(40):
        pid = rng.choice(list(active))
        prog = s.programs[pid]
        if prog.status in (Status.ACTING,) and not prog.has_pending:
            active[pid] += rng.randrange(20)
            s.request_arrived(pid, active[pid], t)
        elif prog.status is Status.GATED and prog.tier is Tier.GPU:
            s.notify_inference_started(pid, t)
        elif prog.status is Status.REASONING:
            out = rng.randrange(1, 15)
            active[pid] += out
            s.request_completed(pid, out, t)
        t += rng.random() * 5
        if rng.random() < 0.3:
            s.tick(t)
        for rep in s.replicas:
            rep.check()
        gpu_pids = {p for rep in s.replicas for p in rep.gpu}
        cpu_pids = {p for rep in s.replicas for p in rep.cpu}
        assert not (gpu_pids & cpu_pids)
        assert not (gpu_pids & set(s.waiting.programs))
    s.tick(t + 10)
    for rep in s.replicas:
        lazy = sum(p.kv_bytes for p in rep.gpu.values() if p.lazy_demote)
        assert rep.gpu_used - lazy <= rep.capacity.gpu_kv_bytes
        assert rep.cpu_used <= rep.capacity.cpu_kv_bytes


@given(
    seed=st.integers(0, 10_000),
    n_programs=st.integers(2, 8),
    gpu=st.integers(50, 400),
    cpu=st.integers(0, 300),
    ssd=st.integers(0, 300),
)
@settings(max_examples=60, deadline=None)
def test_property_invariants_with_ssd_tier(seed, n_programs, gpu, cpu, ssd):
    """The §7.1 SSD tier preserves every invariant of the two-tier design:
    exact byte accounting, tier exclusivity across all four placements,
    capacity bounds after a tick."""
    import random

    rng = random.Random(seed)
    s, _ = make(gpu=gpu, cpu=cpu, ssd=ssd)
    t = 0.0
    active = {}
    for i in range(n_programs):
        pid = f"p{i}"
        s.program_arrived(pid, 1, t)
        active[pid] = 10 + rng.randrange(40)
    for _ in range(40):
        pid = rng.choice(list(active))
        prog = s.programs[pid]
        if prog.status in (Status.ACTING,) and not prog.has_pending:
            active[pid] += rng.randrange(20)
            s.request_arrived(pid, active[pid], t)
        elif prog.status is Status.GATED and prog.tier is Tier.GPU:
            s.notify_inference_started(pid, t)
        elif prog.status is Status.REASONING:
            out = rng.randrange(1, 15)
            active[pid] += out
            s.request_completed(pid, out, t)
        t += rng.random() * 5
        if rng.random() < 0.3:
            s.tick(t)
        for rep in s.replicas:
            rep.check()
        placements = [
            {p for rep in s.replicas for p in rep.gpu},
            {p for rep in s.replicas for p in rep.cpu},
            {p for rep in s.replicas for p in rep.ssd},
            set(s.waiting.programs),
        ]
        for i, a in enumerate(placements):
            for b in placements[i + 1:]:
                assert not (a & b)
    s.tick(t + 10)
    for rep in s.replicas:
        lazy = sum(p.kv_bytes for p in rep.gpu.values() if p.lazy_demote)
        assert rep.gpu_used - lazy <= rep.capacity.gpu_kv_bytes
        assert rep.cpu_used <= rep.capacity.cpu_kv_bytes
        assert rep.ssd_used <= rep.capacity.ssd_kv_bytes


@pytest.mark.parametrize("name", list(SCHEDULERS))
def test_all_schedulers_run_a_small_workload(name):
    s = Driver(SCHEDULERS[name](2, TierCapacity(500, 500)))
    t = 0.0
    for i in range(3):
        s.program_arrived(f"p{i}", 1, t)
    for step in range(4):
        for i in range(3):
            pid = f"p{i}"
            if pid not in s.programs:
                continue
            prog = s.programs[pid]
            s.request_arrived(pid, prog.context_tokens + 20, t)
            if prog.tier is Tier.GPU:
                s.notify_inference_started(pid, t)
                s.request_completed(pid, 10, t + 1.0)
            t += 0.5
        s.tick(t)
        s.ack_all(t)
    for i in range(3):
        if f"p{i}" in s.programs:
            s.program_finished(f"p{i}", t)
    assert all(rep.gpu_used == 0 for rep in s.replicas)
