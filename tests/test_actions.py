"""Action IR, TransferLedger, and plan-protocol semantics.

Golden-sequence tests pin the *exact* action stream two schedulers emit on
a small scripted trace — the IR makes mock-call-order tests obsolete: a
plan is data, so a policy regression shows up as a diff against a literal.
"""
from __future__ import annotations

from hypothesis import given, settings
from hypothesis import strategies as st

import pytest

from _plan_driver import Driver
from repro.core import (
    CancelTransfer,
    Channel,
    Discard,
    Forward,
    MoriScheduler,
    Offload,
    PlacementPlan,
    SCHEDULERS,
    SchedulerConfig,
    SetLabel,
    Status,
    TAOScheduler,
    Tier,
    TierCapacity,
    TransferLedger,
    TransferRecord,
    TypeLabel,
    action_from_json,
    action_to_json,
    plan_from_json,
)


# --------------------------------------------------------------------- IR
class TestActionIR:
    def test_actions_are_frozen(self):
        act = Forward(1, "a", 0, Tier.CPU, False, 128)
        with pytest.raises(Exception):
            act.replica = 3  # type: ignore[misc]

    def test_json_roundtrip_every_kind(self):
        acts = [
            Forward(1, "a", 0, Tier.SSD, False, 64),
            Offload(2, "a", 0, Tier.GPU, Tier.CPU, 64),
            Discard(3, "a", None, Tier.CPU),
            SetLabel(4, "a", 0, TypeLabel.IDLE),
            CancelTransfer(5, "a", 0, 2),
        ]
        for act in acts:
            assert action_from_json(action_to_json(act)) == act

    def test_plan_roundtrip_and_equality(self):
        plan = PlacementPlan(3.5, (Forward(1, "a", 0), Discard(2, "a", 0, Tier.GPU)))
        again = plan_from_json(plan.now, plan.to_json())
        assert again == plan
        assert len(plan) == 2 and bool(plan)
        assert plan.of_kind(Forward) == [plan.actions[0]]

    def test_plan_coalesces_superseded_labels(self):
        s = MoriScheduler(1, TierCapacity(1000, 1000), SchedulerConfig())
        p = s.program_arrived("a", 1, 0.0)
        s._set_label(p, TypeLabel.BUSY)
        s._set_label(p, TypeLabel.IDLE)
        s._set_label(p, TypeLabel.INACTIVE)
        plan = s._drain(0.0)
        labels = plan.of_kind(SetLabel)
        assert len(labels) == 1 and labels[0].label is TypeLabel.INACTIVE


# ----------------------------------------------------------------- ledger
class TestTransferLedger:
    def rec(self, aid, pid="a", replica=0, channel=Channel.PCIE, nbytes=100,
            kind="offload"):
        return TransferRecord(aid, pid, replica, kind, channel, nbytes,
                              Tier.GPU, Tier.CPU, 0.0)

    def test_open_complete_cycle(self):
        led = TransferLedger()
        led.open(self.rec(1))
        led.open(self.rec(2, channel=Channel.NVME, nbytes=50))
        assert led.in_flight_bytes(0, Channel.PCIE) == 100
        assert led.in_flight_bytes(0, Channel.NVME) == 50
        assert led.in_flight_bytes() == 150
        assert led.complete(1).nbytes == 100
        assert led.complete(1) is None  # double-ack tolerated
        assert led.completed == 1 and led.completed_bytes[Channel.PCIE] == 100
        assert len(led) == 1

    def test_cancel_and_drop(self):
        led = TransferLedger()
        led.open(self.rec(1, pid="a"))
        led.open(self.rec(2, pid="b", replica=1))
        led.open(self.rec(3, pid="b", replica=1, kind="reload"))
        assert led.open_offload("a").action_id == 1
        assert led.cancel(1) is not None
        assert led.open_offload("a") is None
        dropped = led.drop_replica(1)
        assert {r.action_id for r in dropped} == {2, 3}
        assert len(led) == 0

    def test_drop_pid(self):
        led = TransferLedger()
        led.open(self.rec(1, pid="a"))
        led.open(self.rec(2, pid="b"))
        assert [r.pid for r in led.drop_pid("a")] == ["a"]
        assert len(led) == 1


# ------------------------------------------------------- golden sequences
def _drive_trace(sched_name: str) -> list[dict]:
    """Replay one fixed 2-program script and return the serialized stream:
    p0 runs a step and overflows the GPU during its tool call, p1 takes its
    place, capacity scales up, p0 returns."""
    d = Driver(SCHEDULERS[sched_name](
        1, TierCapacity(100, 1000), SchedulerConfig(tick_interval_s=5.0)
    ))
    d.program_arrived("p0", 1, 0.0)
    d.request_arrived("p0", 60, 0.0)           # admit + first step
    d.notify_inference_started("p0", 0.0)
    d.request_completed("p0", 50, 1.0)         # p0 -> 110 bytes: overflow
    d.tick(5.0)
    d.ack_all(5.0)                             # demotion transfer lands
    d.program_arrived("p1", 1, 6.0)
    d.request_arrived("p1", 80, 6.0)           # p1 takes the freed HBM
    d.notify_inference_started("p1", 6.0)
    d.request_completed("p1", 5, 7.0)          # p1 acting, 85 bytes
    d.sched.replicas[0].capacity = TierCapacity(250, 1000)  # scale-up
    d.request_arrived("p0", 115, 40.0)         # p0 returns from its tool call
    d.tick(45.0)
    d.ack_all(45.0)
    return [action_to_json(a) for a in d.actions]


def test_golden_sequence_mori():
    """MORI: scheduler-coordinated offload with typed labels, then an
    affinity-preserving reload on return — byte-for-byte pinned stream."""
    assert _drive_trace("mori") == [
        {"action_id": 1, "pid": "p0", "replica": 0, "label": "busy",
         "kind": "SetLabel"},
        {"action_id": 2, "pid": "p0", "replica": 0, "source_tier": "waiting",
         "recompute": True, "nbytes": 0, "kind": "Forward"},
        # growth overflow: the acting p0 demotes GPU -> CPU, restamped idle
        {"action_id": 3, "pid": "p0", "replica": 0, "src_tier": "gpu",
         "dst_tier": "cpu", "nbytes": 110, "kind": "Offload"},
        {"action_id": 4, "pid": "p0", "replica": 0, "label": "idle",
         "kind": "SetLabel"},
        {"action_id": 5, "pid": "p1", "replica": 0, "label": "busy",
         "kind": "SetLabel"},
        {"action_id": 6, "pid": "p1", "replica": 0, "source_tier": "waiting",
         "recompute": True, "nbytes": 0, "kind": "Forward"},
        # p0 returns: affinity-preserving CPU -> GPU promotion; the reload
        # moves exactly the 110 materialized bytes, not the grown context
        {"action_id": 7, "pid": "p0", "replica": 0, "label": "busy",
         "kind": "SetLabel"},
        {"action_id": 8, "pid": "p0", "replica": 0, "source_tier": "cpu",
         "recompute": False, "nbytes": 110, "kind": "Forward"},
    ]


def test_golden_sequence_tao():
    """TA+O on the same script: no typed labels, spill via uncoordinated
    HiCache, reload only because routing happened to pick replica 0."""
    assert _drive_trace("ta+o") == [
        {"action_id": 1, "pid": "p0", "replica": 0, "source_tier": "waiting",
         "recompute": True, "nbytes": 0, "kind": "Forward"},
        {"action_id": 2, "pid": "p0", "replica": 0, "src_tier": "gpu",
         "dst_tier": "cpu", "nbytes": 110, "kind": "Offload"},
        {"action_id": 3, "pid": "p1", "replica": 0, "source_tier": "waiting",
         "recompute": True, "nbytes": 0, "kind": "Forward"},
        {"action_id": 4, "pid": "p0", "replica": 0, "source_tier": "cpu",
         "recompute": False, "nbytes": 110, "kind": "Forward"},
    ]


# ------------------------------------------------------ cancel semantics
class TestCancelOnEarlyReturn:
    def _offloaded(self):
        d = Driver(MoriScheduler(1, TierCapacity(1000, 1000), SchedulerConfig()))
        d.program_arrived("a", 1, 0.0)
        d.request_arrived("a", 100, 0.0)
        d.notify_inference_started("a", 0.0)
        d.request_completed("a", 10, 1.0)
        d.sched.replicas[0].capacity = TierCapacity(10, 1000)
        d.tick(5.0)  # offload emitted, NOT acknowledged yet
        d.sched.replicas[0].capacity = TierCapacity(1000, 1000)
        assert d.programs["a"].tier is Tier.CPU
        return d

    def test_early_return_cancels_inflight_offload(self):
        d = self._offloaded()
        off = d.of_kind(Offload)[-1]
        plan = d.request_arrived("a", 110, 6.0)
        cancels = plan.of_kind(CancelTransfer)
        assert len(cancels) == 1 and cancels[0].target_action_id == off.action_id
        # re-admitted warm: no reload, no recompute
        fwd = plan.of_kind(Forward)[-1]
        assert fwd.source_tier is Tier.GPU and not fwd.recompute
        assert d.programs["a"].tier is Tier.GPU
        assert d.programs["a"].metrics.cancelled_offloads == 1
        assert len(d.sched.ledger) == 0
        d.sched.replicas[0].check()

    def test_late_return_reloads_normally(self):
        d = self._offloaded()
        d.ack_all(5.0)  # transfer completed before the tool returned
        plan = d.request_arrived("a", 110, 6.0)
        assert not plan.of_kind(CancelTransfer)
        fwd = plan.of_kind(Forward)[-1]
        assert fwd.source_tier is Tier.CPU
        assert d.programs["a"].tier is Tier.GPU

    def test_stale_ack_after_cancel_is_ignored(self):
        d = self._offloaded()
        off = d.of_kind(Offload)[-1]
        d.request_arrived("a", 110, 6.0)  # cancels
        plan = d.on_transfer_complete("a", off.action_id, 6.5)  # stale
        assert len(plan) == 0
        assert d.sched.ledger.completed == 0


# ------------------------------------------------- ack-interleaving property
@given(
    seed=st.integers(0, 10_000),
    n_programs=st.integers(2, 6),
    gpu=st.integers(60, 300),
    cpu=st.integers(0, 300),
    ack_delay=st.integers(0, 6),
)
@settings(max_examples=50, deadline=None)
def test_property_ack_interleaving_never_double_admits(
    seed, n_programs, gpu, cpu, ack_delay
):
    """Any interleaving of transfer acknowledgements — delayed, reordered,
    replayed against finished programs — never lands a program's bytes in
    two tiers at once, and the ledger never resurrects closed records."""
    import random

    rng = random.Random(seed)
    d = Driver(MoriScheduler(1, TierCapacity(gpu, cpu), SchedulerConfig()))
    t = 0.0
    active = {}
    pending_acks: list[tuple[str, int]] = []
    for i in range(n_programs):
        pid = f"p{i}"
        d.program_arrived(pid, 1, t)
        active[pid] = 10 + rng.randrange(30)

    def stage_acks():
        for rec in d.sched.ledger.in_flight():
            if (rec.pid, rec.action_id) not in pending_acks:
                pending_acks.append((rec.pid, rec.action_id))

    for _ in range(60):
        pid = rng.choice(list(active))
        prog = d.programs[pid]
        if prog.status is Status.ACTING and not prog.has_pending:
            active[pid] += rng.randrange(15)
            d.request_arrived(pid, active[pid], t)
        elif prog.status is Status.GATED and prog.tier is Tier.GPU:
            d.notify_inference_started(pid, t)
        elif prog.status is Status.REASONING:
            out = rng.randrange(1, 10)
            active[pid] += out
            d.request_completed(pid, out, t)
        t += rng.random() * 5
        if rng.random() < 0.3:
            d.tick(t)
        stage_acks()
        # deliver a random subset of pending acks, in shuffled order
        rng.shuffle(pending_acks)
        while pending_acks and rng.random() > ack_delay / 10.0:
            apid, aid = pending_acks.pop()
            d.on_transfer_complete(apid, aid, t)
        # invariants: exact accounting + tier exclusivity
        for rep in d.sched.replicas:
            rep.check()
        placements = [
            set(d.sched.replicas[0].gpu),
            set(d.sched.replicas[0].cpu),
            set(d.sched.replicas[0].ssd),
            set(d.sched.waiting.programs),
        ]
        for i, a in enumerate(placements):
            for b in placements[i + 1:]:
                assert not (a & b)
        # a ledger record always refers to a live program's single placement
        for rec in d.sched.ledger.in_flight():
            assert rec.pid in d.sched.programs
    # drain every remaining ack (plus stale duplicates) — still consistent
    stage_acks()
    for apid, aid in pending_acks + pending_acks:
        d.on_transfer_complete(apid, aid, t)
    for rep in d.sched.replicas:
        rep.check()


# ----------------------------------------------------------- migration IR
class TestMigrate:
    def _stuck_setup(self):
        d = Driver(MoriScheduler(
            2, TierCapacity(100, 200),
            SchedulerConfig(migrate_on_pressure=True, eager_promote=False),
        ))
        # hog fills one replica's GPU and stays Reasoning (not displaceable)
        d.program_arrived("hog", 1, 0.0)
        d.request_arrived("hog", 95, 0.0)
        d.tick(0.5)  # eager_promote off: admission happens on the tick
        rep0 = d.programs["hog"].replica
        d.notify_inference_started("hog", 0.5)
        # stuck lives on the same replica's CPU tier with a pending request
        d.program_arrived("stuck", 1, 0.0)
        stuck = d.programs["stuck"]
        d.sched.waiting.remove(stuck)
        stuck.context_tokens = 50
        stuck.materialized_tokens = 50
        d.sched.replicas[rep0].cpu_admit(stuck)
        d.request_arrived("stuck", 50, 1.0)
        return d, stuck, rep0

    def test_migrate_promotion_deferred_until_ack(self):
        """The promotion (a reload Forward of the same bytes) must wait for
        the migrate's on_transfer_complete — emitting it while the migrate
        record is open would double-bill the PCIe channel and forward KV
        that has not landed on the destination (regression)."""
        from repro.core import Migrate

        d, stuck, rep0 = self._stuck_setup()
        plan = d.tick(2.0)
        migs = plan.of_kind(Migrate)
        assert len(migs) == 1
        assert migs[0].src_replica == rep0 and migs[0].dst_replica != rep0
        assert stuck.replica == migs[0].dst_replica
        # the DRAM copy is still in flight: no promotion, no reload Forward
        assert stuck.tier is Tier.CPU
        assert not [f for f in plan.of_kind(Forward) if f.pid == "stuck"]
        rec = d.sched.ledger.open_migrate("stuck")
        assert rec is not None and rec.action_id == migs[0].action_id
        assert d.sched.ledger.in_flight_bytes(replica=migs[0].dst_replica) == 50
        # further ticks while the migrate is open must not promote either
        plan2 = d.tick(3.0)
        assert not [f for f in plan2.of_kind(Forward) if f.pid == "stuck"]
        assert len(d.sched.ledger.in_flight(kind="migrate")) == 1
        # ack lands the bytes: the deferred promotion opens its reload now
        plan3 = d.on_transfer_complete("stuck", migs[0].action_id, 4.0)
        assert d.sched.ledger.open_migrate("stuck") is None
        fwd = [f for f in plan3.of_kind(Forward) if f.pid == "stuck"]
        assert len(fwd) == 1 and fwd[0].source_tier is Tier.CPU
        assert stuck.tier is Tier.GPU
        # exactly one transfer open now: the reload billed after the move
        reloads = d.sched.ledger.in_flight(kind="reload")
        assert [r.pid for r in reloads] == ["stuck"]
        for rep in d.sched.replicas:
            rep.check()

    def test_migration_off_by_default(self):
        d = Driver(MoriScheduler(2, TierCapacity(100, 200), SchedulerConfig()))
        assert d.sched.config.migrate_on_pressure is False

    def test_router_rejects_migration_config(self):
        pytest.importorskip("jax")
        from repro.serving.router import MoriRouter

        with pytest.raises(ValueError, match="migrate_on_pressure"):
            MoriRouter([_FakeEngine()], config=SchedulerConfig(migrate_on_pressure=True))


class _FakeEngine:
    """Just enough surface for MoriRouter.__init__'s capacity probe."""

    class cfg:
        num_layers = 2
        num_kv_heads = 2
        head_dim = 8

    class pool:
        n_device_pages = 4
        n_host_pages = 4
        page_bytes = 1024


def test_sim_executes_migration_end_to_end():
    """Simulator smoke: migration enabled completes a run and actually
    migrates under per-replica pressure."""
    from repro.sim import Simulation, small_test_hw
    from repro.traces import generate_corpus

    corpus = generate_corpus(20, seed=3)
    hw = small_test_hw(hbm_bytes=120_000_000)
    sim = Simulation(
        "mori", hw, corpus, num_replicas=2, concurrency_per_replica=8,
        duration_s=200.0, warmup_s=20.0, seed=0,
        sched_config=SchedulerConfig(migrate_on_pressure=True),
    )
    r = sim.run()
    assert r.steps_completed > 50
    for rep in sim.sched.replicas:
        rep.check()


def test_tao_offload_is_ledger_tracked():
    d = Driver(TAOScheduler(1, TierCapacity(100, 1000), SchedulerConfig()))
    d.program_arrived("a", 1, 0.0)
    d.request_arrived("a", 60, 0.0)
    d.notify_inference_started("a", 0.0)
    d.request_completed("a", 50, 1.0)  # grows past capacity: HiCache spill
    offs = d.of_kind(Offload)
    assert offs and offs[-1].pid == "a"
    assert d.sched.ledger.open_offload("a") is not None
    d.ack_all(2.0)
    assert len(d.sched.ledger) == 0
