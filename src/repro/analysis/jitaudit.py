"""JITAUDIT — static auditor over the hot-path jits' jaxprs and HLO.

The serving numbers only hold while three compile-plane properties do,
none of which ordinary tests observe:

1. **donation** — ``donate_argnums`` on the decode/chunk fns is what
   makes the KV-pool scatter an in-place update.  A donation XLA cannot
   honor (output dtype/shape drifted from the donated input) degrades
   silently to a full pool copy per step; jax prints a warning once and
   the replay still passes every token-equivalence test.
2. **recompile budget** — the pump dispatches from jit's cache; one
   unbucketed shape mid-replay stalls every live slot for a full XLA
   compile.  After ``Engine.warmup()`` a replay must compile nothing.
3. **static roofline** — scheduling policy (and the paper's idle-window
   model) assumes per-step FLOPs/bytes that nobody re-derives when the
   model or kernels change.

This module audits all three *statically*, against what jit actually
traced and XLA actually compiled:

* **donation verifier** — counts the donated array leaves a target
  requests, the ``tf.aliasing_output`` marks the lowered StableHLO
  kept, and the ``input_output_alias`` pairs the compiled module
  honors; any narrowing step is a violation with the dropped avals.
* **retrace-hazard scan** — weak-typed invars (a Python scalar at the
  call site retraces per value-type), closure-captured arrays baked in
  as jaxpr constants (pool snapshots frozen at trace time), and
  structural probes: two same-rank bucket shapes must trace to the
  same primitive sequence, else some Python branch is shape-dependent
  and every new bucket is a surprise recompile.
* **static roofline** — a loop-aware jaxpr walk (scan bodies multiply
  by trip count) tallying dot FLOPs and touched HBM bytes per bucket,
  cross-checked against ``compiled.cost_analysis()`` (XLA's own count,
  while-bodies once, whole-operand bytes) and
  :func:`repro.launch.hlo_cost.analyze` (loop- and utilization-aware);
  ratios outside the documented bands fail the audit.  Emitted as
  ``artifacts/STATIC_roofline.json``.

CLI (the CI ``compile-audit`` job)::

    PYTHONPATH=src python -m repro.analysis.jitaudit \
        --out artifacts/STATIC_roofline.json

audits the engine warmup set (dense + paged + chunked prefill) and the
three kernel dispatches, runs the seeded-violation selftest (a broken
donation and a shape-branching fn MUST be caught — the auditor audits
itself), then replays a small corpus through the pump under the compile
tracker and fails on any post-warmup compile.  Exit 1 on violations.

Tolerance bands (documented, asserted, and recorded in the JSON):

=================  ============  =========================================
ratio              band          why it is loose/tight
=================  ============  =========================================
flops vs hlo_cost  [0.65, 1.60]  both sides are loop-aware dot counts;
                                 disagreement means a lowering rewrote
                                 contractions (calibrated: 1.00 +- 0.01)
flops vs XLA       [0.25, 4.00]  cost_analysis() loop conventions vary by
                                 program — an unrolled scan counts fully,
                                 a while body once (observed 0.9x-3.4x on
                                 this repo's hot paths)
bytes vs hlo_cost  [0.25, 4.00]  different fusion/utilization judgments
bytes vs XLA       [0.01, 1.05]  XLA charges whole operands per op; the
                                 static walk charges touched bytes, so it
                                 must be a lower bound (paged gathers read
                                 pages, not the pool)
=================  ============  =========================================
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import warnings
from dataclasses import dataclass, field

import numpy as np

#: ratio bands, static/reference (see module docstring table)
TOLERANCES = {
    "flops_vs_hlo_cost": (0.65, 1.60),
    "flops_vs_xla": (0.25, 4.00),
    "bytes_vs_hlo_cost": (0.25, 4.00),
    "bytes_vs_xla": (0.01, 1.05),
}

#: a jaxpr constant bigger than this is a baked-in closure capture, not a
#: scalar config value (the pool is megabytes; epsilons are bytes)
CONST_BYTES_LIMIT = 512


@dataclass
class AuditTarget:
    """One jitted hot-path function with example (bucket) arguments.

    ``make_args`` builds the sample call lazily — donation-adjacent
    buffers (the pool view) must be read at trace time, not target-
    construction time.  ``probe_args``, when given, builds a *second*
    bucket shape in the same branch class; the hazard pass asserts both
    trace to the same primitive structure.
    """

    name: str
    fn: object
    make_args: object
    donate_argnums: tuple = ()
    static_argnums: tuple = ()
    bucket: dict = field(default_factory=dict)
    probe_args: object = None


@dataclass
class AuditViolation:
    target: str
    pass_name: str                # donation | retrace-hazard | roofline
    msg: str
    provenance: str = ""

    def __str__(self) -> str:
        s = f"[{self.pass_name}] {self.target}: {self.msg}"
        if self.provenance:
            s += f"\n    provenance: {self.provenance}"
        return s


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------
def trace_target(target: AuditTarget):
    """AOT-trace ``target`` (no execution, no buffer donation) and return
    ``(traced, lowered, compiled, captured_warnings)``."""
    args = target.make_args()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        traced = target.fn.trace(*args)
        lowered = traced.lower()
        compiled = lowered.compile()
    notes = [str(w.message) for w in caught if "donated" in str(w.message)]
    return traced, lowered, compiled, notes


def donated_leaf_count(target: AuditTarget) -> int:
    """Array leaves under the donated argument positions of the sample
    call — what the lowering must mark with ``tf.aliasing_output``."""
    import jax

    args = target.make_args()
    return sum(
        len(jax.tree.leaves(args[i]))
        for i in target.donate_argnums
        if i < len(args)
    )


# --------------------------------------------------------------------------
# pass 1: donation verifier
# --------------------------------------------------------------------------
_MLIR_ALIAS_RE = re.compile(
    r"%arg(\d+):\s*tensor<[^>]*>\s*(?:loc\([^)]*\)\s*)?\{([^}]*)\}"
)
_ALIAS_OUT_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_DONOR_RE = re.compile(r"jax\.buffer_donor\s*=\s*true")


def donation_marks(mlir_text: str) -> dict[int, int]:
    """``{arg_index: output_index}`` for every ``tf.aliasing_output`` mark
    in the lowered module's ``@main`` signature — donations jit kept."""
    start = mlir_text.find("@main(")
    if start < 0:
        start = 0
    # the signature ends at the return-type arrow; scanning to the first
    # function body brace would also work but the arrow is unambiguous
    end = mlir_text.find("->", start)
    sig = mlir_text[start:end if end > 0 else len(mlir_text)]
    out: dict[int, int] = {}
    for m in _MLIR_ALIAS_RE.finditer(sig):
        alias = _ALIAS_OUT_RE.search(m.group(2))
        if alias:
            out[int(m.group(1))] = int(alias.group(1))
    return out


def unmatched_donors(mlir_text: str) -> list[int]:
    """Arg indices marked ``jax.buffer_donor`` (donated, but jit found no
    shape/dtype-compatible output to alias them into)."""
    start = mlir_text.find("@main(")
    end = mlir_text.find("->", max(start, 0))
    sig = mlir_text[max(start, 0):end if end > 0 else len(mlir_text)]
    return [
        int(m.group(1))
        for m in _MLIR_ALIAS_RE.finditer(sig)
        if _DONOR_RE.search(m.group(2))
    ]


def verify_donation(target: AuditTarget, lowered, compiled,
                    notes: list[str]) -> list[AuditViolation]:
    """Every donated leaf must survive lowering (``tf.aliasing_output``)
    and compilation (``input_output_alias``)."""
    if not target.donate_argnums:
        return []
    from repro.launch.hlo_cost import parse_input_output_alias

    expected = donated_leaf_count(target)
    marks = donation_marks(lowered.as_text())
    honored = parse_input_output_alias(compiled.as_text())
    out: list[AuditViolation] = []
    if len(marks) < expected:
        dropped = unmatched_donors(lowered.as_text())
        out.append(AuditViolation(
            target.name, "donation",
            f"{expected - len(marks)} of {expected} donated buffers were "
            f"dropped at lowering — no output shares their shape/dtype, "
            f"so each costs a full copy per call",
            provenance=(
                f"donate_argnums={target.donate_argnums}, lowered marks "
                f"args {sorted(marks)} -> outputs "
                f"{sorted(marks.values())}; unmatched donor args "
                f"{dropped}; jax: {notes or 'no warning captured'}"
            ),
        ))
    # compiled honoring: every lowered mark must appear as an alias pair
    honored_outs = {o for o, _ in honored}
    lost = sorted(set(marks.values()) - honored_outs)
    if lost:
        out.append(AuditViolation(
            target.name, "donation",
            f"lowered donation marks for output(s) {lost} were not honored "
            f"by XLA (missing from the compiled input_output_alias map)",
            provenance=f"compiled aliases: {sorted(honored)}",
        ))
    return out


# --------------------------------------------------------------------------
# pass 2: retrace hazards
# --------------------------------------------------------------------------
def _walk_prims(jaxpr, out: list[str]) -> None:
    """Flatten a jaxpr's primitive sequence, recursing into sub-jaxprs in
    a deterministic order (the structural fingerprint for probes)."""
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        name = eqn.primitive.name
        if name == "scan":
            _walk_prims(eqn.params["jaxpr"].jaxpr, out)
        elif name == "while":
            _walk_prims(eqn.params["body_jaxpr"].jaxpr, out)
        elif name == "cond":
            for br in eqn.params["branches"]:
                _walk_prims(br.jaxpr, out)
        else:
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                _walk_prims(getattr(sub, "jaxpr", sub), out)


def prim_signature(closed) -> list[str]:
    out: list[str] = []
    _walk_prims(closed.jaxpr, out)
    return out


def retrace_hazards(target: AuditTarget, traced) -> list[AuditViolation]:
    out: list[AuditViolation] = []
    closed = traced.jaxpr
    # (a) weak-typed invars: a Python scalar at the call site — the next
    # call with a different Python type (or a strong array) retraces
    weak = [
        (i, str(v.aval))
        for i, v in enumerate(closed.jaxpr.invars)
        if getattr(v.aval, "weak_type", False)
    ]
    if weak:
        out.append(AuditViolation(
            target.name, "retrace-hazard",
            f"{len(weak)} weak-typed invar(s) — a Python scalar reached the "
            f"jit boundary; pass a committed array so dtype promotion "
            f"cannot retrace",
            provenance=f"invars {weak}",
        ))
    # (b) closure-captured arrays baked in as constants: a pool snapshot
    # frozen at trace time is both a staleness bug and a retrace per object
    for var, const in zip(closed.jaxpr.constvars, closed.consts):
        nbytes = getattr(const, "nbytes", None)
        if nbytes is None:
            nbytes = np.asarray(const).nbytes
        if nbytes > CONST_BYTES_LIMIT:
            out.append(AuditViolation(
                target.name, "retrace-hazard",
                f"closure-captured array baked into the jaxpr as a "
                f"constant ({nbytes} bytes > {CONST_BYTES_LIMIT}) — pass "
                f"it as an argument instead",
                provenance=f"constvar {var} : {var.aval}",
            ))
    # (c) structural probe: a second bucket shape in the same branch class
    # must trace to the same primitive sequence
    if target.probe_args is not None:
        sig_a = prim_signature(closed)
        sig_b = prim_signature(target.fn.trace(*target.probe_args()).jaxpr)
        if sig_a != sig_b:
            div = next(
                (i for i, (a, b) in enumerate(zip(sig_a, sig_b)) if a != b),
                min(len(sig_a), len(sig_b)),
            )
            ctx_a = sig_a[max(0, div - 2):div + 3]
            ctx_b = sig_b[max(0, div - 2):div + 3]
            out.append(AuditViolation(
                target.name, "retrace-hazard",
                "primitive structure differs between two bucket shapes — "
                "a Python branch depends on the shape, so every bucket "
                "compiles a different program",
                provenance=(
                    f"diverges at eqn {div}: {ctx_a} vs {ctx_b} "
                    f"(lengths {len(sig_a)} vs {len(sig_b)})"
                ),
            ))
    return out


# --------------------------------------------------------------------------
# pass 3: static roofline
# --------------------------------------------------------------------------
#: primitives charged 2 x output bytes (read the touched region, write or
#: forward the result) — mirrors hlo_cost's slice-utilization convention
_GATHERISH = frozenset({"gather", "dynamic_slice", "slice"})
#: primitives charged 2 x update bytes (in-place touched region)
_SCATTERISH = frozenset({"scatter", "scatter-add", "dynamic_update_slice"})
#: primitives charged operand + output bytes (real data movement)
_READWRITE = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "argmax",
    "argmin", "concatenate", "sort", "cumsum", "cumlogsumexp",
})


def _aval_bytes(v) -> int:
    aval = v.aval
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape \
        else dtype.itemsize


@dataclass
class StaticCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    eqns: int = 0


def static_cost(closed, *, loop_aware: bool = True) -> StaticCost:
    """Loop-aware FLOPs/bytes from a ClosedJaxpr.

    FLOPs: dot_general only (2 x out_elems x contraction), matching both
    references' dominant term.  Bytes: touched-region model — gathers and
    slices move their *output*, scatters their *update*, dots their
    operands and result; elementwise/layout ops fuse for free on the TPU
    target.  ``loop_aware=False`` reproduces XLA's count-the-body-once
    convention for cross-checking against ``cost_analysis()``.
    """
    acc = StaticCost()

    def walk(jaxpr, mult: float) -> None:
        for eqn in jaxpr.eqns:
            acc.eqns += 1
            name = eqn.primitive.name
            if name == "scan":
                body_mult = mult * (eqn.params["length"] if loop_aware else 1)
                walk(eqn.params["jaxpr"].jaxpr, body_mult)
                continue
            if name == "while":
                walk(eqn.params["body_jaxpr"].jaxpr, mult)
                continue
            if name == "cond":
                # max over branches (the compiled program pays for the
                # branch it takes; buckets should make them equal anyway)
                best: StaticCost | None = None
                for br in eqn.params["branches"]:
                    saved = StaticCost(acc.flops, acc.hbm_bytes, acc.eqns)
                    walk(br.jaxpr, mult)
                    cand = StaticCost(acc.flops, acc.hbm_bytes, acc.eqns)
                    acc.flops, acc.hbm_bytes, acc.eqns = (
                        saved.flops, saved.hbm_bytes, saved.eqns)
                    if best is None or cand.flops > best.flops:
                        best = cand
                if best is not None:
                    acc.flops, acc.hbm_bytes, acc.eqns = (
                        best.flops, best.hbm_bytes, best.eqns)
                continue
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
            if sub is not None:
                walk(getattr(sub, "jaxpr", sub), mult)
                continue
            if name == "dot_general":
                (lc, _), _ = eqn.params["dimension_numbers"]
                lhs = eqn.invars[0].aval
                contract = 1
                for d in lc:
                    contract *= lhs.shape[d]
                out_elems = int(np.prod(
                    eqn.outvars[0].aval.shape, dtype=np.int64))
                acc.flops += 2.0 * out_elems * max(1, contract) * mult
                acc.hbm_bytes += mult * (
                    sum(_aval_bytes(v) for v in eqn.invars[:2])
                    + _aval_bytes(eqn.outvars[0])
                )
            elif name in _GATHERISH:
                acc.hbm_bytes += 2 * mult * sum(
                    _aval_bytes(o) for o in eqn.outvars)
            elif name in _SCATTERISH:
                idx = 1 if name == "dynamic_update_slice" else 2
                upd = (eqn.invars[idx] if len(eqn.invars) > idx
                       else eqn.outvars[0])
                acc.hbm_bytes += 2 * mult * _aval_bytes(upd)
            elif name in _READWRITE:
                acc.hbm_bytes += mult * (
                    sum(_aval_bytes(v) for v in eqn.invars)
                    + sum(_aval_bytes(o) for o in eqn.outvars)
                )
            # remaining elementwise/layout/metadata ops: fused, free

    walk(closed.jaxpr, 1.0)
    return acc


def roofline_row(target: AuditTarget, traced, compiled) -> dict:
    """One STATIC_roofline.json row: the static walk next to both
    references, with the gated ratios."""
    from repro.launch.hlo_cost import analyze as hlo_analyze

    st = static_cost(traced.jaxpr)
    ca = compiled.cost_analysis()
    hc = hlo_analyze(compiled.as_text())

    def ratio(a: float, b: float) -> float:
        return a / b if b else float("inf")

    xla_flops = float(ca.get("flops", 0.0))
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    return {
        "target": target.name,
        "bucket": target.bucket,
        "static": {"flops": st.flops, "hbm_bytes": st.hbm_bytes,
                   "eqns": st.eqns},
        "xla_cost_analysis": {"flops": xla_flops,
                              "bytes_accessed": xla_bytes},
        "hlo_cost": {"flops": hc.flops, "hbm_bytes": hc.hbm_bytes},
        "ratios": {
            "flops_vs_hlo_cost": ratio(st.flops, hc.flops),
            "flops_vs_xla": ratio(st.flops, xla_flops),
            "bytes_vs_hlo_cost": ratio(st.hbm_bytes, hc.hbm_bytes),
            "bytes_vs_xla": ratio(st.hbm_bytes, xla_bytes),
        },
    }


def check_roofline(target: AuditTarget, row: dict) -> list[AuditViolation]:
    out: list[AuditViolation] = []
    for key, (lo, hi) in TOLERANCES.items():
        r = row["ratios"][key]
        # a reference reporting 0 for a non-trivial program (some backends
        # omit cost fields) is a skip, not a violation
        if r == float("inf"):
            continue
        if not (lo <= r <= hi):
            out.append(AuditViolation(
                target.name, "roofline",
                f"static/{key.split('_vs_')[1]} ratio {r:.3f} outside "
                f"documented band [{lo}, {hi}] for metric "
                f"{key.split('_vs_')[0]}",
                provenance=json.dumps(row["ratios"]),
            ))
    return out


# --------------------------------------------------------------------------
# target construction
# --------------------------------------------------------------------------
def engine_targets(engine, *, prefill_chunks: bool = True) -> list[AuditTarget]:
    """Audit targets for every shape ``Engine.warmup`` precompiles,
    with structural probes paired inside each warmup probe group."""
    specs = engine.warmup_specs(prefill_chunks=prefill_chunks)
    by_group: dict[str, list] = {}
    for s in specs:
        by_group.setdefault(s.probe_group, []).append(s)
    out: list[AuditTarget] = []
    for group in by_group.values():
        for i, s in enumerate(group):
            probe = group[i + 1].make_args if i + 1 < len(group) else None
            out.append(AuditTarget(
                name=s.name,
                fn=getattr(engine, s.fn_name),
                make_args=s.make_args,
                donate_argnums=s.donate_argnums,
                static_argnums=s.static_argnums,
                bucket=dict(s.bucket),
                probe_args=probe,
            ))
    return out


def kernel_targets() -> list[AuditTarget]:
    """The three kernel dispatch entry points at example bucket shapes
    (each ops module owns its shapes via ``audit_spec()``)."""
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.paged_attention import ops as paged_ops
    from repro.kernels.ssd import ops as ssd_ops

    out: list[AuditTarget] = []
    for mod in (paged_ops, flash_ops, ssd_ops):
        spec = mod.audit_spec()
        out.append(AuditTarget(
            name=spec["name"],
            fn=spec["fn"],
            make_args=spec["make_args"],
            bucket=spec.get("bucket", {}),
            probe_args=spec.get("probe_args"),
        ))
    return out


def audit(targets: list[AuditTarget]) -> tuple[list[dict], list[AuditViolation]]:
    """All three static passes over ``targets``; returns (roofline rows,
    violations)."""
    rows: list[dict] = []
    violations: list[AuditViolation] = []
    for t in targets:
        traced, lowered, compiled, notes = trace_target(t)
        violations += verify_donation(t, lowered, compiled, notes)
        violations += retrace_hazards(t, traced)
        row = roofline_row(t, traced, compiled)
        rows.append(row)
        violations += check_roofline(t, row)
    return rows, violations


# --------------------------------------------------------------------------
# seeded-violation selftest: the auditor must catch planted bugs
# --------------------------------------------------------------------------
def selftest() -> list[str]:
    """Plant one instance of each bug class in throwaway fns and assert
    the corresponding pass fires; returns failure descriptions (empty ==
    the auditor still detects what it claims to detect)."""
    import jax
    import jax.numpy as jnp

    failures: list[str] = []

    # (a) broken donation: the donated buffer's dtype drifts from every
    # output, so the alias request cannot be honored
    k = jnp.zeros((8, 16), jnp.bfloat16)

    def args():
        return (jnp.float32(1.0), k, k + 1)

    broken = AuditTarget(
        "selftest-donation-broken",
        jax.jit(lambda s, a, b: (a.astype(jnp.float32) * s, b),
                donate_argnums=(1, 2)),
        args, donate_argnums=(1, 2))
    _, lo, co, notes = trace_target(broken)
    if not verify_donation(broken, lo, co, notes):
        failures.append("donation verifier missed a dtype-broken donation")

    # NB the scale multiplies in the donated dtype — `a * jnp.float32(s)`
    # would promote output 0 to f32 and (correctly) break the donation
    intact = AuditTarget(
        "selftest-donation-ok",
        jax.jit(lambda s, a, b: (a * s.astype(a.dtype), b + 1),
                donate_argnums=(1, 2)),
        args, donate_argnums=(1, 2))
    _, lo, co, notes = trace_target(intact)
    if verify_donation(intact, lo, co, notes):
        failures.append("donation verifier false-positived on an honored "
                        "donation")

    # (b) shape-branching fn: adjacent buckets trace different programs
    def branchy(x):
        if x.shape[0] > 8:  # lint: jit-shape-branch-ok — seeded violation
            return x * 2
        return x + 1

    hazard = AuditTarget(
        "selftest-shape-branch", jax.jit(branchy),
        lambda: (jnp.zeros(8),), probe_args=lambda: (jnp.zeros(16),))
    tr = hazard.fn.trace(*hazard.make_args())
    if not any(v.pass_name == "retrace-hazard"
               for v in retrace_hazards(hazard, tr)):
        failures.append("hazard scan missed a shape-dependent branch")

    # (c) closure-captured pool baked in as a constant
    pool = jnp.zeros((64, 64), jnp.float32)
    baked = AuditTarget(
        "selftest-baked-const", jax.jit(lambda x: x + pool[0]),
        lambda: (jnp.zeros(64),))
    tr = baked.fn.trace(*baked.make_args())
    if not any("constant" in v.msg for v in retrace_hazards(baked, tr)):
        failures.append("hazard scan missed a closure-captured array")

    # (d) weak-typed invar from a Python scalar
    weak = AuditTarget(
        "selftest-weak-type", jax.jit(lambda a, b: a * b),
        lambda: (3.0, jnp.zeros(4)))
    tr = weak.fn.trace(*weak.make_args())
    if not any("weak" in v.msg for v in retrace_hazards(weak, tr)):
        failures.append("hazard scan missed a weak-typed invar")
    return failures


# --------------------------------------------------------------------------
# replay gate: zero post-warmup compiles through the real pump
# --------------------------------------------------------------------------
def replay_gate(cfg, params, *, max_seq: int = 128,
                page_tokens: int = 16, log=print) -> dict:
    """Warm a paged engine, mark the compile tracker, push a small corpus
    through the chunked-prefill decode pump, and return the tracker's
    verdict (raises via the router's end-of-replay hook on violations)."""
    from repro.analysis.compile_tracker import get_tracker
    from repro.core.types import ProgramTrace, RequestRecord
    from repro.serving import Engine, MoriRouter

    os.environ[_tracker_env()] = "1"
    tracker = get_tracker()
    with tracker.phase("engine-build"):
        engine = Engine(
            cfg, params, page_tokens=page_tokens, n_device_pages=96,
            n_host_pages=64, max_slots=2, max_seq=max_seq,
        )
    with tracker.phase("warmup"):
        engine.warmup(prefill_chunks=True)
    router = MoriRouter(
        [engine], scheduler="mori",
        gpu_capacity_bytes=engine.radix_device_pages * engine.pool.page_bytes,
        chunked_prefill=True,
    )
    corpus = [
        ProgramTrace(f"audit-p{p}", [
            RequestRecord(input_tokens=24 + 13 * p + 7 * s, output_tokens=4,
                          tool_duration_s=0.0 if s == 2 else 5.0,
                          reasoning_wall_s=0.0)
            for s in range(3)
        ])
        for p in range(3)
    ]
    with tracker.phase("replay"):
        # the router's end-of-replay hook raises on post-warmup compiles
        router.replay(corpus, vocab_size=cfg.vocab_size, max_new_tokens=4)
    verdict = {
        "post_warmup_compiles": tracker.post_warmup_compiles(),
        "cache_sizes": tracker.cache_sizes(),
        "backend_compiles_by_phase": {
            ph: len(tracker.events_in(ph))
            for ph in ("engine-build", "warmup", "replay")
        },
    }
    log(f"replay gate: cache sizes {verdict['cache_sizes']}, "
        f"backend compiles by phase "
        f"{verdict['backend_compiles_by_phase']}")
    return verdict


def _tracker_env() -> str:
    from repro.analysis.compile_tracker import ENV_VAR

    return ENV_VAR


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.jitaudit",
        description="static compile-plane audit: donation verification, "
                    "retrace hazards, recompile budget, static roofline",
    )
    ap.add_argument("--model", default="qwen1.5-0.5b")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--out", default="artifacts/STATIC_roofline.json")
    ap.add_argument("--skip-replay", action="store_true",
                    help="skip the pump-replay recompile-budget gate")
    ap.add_argument("--skip-selftest", action="store_true",
                    help="skip the seeded-violation selftest")
    args = ap.parse_args(argv)

    from repro.configs import get_config
    from repro.models import Model, materialize
    from repro.serving import Engine

    cfg = get_config(args.model).reduced()
    params = materialize(Model(cfg).describe(), seed=0)

    failures: list[str] = []
    if not args.skip_selftest:
        failures = selftest()
        for f in failures:
            print(f"SELFTEST FAIL: {f}")
        if not failures:
            print("selftest: 4 seeded violation classes all detected")

    paged = Engine(cfg, params, page_tokens=args.page_tokens,
                   n_device_pages=96, n_host_pages=64, max_slots=2,
                   max_seq=args.max_seq)
    dense = Engine(cfg, params, page_tokens=args.page_tokens,
                   n_device_pages=8, n_host_pages=8, max_slots=2,
                   max_seq=64, dense_slots=True)
    targets = (engine_targets(paged, prefill_chunks=True)
               + engine_targets(dense, prefill_chunks=False)
               + kernel_targets())
    print(f"auditing {len(targets)} jit targets "
          f"({args.model} reduced, max_seq={args.max_seq})")
    rows, violations = audit(targets)
    for v in violations:
        print(v)

    report = {
        "generated_by": "repro.analysis.jitaudit",
        "model": args.model,
        "geometry": {"max_seq": args.max_seq,
                     "page_tokens": args.page_tokens},
        "tolerances": {k: list(v) for k, v in TOLERANCES.items()},
        "targets": rows,
        "violations": [
            {"target": v.target, "pass": v.pass_name, "msg": v.msg,
             "provenance": v.provenance}
            for v in violations
        ],
        "selftest_failures": failures,
    }
    if not args.skip_replay:
        report["replay"] = replay_gate(
            cfg, params, max_seq=args.max_seq, page_tokens=args.page_tokens)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, default=str)
        print(f"wrote {args.out} ({len(rows)} roofline rows)")
    ok = not violations and not failures
    print("jitaudit: " + ("clean" if ok else
                          f"{len(violations)} violation(s), "
                          f"{len(failures)} selftest failure(s)"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
