import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input-shape) cell on the production
meshes — 16x16 single pod and 2x16x16 multi-pod — and records
memory_analysis / cost_analysis / collective traffic per cell into a JSON
artifact that §Roofline and §Perf read.

The XLA_FLAGS line above MUST stay the first statement: jax locks the
device count on first init. Do not import jax (directly or transitively)
before it.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # everything
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-9b \
        --shape decode_32k --mesh single
    ... --skip-existing     # resume into artifacts/dryrun.json
"""
import argparse
import gzip
import json
import time
import traceback
from pathlib import Path

import jax  # noqa: F401  (deliberate early init: locks device count under XLA_FLAGS)

from repro.configs import ARCH_IDS, get_config
from repro.launch.hlo_cost import analyze as analyze_hlo
from repro.launch.hlo_cost import parse_input_output_alias
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell, cell_skip_reason
from repro.models.config import SHAPES

ARTIFACT = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun.json"

MESHES = {"single": False, "multi": True}


def run_cell(
    arch: str, shape: str, mesh_name: str, *,
    hlo_dir: Path | None = None, key: str = "", vmem_budget: int = 0,
    **build_kw,
) -> dict:
    cfg = get_config(arch)
    reason = cell_skip_reason(cfg, shape)
    if reason:
        return {"status": "skipped", "reason": reason}
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=MESHES[mesh_name])
    cell = build_cell(arch, shape, mesh, **build_kw)
    lowered = cell.lower()
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    peak = mem.peak_memory_in_bytes
    cost = compiled.cost_analysis()  # NOTE: counts while bodies ONCE
    hlo_text = compiled.as_text()
    if hlo_dir is not None and key:
        hlo_dir.mkdir(parents=True, exist_ok=True)
        with gzip.open(hlo_dir / (key.replace("|", "__") + ".hlo.gz"), "wt") as f:
            f.write(hlo_text)
    hlo = analyze_hlo(hlo_text, vmem_budget=vmem_budget)  # loop-aware
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    return {
        "status": "ok",
        "kind": cell.kind,
        "mesh": mesh_name,
        "devices": n_dev,
        "tokens_per_step": cell.meta.get("tokens_per_step"),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": peak,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        # (output, param) pairs XLA actually aliased — donation requests
        # the compiler dropped show up as alias_bytes lower than the
        # carry footprint; the pair count makes that auditable per cell
        "honored_aliases": len(parse_input_output_alias(hlo_text)),
        "xla_cost_once": {  # raw XLA numbers, loop bodies counted once
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "cost": {  # loop-aware per-device totals (TPU-target normalized)
            "flops": hlo.flops,
            "hbm_bytes": hlo.hbm_bytes,
            "hbm_bytes_raw": hlo.hbm_bytes_raw,  # CPU-backend f32-promoted
        },
        "collectives": hlo.to_dict(),
        "fallbacks": sorted(set(map(tuple, cell.rules.fallbacks))),
    }


def reanalyze(
    results: dict, out_path: Path, archs, shapes, meshes, *,
    src_tag: str = "", vmem_budget: int = 0, assume_donation: bool = False,
) -> None:
    """Recompute cost/collectives from saved HLO (no recompile). With
    accounting levers on, results land under a derived tag
    (``vmem<N>m``/``donate``) so the baseline rows stay; with none, the
    base record is updated in place (accounting-fidelity fixes)."""
    hlo_dir = out_path.parent / "hlo"
    lever = []
    if vmem_budget:
        lever.append(f"vmem{vmem_budget >> 20}m")
    if assume_donation:
        lever.append("donate")
    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                base = f"{arch}|{shape}|{mesh_name}"
                src = base + (f"|{src_tag}" if src_tag else "")
                rec = results.get(src)
                if not rec or rec.get("status") != "ok":
                    continue
                f = hlo_dir / (src.replace("|", "__") + ".hlo.gz")
                if not f.exists():
                    print(f"  {src}: no saved HLO, skipping")
                    continue
                with gzip.open(f, "rt") as fh:
                    hlo = analyze_hlo(
                        fh.read(), vmem_budget=vmem_budget,
                        assume_donation=assume_donation,
                    )
                dst = src + ("|" + "+".join(lever) if lever else "")
                new = dict(rec)
                new["cost"] = {
                    "flops": hlo.flops,
                    "hbm_bytes": hlo.hbm_bytes,
                    "hbm_bytes_raw": hlo.hbm_bytes_raw,
                }
                new["collectives"] = hlo.to_dict()
                results[dst] = new
                print(
                    f"  {dst}: hbm {hlo.hbm_bytes/2**30:.1f} GiB, "
                    f"wire {hlo.total_wire_bytes/2**30:.2f} GiB"
                )
    out_path.write_text(json.dumps(results, indent=1, sort_keys=True))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=str(ARTIFACT))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--window-limited-cache", action="store_true",
                    help="§Perf lever: gemma2 local layers cache only the window")
    ap.add_argument("--sequence-parallel", action="store_true",
                    help="§Perf lever: shard train activations over 'model' on seq")
    ap.add_argument("--pad-heads", action="store_true",
                    help="§Perf lever: pad q heads to the model-axis size "
                         "(zero-weight heads; exact) so attention shards")
    ap.add_argument("--tag", default="", help="suffix for result keys (perf runs)")
    ap.add_argument("--save-hlo", action="store_true",
                    help="save compiled HLO (gz) under artifacts/hlo/ for "
                         "re-analysis without recompiling")
    ap.add_argument("--vmem-budget", type=int, default=0,
                    help="§Perf lever: while-body temporaries <= this many "
                         "bytes stay in VMEM (Pallas-kernel accounting)")
    ap.add_argument("--assume-donation", action="store_true",
                    help="§Perf lever: entry copies/zero-inits of donated "
                         "carries alias away on the TPU target")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute cost/collectives from saved HLO "
                         "(artifacts/hlo/) without recompiling")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results: dict = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    build_kw = {}
    if args.window_limited_cache:
        build_kw["window_limited_cache"] = True
    if args.sequence_parallel:
        build_kw["sequence_parallel"] = True
    if args.pad_heads:
        build_kw["pad_heads"] = True

    if args.reanalyze:
        reanalyze(
            results, out_path, archs, shapes, meshes,
            src_tag=args.tag, vmem_budget=args.vmem_budget,
            assume_donation=args.assume_donation,
        )
        return

    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                key = f"{arch}|{shape}|{mesh_name}"
                if args.tag:
                    key += f"|{args.tag}"
                if args.skip_existing and results.get(key, {}).get("status") in (
                    "ok",
                    "skipped",
                ):
                    continue
                print(f"=== {key} ===", flush=True)
                try:
                    rec = run_cell(
                        arch, shape, mesh_name,
                        hlo_dir=(out_path.parent / "hlo") if args.save_hlo else None,
                        key=key,
                        vmem_budget=args.vmem_budget,
                        **build_kw,
                    )
                except Exception as e:  # a failure here is a bug in our system
                    rec = {
                        "status": "error",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                results[key] = rec
                out_path.write_text(json.dumps(results, indent=1, sort_keys=True))
                if rec["status"] == "ok":
                    m = rec["memory"]
                    print(
                        f"  ok ({rec['kind']}): compile {rec['compile_s']}s, "
                        f"peak/dev {m['peak_bytes']/2**30:.2f} GiB, "
                        f"args/dev {m['argument_bytes']/2**30:.2f} GiB, "
                        f"flops/dev {rec['cost']['flops']:.3e}, "
                        f"wire/dev {rec['collectives']['total_wire_bytes']/2**20:.2f} MiB",
                        flush=True,
                    )
                else:
                    print(f"  {rec['status']}: {rec.get('reason', rec.get('error'))}",
                          flush=True)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
