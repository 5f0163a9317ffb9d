import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax (device count is now locked at 512) -----

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
from pathlib import Path # noqa: E402

import jax               # noqa: E402

from repro.core import ARCH_IDS, INPUT_SHAPES, ParallelPlan, SHAPES_BY_NAME  # noqa: E402
from repro.core.config import Family  # noqa: E402
from repro.launch.cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.stepbuilder import build_step, jit_step, resolve_config, skip_reason  # noqa: E402
from repro.perf import Roofline, model_flops_for  # noqa: E402
from repro.perf.hlo_cost import analyze_hlo  # noqa: E402

"""Multi-pod dry-run (assignment deliverable (e)).

For every (architecture × input shape × mesh) combination: lower + compile the
step function against ShapeDtypeStruct inputs on the production mesh (no
allocation), print memory/cost analysis, and persist a JSON record with the
roofline terms (deliverable (g) reads these).

`XLA_FLAGS=--xla_force_host_platform_device_count=512` is set in the FIRST TWO
LINES of this file, before any other import — jax locks the device count on
first init, and ONLY the dry-run may see 512 placeholder devices.
"""


def default_plan(arch: str) -> ParallelPlan:
    """The paper-faithful baseline recipe (DESIGN.md §0): TP over ``model``,
    DP + ZeRO-1 over ``data``, full remat, EP for MoE archs (folded onto the
    16-wide tp ring — ``ep`` is a degree now, pinned to cp×tp)."""
    cfg = resolve_config(arch, "train_4k")
    return ParallelPlan(
        tp=16,
        dp_shard=1,
        zero_stage=1,
        ep=16 if cfg.family == Family.MOE else 1,
        remat="full",
    )


def plan_from_args(arch: str, args) -> ParallelPlan:
    plan = default_plan(arch)
    overrides = {}
    if args.dp_shard is not None:
        overrides["dp_shard"] = args.dp_shard
    if args.remat is not None:
        overrides["remat"] = args.remat
    if args.zero is not None:
        overrides["zero_stage"] = args.zero
    if args.no_ep:
        overrides["ep"] = 1
    if args.no_seq_shard:
        overrides["seq_shard_decode"] = False
        overrides["seq_shard_attn"] = False
    if args.pad_vocab:
        overrides["pad_vocab_to_multiple"] = args.pad_vocab
    if args.microbatches:
        overrides["microbatches"] = args.microbatches
    if args.dp_over_model:
        overrides["dp_over_model"] = True
        overrides["ep"] = 1
    if args.moe_dispatch:
        overrides["moe_dispatch"] = args.moe_dispatch
    return dataclasses.replace(plan, **overrides) if overrides else plan


def run_one(arch: str, shape_name: str, multi_pod: bool, plan: ParallelPlan,
            out_dir: Path, tag: str = "baseline") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    shape = SHAPES_BY_NAME[shape_name]
    cfg = resolve_config(arch, shape_name)
    reason = skip_reason(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
           "plan": dataclasses.asdict(plan)}
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: SKIP ({reason})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    fn, args, shardings, meta = build_step(arch, shape_name, mesh, plan)

    with mesh:
        jitted = jit_step(fn, shardings, meta)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover - backend specific
        mem, mem_rec = None, {"error": str(e)}

    # trip-count-aware HLO walk (cost_analysis counts scan bodies once; our
    # layer stacks are scans — see repro/perf/hlo_cost.py)
    hlo = compiled.as_text()
    hc = analyze_hlo(hlo, chips)
    roof = Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=hc.flops, hlo_bytes=hc.bytes,
        collective_bytes=hc.collective_link_bytes,
        model_flops=model_flops_for(meta["cfg"], shape),
        collectives={"counts": hc.collective_counts,
                     "link_bytes": hc.collective_bytes_by_kind},
    )

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem_rec,
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed", "transcendentals")},
        "roofline": roof.row(),
    })
    print(f"[dryrun] {arch} × {shape_name} × {mesh_name} [{tag}]: OK "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s) "
          f"t_comp={roof.t_compute:.3e}s t_mem={roof.t_memory:.3e}s "
          f"t_coll={roof.t_collective:.3e}s -> {roof.bottleneck}-bound")
    if mem_rec.get("temp_size_in_bytes") is not None:
        print(f"         memory: args={mem_rec['argument_size_in_bytes']} "
              f"out={mem_rec['output_size_in_bytes']} "
              f"temp={mem_rec['temp_size_in_bytes']} (per device)")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None,
                    choices=[s.name for s in INPUT_SHAPES] + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    # plan overrides (hillclimbing knobs)
    ap.add_argument("--dp-shard", type=int, default=None)
    ap.add_argument("--remat", default=None,
                    choices=["none", "selective", "full", None])
    ap.add_argument("--zero", type=int, default=None)
    ap.add_argument("--no-ep", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--pad-vocab", type=int, default=0)
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--dp-over-model", action="store_true")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["einsum", "scatter", None])
    args = ap.parse_args()
    use_compile_cache()

    assert len(jax.devices()) == 512, "dry-run requires 512 placeholder devices"

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else [s.name for s in INPUT_SHAPES]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        plan = plan_from_args(arch, args)
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = out_dir / f"{args.tag}__{arch}__{shape}__{mesh_name}.json"
                if args.skip_existing and path.exists():
                    print(f"[dryrun] exists: {path.name}")
                    n_ok += 1
                    continue
                try:
                    rec = run_one(arch, shape, mp, plan, out_dir, args.tag)
                    if rec["status"] == "ok":
                        n_ok += 1
                    else:
                        n_skip += 1
                except Exception as e:
                    n_fail += 1
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "failed", "error": repr(e)}
                    print(f"[dryrun] {arch} × {shape} × {mesh_name}: FAILED {e!r}")
                path.write_text(json.dumps(rec, indent=2, default=str))
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
