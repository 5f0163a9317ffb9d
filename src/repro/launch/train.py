"""End-to-end training driver.

Runs real steps on the available devices (one TPU chip, a TPU slice through
the local mesh, or the CPU):

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \\
        --steps 50 --batch 8 --seq 128
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --full \\
        --steps 100 --batch 8 --seq 2048 --remat full   # one v5e chip

Integrates the full substrate: synthetic data pipeline, sharded AdamW + ZeRO-1,
remat, checkpointing (async persist, optional double-buffered snapshots), and
anomaly-driven recovery (survey §8): NaN/spike -> rollback-and-replay,
repeated spike -> LR-rescue, hang -> advisory or elastic remesh. ``--resume``
continues from the latest checkpoint in ``--ckpt-dir`` — including one
written on a *different* mesh layout (elastic reshard-restore, §8.3.2).

Fast-recovery layer (§8.3.1): ``--ckpt-memory-keep K`` keeps a hot RAM ring
of the last K snapshots (peer-mirrored unless ``--no-peer-redundancy``) that
every rollback restores from before touching disk. A SIGTERM/SIGUSR1
(spot-instance preemption notice) is caught between steps: the driver takes
a just-in-time snapshot within ``--preempt-grace`` seconds, writes a
``PREEMPTED`` marker, and exits 0 — rerun with ``--resume`` to continue
bit-identically. ``--flight-path`` arms the crash flight recorder: a
bounded ring of per-step events dumped to JSON on preemption, crash, or
recovery exhaustion for post-mortem attribution. Each step's ``"loop"``
event carries the host seconds of each of the loop's sections
(``train.fetch``, ``train.step``, ``train.readback``, ``train.monitor``,
``train.ckpt``, ...), and the ``"setup"`` event those of the step-0 save.

The step is compiled ahead of the loop, so compile time is reported apart
from step time; :func:`main` returns a :class:`TrainResult` for callers that
drive the trainer in-process (``chip_smoke.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import ARCH_IDS, InputShape, ParallelPlan, RecoveryPolicy
from repro.core.config import RECOVERY_ACTIONS, Family
from repro.checkpoint import CheckpointManager, MemoryCheckpointTier
from repro.data import Prefetcher, SyntheticDataset
from repro.ft import (FlightRecorder, Monitor, RunReport, StragglerTimer,
                      run_with_recovery)
from repro.ft.preempt import PreemptionGuard
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import batch_axes_for, make_local_mesh
from repro.launch.stepbuilder import resolve_config
from repro.models import build_model
from repro.train import Hyper, TrainState, init_train_state, make_train_step


@dataclasses.dataclass
class TrainResult:
    report: RunReport
    compile_seconds: float
    compiled: Any          # the train step as compiled (jax.stages.Compiled)
    flight: FlightRecorder  # per-step events: losses, checkpoint seconds


def main(argv: Optional[Sequence[str]] = None) -> TrainResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced config (full configs need a real pod)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="selective",
                    choices=["none", "selective", "full"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in --ckpt-dir "
                         "instead of starting fresh; a checkpoint written on "
                         "a different mesh layout is reshard-restored onto "
                         "the current one (elastic recovery, survey §8.3.2)")
    ap.add_argument("--async-snapshot", action="store_true",
                    help="double-buffer the device->host checkpoint snapshot "
                         "(survey §8.3.1): save() only dispatches a device-"
                         "side clone and the copy+write overlap later steps, "
                         "at the cost of transiently one extra state copy in "
                         "device memory")
    ap.add_argument("--on-nan", default="rollback", choices=RECOVERY_ACTIONS,
                    help="recovery action for a non-finite loss/grad-norm")
    ap.add_argument("--on-spike", default="rollback", choices=RECOVERY_ACTIONS,
                    help="recovery action for a first loss spike at a step")
    ap.add_argument("--on-repeated-spike", default="lr_rescue",
                    choices=RECOVERY_ACTIONS,
                    help="action when the same step spikes again after a "
                         "rollback (replay alone would loop): lr_rescue "
                         "replays it with LR x --rescue-lr-scale")
    ap.add_argument("--on-hang", default="ignore", choices=RECOVERY_ACTIONS,
                    help="action for a hung/straggling step (wall-time >> "
                         "trailing median); 'ignore' logs only")
    ap.add_argument("--on-straggler", default="ignore",
                    choices=RECOVERY_ACTIONS,
                    help="action for a confirmed fail-slow attribution "
                         "(survey §8.1): 'ignore' logs the (rank, component, "
                         "class) triple; 'rebalance' re-partitions "
                         "layers-per-stage (Malleus-style pp_layout) when a "
                         "pipeline stage is the straggler")
    ap.add_argument("--straggler-factor", type=float, default=2.0,
                    help="relative slowdown (work-normalized, vs peer median "
                         "or trailing window) that counts as slow")
    ap.add_argument("--straggler-window", type=int, default=16,
                    help="sliding-window length of the straggler detector")
    ap.add_argument("--straggler-confirm", type=int, default=3,
                    help="consecutive slow observations before an attribution "
                         "is emitted (detection latency in steps)")
    ap.add_argument("--prefetch", action="store_true",
                    help="synthesize the next batch on a background thread "
                         "while the device step runs (pure host work; batch "
                         "contents are unchanged)")
    ap.add_argument("--rescue-lr-scale", type=float, default=0.1,
                    help="LR multiplier used by the lr_rescue policy while "
                         "replaying the offending step")
    ap.add_argument("--max-restores", type=int, default=3,
                    help="give up after this many checkpoint restores")
    ap.add_argument("--simulate-hang-at", type=int, default=-1,
                    help="fault injection for demos/tests: sleep 2s before "
                         "this step so the hang watchdog fires (-1 = off)")
    ap.add_argument("--integrity", default="off", choices=["off", "audit"],
                    help="silent-data-corruption audit (survey §8.2): 'audit' "
                         "adds an exact param/grad checksum to every step, "
                         "cross-checked across replicas; any divergence "
                         "raises an 'sdc' anomaly routed through --on-sdc")
    ap.add_argument("--on-sdc", default="rollback", choices=RECOVERY_ACTIONS,
                    help="recovery action when the integrity audit detects "
                         "replica checksum divergence")
    ap.add_argument("--ckpt-memory-keep", type=int, default=2,
                    help="hot in-memory checkpoint tier (survey §8.3.1): RAM "
                         "ring of the last K snapshots restored before any "
                         "disk walk; 0 disables the tier")
    ap.add_argument("--no-peer-redundancy", dest="peer_redundancy",
                    action="store_false", default=True,
                    help="skip mirroring each host-group's RAM shards onto "
                         "its ring neighbor (halves hot-tier RAM, loses "
                         "tolerance to a lost host-group)")
    ap.add_argument("--preempt-grace", type=float, default=30.0,
                    help="seconds of grace between a preemption notice "
                         "(SIGTERM/SIGUSR1) and the kill; the just-in-time "
                         "snapshot tier is chosen so it fits this budget")
    ap.add_argument("--flight-len", type=int, default=256,
                    help="crash flight recorder ring capacity (events)")
    ap.add_argument("--flight-path", default=None,
                    help="where the flight recorder dumps its JSON on "
                         "preemption/crash/exhaustion (default: "
                         "<ckpt-dir>/flight.json); each step's 'loop' event "
                         "there holds the seconds of each section of the "
                         "loop (train.fetch, train.step, train.ckpt, ...)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = resolve_config(args.arch, "train_4k", smoke=args.smoke)
    shape = InputShape("cli", args.seq, args.batch, "train")

    n_dev = len(jax.devices())
    mesh = make_local_mesh() if n_dev > 1 else None
    baxes = batch_axes_for(mesh, args.batch) if mesh else ()
    # MoE archs ride the local mesh's model axis as an expert ring (ep-only
    # folding) when the expert count divides it; otherwise dense dispatch
    ep = (mesh.shape.get("model", 1)
          if cfg.family == Family.MOE and mesh is not None
          and cfg.moe.num_experts % mesh.shape.get("model", 1) == 0 else 1)
    plan = ParallelPlan(remat=args.remat, microbatches=args.microbatches,
                        compute_dtype="float32" if args.smoke else "bfloat16",
                        ep=ep, integrity=args.integrity)
    model = build_model(cfg, plan, mesh, baxes)

    hyper = Hyper(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 5),
                  total_steps=args.steps)
    state = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"[train] arch={cfg.arch_id} params={n_params/1e6:.1f}M "
          f"devices={n_dev} batch={args.batch} seq={args.seq}")

    step_fn = jax.jit(make_train_step(model, plan, hyper, mesh=mesh),
                      donate_argnums=(0,))
    ds = SyntheticDataset(cfg, shape)
    flight = FlightRecorder(
        maxlen=args.flight_len,
        path=args.flight_path or f"{args.ckpt_dir}/flight.json")
    ckpt = CheckpointManager(args.ckpt_dir, keep=2,
                             async_snapshot=args.async_snapshot,
                             flight=flight)
    monitor = Monitor(flight=flight)
    policy = RecoveryPolicy(
        nan=args.on_nan, spike=args.on_spike,
        repeated_spike=args.on_repeated_spike, hang=args.on_hang,
        sdc=args.on_sdc, straggler=args.on_straggler,
        max_restores=args.max_restores,
        rescue_lr_scale=args.rescue_lr_scale,
        ckpt_memory_keep=args.ckpt_memory_keep,
        peer_redundancy=args.peer_redundancy,
        preempt_grace=args.preempt_grace, flight_len=args.flight_len,
        straggler_factor=args.straggler_factor,
        straggler_window=args.straggler_window,
        straggler_confirm=args.straggler_confirm)
    mem_ckpt = None
    if policy.ckpt_memory_keep > 0:
        mem_ckpt = MemoryCheckpointTier(
            keep=policy.ckpt_memory_keep,
            peer_redundancy=policy.peer_redundancy,
            groups=max(2, n_dev), flight=flight)
    rescue_fn = None
    if "lr_rescue" in (policy.spike, policy.repeated_spike,
                       policy.nan, policy.hang):
        rescue_hyper = hyper._replace(peak_lr=args.lr * args.rescue_lr_scale)
        rescue_fn = jax.jit(make_train_step(model, plan, rescue_hyper,
                                            mesh=mesh))

    straggler = StragglerTimer(cfg=cfg, plan=plan, policy=policy,
                               flight=flight)

    prefetch = Prefetcher(ds) if args.prefetch else None
    source = prefetch.batch if prefetch is not None else ds.batch

    def get_batch(step: int):
        return {k: jnp.asarray(v) for k, v in source(step).items()}

    # compile ahead of the loop: the jit call below reuses this executable,
    # so the first step's time is a step time, not a compile time
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, get_batch(0)).compile()
    compile_seconds = time.perf_counter() - t0
    print(f"[train] step compiled in {compile_seconds:.1f}s")

    t_start = time.time()

    def injector(step, st):
        if step == args.simulate_hang_at:
            time.sleep(2.0)
        return st

    try:
        with PreemptionGuard(grace=policy.preempt_grace) as guard:
            state, report = run_with_recovery(
                state, step_fn, get_batch, args.steps, ckpt, monitor,
                ckpt_every=args.ckpt_every, plan=plan, mesh=mesh,
                policy=policy, rescue_step=rescue_fn, resume=args.resume,
                fault_injector=(injector if args.simulate_hang_at >= 0
                                else None),
                mem_ckpt=mem_ckpt, preempt=guard, flight=flight,
                straggler=straggler)
    except KeyboardInterrupt as e:
        # Ctrl-C is an exit, not a crash — but it still leaves a black box:
        # the driver dumped the ring on the way out (any BaseException does)
        fp = getattr(e, "flight_path", None) or flight.dump("KeyboardInterrupt")
        print(f"[train] interrupted; flight log at {fp}")
        raise SystemExit(130)
    finally:
        if prefetch is not None:
            prefetch.close()

    dt = time.time() - t_start
    if report.preempted:
        print(f"[train] preempted at step {report.preempt_step} "
              f"(signal {guard.signum}): just-in-time snapshot taken, "
              f"PREEMPTED marker written, flight log at "
              f"{report.flight_path}; rerun with --resume to continue")
        return TrainResult(report, compile_seconds, compiled, flight)
    tokens = args.steps * args.batch * args.seq
    print(f"[train] {args.steps} steps in {dt:.1f}s "
          f"({tokens/dt:.0f} tok/s), loss {report.losses[0]:.4f} -> "
          f"{report.losses[-1]:.4f}, anomalies={len(report.anomalies)}, "
          f"restores={report.restores} (memory-tier {report.mem_restores}), "
          f"remeshes={report.remeshes}, rebalances={report.rebalances}")
    for step, kind, action in report.actions:
        print(f"[train]   step {step}: {kind} -> {action}")
    print(f"[train] ckpt snapshot {ckpt.snapshot_seconds*1e3:.1f}ms "
          f"persist {ckpt.persist_seconds*1e3:.1f}ms "
          f"({'double-buffered' if args.async_snapshot else 'blocking'} "
          f"snapshot, async persist)")
    return TrainResult(report, compile_seconds, compiled, flight)


if __name__ == "__main__":
    main()
