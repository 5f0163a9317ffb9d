"""Fail-slow defense walkthrough (survey §8.1): detect, attribute, rebalance.

A degraded device drags the whole pipeline down to its pace — the classic
fail-slow failure mode (Malleus, Falcon): nothing crashes, MFU just quietly
halves. This demo runs the full defense ladder on a 2-stage pipeline:

1. a deterministic ``slow`` fault (``ft/inject``) pins a per-layer host
   delay to pipeline stage 1 from step 6 onward;
2. the :class:`~repro.ft.straggler.StragglerTimer` telemetry feeds the
   sliding-window detector, which attributes the slowdown to
   ``(rank=1, pp.stage, compute)`` after ``confirm`` consecutive slow steps
   — work-share-normalized, so an *intentionally* uneven layout would not
   false-positive;
3. ``RecoveryPolicy.straggler = "rebalance"`` invokes
   :func:`~repro.ft.straggler.choose_pp_layout` on the *measured* per-stage
   times: the degraded stage sheds a layer, (2, 2) -> (3, 1);
4. the driver restores the latest checkpoint through the **elastic reshard
   path** (``pp_layout`` is a layout axis in the manifest) and continues on
   the uneven layout — degraded, but no longer paced by the slow stage.

    PYTHONPATH=src python examples/straggler_rebalance.py
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses                                      # noqa: E402
import tempfile                                         # noqa: E402
import time                                             # noqa: E402

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.checkpoint import CheckpointManager          # noqa: E402
from repro.core import (Family, InputShape, ModelConfig,  # noqa: E402
                        ParallelPlan, RecoveryPolicy)
from repro.data import SyntheticDataset                 # noqa: E402
from repro.ft import (Monitor, RemeshSpec, StragglerDetector,  # noqa: E402
                      StragglerTimer, run_with_recovery)
from repro.ft.inject import FaultSpec, armed            # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402
from repro.models import build_model                    # noqa: E402
from repro.train.pipeline import pipelined_loss_fn      # noqa: E402


def main():
    mesh = make_mesh((2, 2), ("pod", "data"))
    cfg = ModelConfig("slow-demo", Family.DENSE, n_layers=4, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2,
                        microbatches=4)
    ds = SyntheticDataset(cfg, InputShape("demo", 32, 8, "train"))
    get_batch = lambda s: {k: jnp.asarray(v)                # noqa: E731
                           for k, v in ds.batch(s).items()}

    model = build_model(cfg, ParallelPlan(remat="none",
                                          compute_dtype="float32"))
    state0 = {"params": model.init(jax.random.PRNGKey(0))}

    def make_step(pl):
        """SGD over the pipelined loss under layout ``pl.pp_layout``."""
        lf = pipelined_loss_fn(cfg, pl, mesh, ("data",))

        def step(state, batch):
            loss, grads = jax.value_and_grad(
                lambda p, b: lf(p, b)[0])(state["params"], batch)
            params = jax.tree.map(lambda p, g: p - 1e-3 * g,
                                  state["params"], grads)
            gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in jax.tree.leaves(grads)))
            return {"params": params}, {"loss": loss, "grad_norm": gn}
        return jax.jit(step)

    # the defense stack: telemetry -> detector -> policy -> rebalance hook
    detector = StragglerDetector(window=8, factor=2.0, confirm=3,
                                 min_seconds=1e-3)
    timer = StragglerTimer(cfg=cfg, plan=plan, detector=detector)
    policy = RecoveryPolicy(straggler="rebalance", max_restores=4)
    monitor = Monitor(hang_min_seconds=60.0)  # straggler ladder owns this

    def rebalance(layout):
        print(f"[demo] rebalance hook: measured stage times "
              f"{ {r: f'{t * 1e3:.1f}ms' for r, t in timer.stage_times().items()} } "
              f"-> pp_layout {layout}")
        pl2 = dataclasses.replace(plan, pp_layout=tuple(layout))
        return RemeshSpec(train_step=make_step(pl2), state_template=state0,
                          plan=pl2, mesh=mesh)

    ckpt = CheckpointManager(tempfile.mkdtemp(), keep=4, async_persist=False)

    # the fault: stage 1 pays 40ms of extra host time per layer it holds,
    # every step from 6 on — a condition, not an event (span covers the run)
    fault = FaultSpec("pp.stage.tick", "slow", step=6, span=999, rank=1,
                      sleep_s=0.04)
    print("[demo] injecting fail-slow on pipeline stage 1 from step 6; "
          "policy.straggler = rebalance")
    t0 = time.time()
    with armed([fault]):
        final, report = run_with_recovery(
            state0, make_step(plan), get_batch, 18, ckpt, monitor,
            ckpt_every=3, plan=plan, mesh=mesh, policy=policy,
            straggler=timer, rebalance=rebalance)
    dt = time.time() - t0

    strag = [a for a in report.anomalies if a.kind == "straggler"]
    assert strag and report.rebalances == 1, (strag, report)
    print(f"[demo] first attribution at step {strag[0].step}: "
          f"{strag[0].detail}")
    for s, kind, action in report.actions:
        print(f"[demo]   step {s}: {kind} -> {action}")
    print(f"[demo] {report.steps_done} steps in {dt:.1f}s, "
          f"rebalances={report.rebalances}, restores={report.restores}, "
          f"final loss {report.losses[-1]:.4f}")
    print("[demo] straggler rebalance walkthrough OK")


if __name__ == "__main__":
    main()
