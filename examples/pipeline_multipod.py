"""Pipeline parallelism across pods (survey §4.1.3) on a host-device mesh.

Builds the (pod=2, data=2, model=2) mesh, pipelines a 4-layer dense model as
2 stages over the ``pod`` axis under both schedules — GPipe fill-drain and the
memory-lean 1F1B custom-VJP schedule (``plan.pp_schedule``) — verifies both
against the non-pipelined loss, compares their compiled peak live memory, and
trains with the 1F1B schedule. Then composes TP x PP (survey §4.1.2 x
§4.1.3): ``plan.tp_impl = "overlap"`` runs the collective-matmul ring steps of
``train/tensor_parallel.py`` *inside* each 1F1B tick, with sequence-sharded
(mb, s/tp, d) activations rotating between stages and a vocab-parallel loss
on the last stage. Finally CP x TP x PP (§4.1.4, the long-context recipe):
``plan.cp`` shards the sequence itself over a "cp" mesh axis and zigzag ring
attention runs inside each tick, so no device ever holds full-context K/V.

    PYTHONPATH=src python examples/pipeline_multipod.py
"""

import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses                                      # noqa: E402

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.core import Family, InputShape, ModelConfig, ParallelPlan  # noqa: E402
from repro.data import SyntheticDataset                 # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402
from repro.models import build_model                    # noqa: E402
from repro.optim import adamw_init, adamw_update, clip_by_global_norm  # noqa: E402
from repro.train import Hyper, make_loss_fn             # noqa: E402
from repro.train.pipeline import pipelined_loss_fn      # noqa: E402


def main():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = ModelConfig("pipe-demo", Family.DENSE, n_layers=4, d_model=128,
                      n_heads=4, n_kv_heads=2, d_ff=256, vocab=512)
    # tp_impl pinned so the baseline stays the GSPMD pipeline even on TPU
    # backends (where "auto" resolves to overlap) — the TP x PP section below
    # flips it explicitly and compares against this
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2,
                        microbatches=4, tp_impl="gspmd")
    shape = InputShape("pipe", seq_len=64, global_batch=8, kind="train")
    ds = SyntheticDataset(cfg, shape)

    model = build_model(cfg, ParallelPlan(remat="none",
                                          compute_dtype="float32"))
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}

    hyper = Hyper(z_loss=0.0)
    ref_loss, _ = make_loss_fn(model, hyper)(params, batch)
    print(f"non-pipelined loss {float(ref_loss):.6f}  "
          f"(bubble fraction {(plan.pp-1)/(plan.microbatches+plan.pp-1):.0%})")

    mems = {}
    for sched in ("gpipe", "1f1b"):
        pl = dataclasses.replace(plan, pp_schedule=sched)
        lf = pipelined_loss_fn(cfg, pl, mesh, ("data",))
        loss, _ = jax.jit(lf)(params, batch)
        assert abs(float(ref_loss) - float(loss)) < 2e-4
        gf = jax.jit(jax.value_and_grad(lambda p, b: lf(p, b)[0]))
        ma = gf.lower(params, batch).compile().memory_analysis()
        mems[sched] = getattr(ma, "temp_size_in_bytes", None) if ma else None
        print(f"{sched:>6} loss {float(loss):.6f}  "
              f"peak temp bytes {mems[sched]}")
    if all(mems.values()):
        print(f"1f1b keeps {mems['1f1b']/mems['gpipe']:.0%} of gpipe's "
              f"in-flight activation memory")

    # a few pipelined training steps under the 1F1B schedule (plan default)
    pipe_loss_fn = pipelined_loss_fn(cfg, plan, mesh, ("data",))
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: pipe_loss_fn(p, b)[0]))
    opt = adamw_init(params)
    for i in range(10):
        batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
        loss, grads = grad_fn(params, batch)
        grads, _ = clip_by_global_norm(grads, 1.0)
        params, opt = adamw_update(grads, opt, params, 1e-3)
        if i % 3 == 0:
            print(f"pipelined step {i}: loss {float(loss):.4f}")
    print("multi-pod pipeline training OK")

    # TP x PP: the same 1F1B pipeline with overlap tensor parallelism on the
    # model axis — ring-decomposed collective matmuls inside each stage tick,
    # (mb, s/tp, d) sequence shards on the stage-to-stage ppermute, and the
    # vocab-parallel cross-entropy on the last stage. Same loss, tp x smaller
    # inter-stage transfers and between-block activations.
    tp_plan = dataclasses.replace(plan, tp=2, tp_impl="overlap")
    tp_loss_fn = pipelined_loss_fn(cfg, tp_plan, mesh, ("data",))
    tp_loss, _ = jax.jit(tp_loss_fn)(params, batch)
    base_loss, _ = jax.jit(pipe_loss_fn)(params, batch)
    assert abs(float(tp_loss) - float(base_loss)) < 2e-5
    print(f"TP x PP (1f1b + overlap rings) loss {float(tp_loss):.6f} == "
          f"pp-only loss {float(base_loss):.6f}")

    # CP x TP x PP — the long-context recipe (survey §4.1.4): the sequence
    # itself is sharded over a "cp" mesh axis end to end, so each device
    # holds (mb, s/(cp·tp), d) activations between blocks and zigzag ring
    # attention ppermutes KV chunks *inside* each 1F1B tick — no device ever
    # materializes full-context K/V or scores. At real long-context sizes
    # (train/executor.py: plan.cp=8, S=512k) this is what keeps attention
    # activation memory, the long-S bottleneck, flat per device.
    cp_mesh = make_mesh((2, 2, 2), ("pod", "cp", "model"))
    cp_plan = dataclasses.replace(plan, tp=2, tp_impl="overlap",
                                  cp=2, cp_impl="ring")
    cp_loss_fn = pipelined_loss_fn(cfg, cp_plan, cp_mesh, ())
    cp_loss, _ = jax.jit(cp_loss_fn)(params, batch)
    assert abs(float(cp_loss) - float(base_loss)) < 2e-5
    print(f"CP x TP x PP (zigzag ring attention in each 1F1B tick) loss "
          f"{float(cp_loss):.6f} == pp-only loss {float(base_loss):.6f}")

    # EP x TP x CP x PP — MoE parallel folding inside each tick (survey
    # §4.1.5): a MoE twin of the demo config re-reads each stage's cp x model
    # devices as one flat ep=4 expert ring; the dispatch/combine all-to-all
    # runs as overlapped ppermute ticks interleaved with expert-GEMM chunks
    # (``plan.ep_impl``), all inside the same 1F1B schedule. The overlapped
    # ring and the blocking all-to-all are the same math.
    from repro.core import MoEConfig
    moe_cfg = dataclasses.replace(
        cfg, family=Family.MOE, d_ff=0,
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=128,
                      num_shared_experts=1, capacity_factor=2.0))
    moe_params = build_model(moe_cfg, ParallelPlan(
        remat="none", compute_dtype="float32")).init(jax.random.PRNGKey(1))
    ep_losses = {}
    for impl in ("blocking", "overlap"):
        ep_plan = dataclasses.replace(cp_plan, ep=4, ep_impl=impl)
        ep_loss_fn = pipelined_loss_fn(moe_cfg, ep_plan, cp_mesh, ())
        ep_losses[impl], _ = jax.jit(ep_loss_fn)(moe_params, batch)
        print(f"EP x TP x CP x PP ({impl:>8} a2a) loss "
              f"{float(ep_losses[impl]):.6f}")
    assert abs(float(ep_losses["overlap"]) - float(ep_losses["blocking"])) \
        < 1e-6
    print("MoE parallel folding in the pipeline OK: overlapped ring == "
          "blocking all-to-all")


if __name__ == "__main__":
    main()
