"""Expert-parallel MoE training (survey §4.1.5) on a multi-device host mesh.

Re-executes itself with 8 forced host devices and trains an OLMoE-family
reduced config through the block executor's expert-parallel route:
``plan.ep`` shards the routed experts over the mesh's ``model`` axis and the
dispatch/combine token exchange runs as the overlapped ``ppermute`` ring of
``kernels/dispatch.dispatch_ep_a2a`` — each ring tick computes the expert
chunk it already holds while the next chunk is in flight (``ep_impl =
"overlap"``; ``"blocking"`` is the exposed GShard-style ``all_to_all`` pair).

Two placements are shown:

- **ep-only** on a (data=2, model=4) mesh: experts ride the model axis and
  attention runs sequence-sharded as a cp ring over those same devices;
- **MoE parallel folding** on a (data=1, cp=2, model=2) mesh: attention keeps
  its cp × tp mapping while the MoE sublayer re-reads the same four devices
  as one flat ep=4 expert ring — parallelism is remapped per sublayer, not
  added.

    PYTHONPATH=src python examples/train_moe_ep.py
"""

import dataclasses
import os

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from repro.core import InputShape, ParallelPlan, get_smoke_config  # noqa: E402
from repro.core.sharding import ep_spec_for_param       # noqa: E402
from repro.data import SyntheticDataset                 # noqa: E402
from repro.launch.mesh import make_mesh                 # noqa: E402
from repro.models import build_model                    # noqa: E402
from repro.train import Hyper, init_train_state, make_train_step  # noqa: E402
from repro.train.executor import make_executor_loss_fn  # noqa: E402


def main():
    assert len(jax.devices()) == 8, "expected 8 forced host devices"
    cfg = get_smoke_config("olmoe-1b-7b")
    # no-drop capacity (>= E/top_k): shard-local routing is then exactly the
    # dense-dispatch math — the regime the equivalence tests pin down
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=2.0))
    e = cfg.moe.num_experts

    # --- ep-only: experts over the model axis, overlapped a2a ring ---------
    mesh = make_mesh((2, 4), ("data", "model"))
    plan = ParallelPlan(ep=4, ep_impl="overlap", zero_stage=1,
                        remat="selective", compute_dtype="float32")
    shape = InputShape("moe-ep", seq_len=64, global_batch=8, kind="train")

    model = build_model(cfg, plan)
    state = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh,
                             plan=plan)
    spec = ep_spec_for_param(("layers", "moe", "experts", "gate"),
                             (cfg.n_layers, e, cfg.d_model,
                              cfg.moe.d_expert), plan)
    print(f"{e} experts sharded {spec} over mesh {dict(mesh.shape)}: "
          f"{e // 4} expert(s) per ring rank, ep_impl={plan.ep_impl}")

    step_fn = jax.jit(make_train_step(model, plan, Hyper(
        peak_lr=5e-3, warmup_steps=10, total_steps=100), mesh=mesh),
        donate_argnums=(0,))
    ds = SyntheticDataset(cfg, shape)
    for i in range(100):
        batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
        state, m = step_fn(state, batch)
        if i % 20 == 0 or i == 99:
            print(f"step {i:3d}  loss {float(m['loss']):.4f}  "
                  f"moe_aux {float(m['moe_aux']):.4f}")
    print("expert-parallel MoE training OK")

    # --- MoE parallel folding: ep == cp x tp on a (2, 2, 2) mesh -----------
    # Attention runs as a zigzag cp ring over "cp" with overlap-TP rings over
    # "model"; the MoE sublayer re-reads those same cp x model devices as one
    # flat expert axis. Overlap and blocking a2a are the same math.
    fold_mesh = make_mesh((2, 2, 2), ("data", "cp", "model"))
    # host copies: the trained params are committed to the ep-only mesh
    params = jax.device_get(state.params)
    losses = {}
    for impl in ("blocking", "overlap"):
        fplan = ParallelPlan(ep=4, ep_impl=impl, cp=2, cp_impl="ring",
                             tp=2, tp_impl="overlap", remat="selective",
                             compute_dtype="float32")
        lf = make_executor_loss_fn(cfg, fplan, fold_mesh, ("data",))
        losses[impl], _ = jax.jit(lf)(params, batch)
        print(f"folded ep=4 (cp=2 x tp=2) {impl:>8} a2a  "
              f"loss {float(losses[impl]):.6f}")
    assert abs(float(losses["overlap"]) - float(losses["blocking"])) < 1e-6
    print("MoE parallel folding OK: overlapped ring == blocking all-to-all")


if __name__ == "__main__":
    main()
