"""Train-step factory: loss + grad + clip + AdamW, with microbatch accumulation.

The returned function is pure and jit/pjit-friendly:

    state = TrainState(params, opt)
    state, metrics = train_step(state, batch)

Gradient accumulation (``plan.microbatches``) runs as a ``lax.scan`` over
microbatch slices — constant HLO size, and under pipeline parallelism the same
slicing provides the pipeline's microbatches.

Tensor parallelism (survey §4.1.2): with a ``mesh`` whose ``model`` axis is
>= 2 and ``plan.tp_impl`` resolving to ``"overlap"``, the step swaps its loss
for the explicit ring path (``train.tensor_parallel.make_tp_loss_fn``) —
collective matmuls + sequence-sharded activations instead of GSPMD's blocking
all-reduces. ``tp_impl="auto"`` only picks it on TPU backends; an unsupported
family under ``"auto"`` silently keeps the GSPMD loss, while an explicit
``"overlap"`` raises.

ZeRO-1 (survey §6.2.1): pass ``mesh`` and the step shards the optimizer work
over the ``data`` axis. The fp32 microbatch accumulator is *born scattered*
(constrained to ``core.sharding.opt_state_specs``), so each microbatch's grads
reduce-scatter straight into the shard and a fully-replicated fp32 grad copy
never exists; the AdamW math then runs on each device's slice of the moments
(``optim.adamw_update_sharded``) and only the updated params all-gather back.
Without ``mesh`` (or with ``plan.zero_stage == 0``) the step is the plain
replicated update — same math either way.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import sharding as shardlib
from repro.core.config import ModelConfig, ParallelPlan
from repro.models.families import Model
from repro.optim import (adamw_init, adamw_update, adamw_update_sharded,
                         clip_by_global_norm, constrain_tree, cosine_schedule)
from .loss import cross_entropy


class TrainState(NamedTuple):
    params: Any
    opt: Any          # AdamWState


class Hyper(NamedTuple):
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    z_loss: float = 1e-4


def init_train_state(model: Model, rng, mesh: Optional[Mesh] = None,
                     plan: Optional[ParallelPlan] = None) -> TrainState:
    """Fresh state; with ``mesh`` + ``plan`` the params are placed on their
    plan layout and the AdamW moments are born on the ZeRO-1 data-scattered
    layout (``core.sharding.opt_state_specs``) — the layouts the jitted step
    would otherwise impose on first use, needed up front when the state
    serves as an elastic-restore template."""
    params = model.init(rng)
    if mesh is None or plan is None:
        return TrainState(params, adamw_init(params))
    pspecs = jax.tree.map(_canonical,
                          shardlib.param_specs(params, model.cfg, plan, mesh),
                          is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(
        lambda p, s: jax.device_put(p, jax.sharding.NamedSharding(mesh, s)),
        params, pspecs)
    ospecs = jax.tree.map(_canonical,
                          shardlib.opt_state_specs(pspecs, params, plan, mesh),
                          is_leaf=lambda x: isinstance(x, P))
    return TrainState(params, adamw_init(params, mesh=mesh, specs=ospecs))


def _canonical(spec: P) -> P:
    """``spec`` without trailing ``None`` entries — the form a jitted step
    hands its outputs back in. jit keys its cache on the spec as written, so
    a state placed as ``P(None, None)`` would make the second step compile
    again for the ``P()`` the first step returned."""
    parts = list(spec)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def make_loss_fn(model: Model, hyper: Hyper) -> Callable:
    def loss_fn(params, batch):
        logits, aux = model.forward(params, batch)
        with jax.named_scope("head"):
            loss = cross_entropy(logits, batch["labels"], z_loss=hyper.z_loss)
        return loss + aux, {"xent": loss, "moe_aux": aux}
    return loss_fn


def _split_microbatches(batch: Dict[str, jax.Array], n: int):
    def split(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape((n, b // n) + x.shape[1:])
    return jax.tree.map(split, batch)


def _overlap_loss_fn(model: Model, plan: ParallelPlan, hyper: Hyper,
                     mesh: Mesh) -> Optional[Callable]:
    """The executor (overlap-TP, context-parallel and/or expert-parallel)
    loss when the plan/mesh select it, else None (GSPMD loss)."""
    from repro.kernels.dispatch import select_tp_impl  # noqa: PLC0415
    use_cp = plan.cp > 1
    use_ep = plan.ep > 1
    if use_cp and (mesh is None or mesh.shape.get("cp", 1) < plan.cp):
        raise ValueError(
            f"plan.cp={plan.cp} was requested but the step has no 'cp' mesh "
            f"axis of size {plan.cp} to shard the sequence over")
    if use_ep and mesh is None:
        raise ValueError(
            f"plan.ep={plan.ep} was requested but the step has no mesh to "
            "fold the expert ring onto")
    if mesh is None or (not use_cp and not use_ep
                        and mesh.shape.get("model", 1) < 2):
        if plan.tp_impl == "overlap":
            raise ValueError(
                "tp_impl='overlap' was requested explicitly but the step has "
                "no 'model' mesh axis of size >= 2 to run the rings on")
        return None
    if not use_cp and not use_ep and select_tp_impl(plan.tp_impl) != "overlap":
        return None
    from repro.train.executor import make_executor_loss_fn  # noqa: PLC0415
    baxes = tuple(a for a in ("pod", "data")
                  if a in mesh.shape and (a != "pod" or plan.pp == 1))
    try:
        return make_executor_loss_fn(model.cfg, plan, mesh, baxes,
                                     z_loss=hyper.z_loss)
    except ValueError:
        if plan.tp_impl == "overlap" or use_cp or use_ep:
            raise                     # explicit request: surface the reason
        return None                   # auto: fall back to the GSPMD loss


def make_train_step(model: Model, plan: ParallelPlan,
                    hyper: Hyper = Hyper(),
                    mesh: Optional[Mesh] = None) -> Callable:
    loss_fn = (_overlap_loss_fn(model, plan, hyper, mesh)
               or make_loss_fn(model, hyper))
    value_and_grad = jax.value_and_grad(loss_fn, has_aux=True)

    def grad_fn(params, batch):
        # named scopes are op metadata only: a profiler trace splits the
        # step into "loss" (forward, "jvp(loss)"; backward and remat
        # recompute, "transpose(jvp(loss))"), "clip" and "optimizer"
        with jax.named_scope("loss"):
            return value_and_grad(params, batch)
    use_zero = (mesh is not None and plan.zero_stage >= 1
                and "data" in mesh.shape and mesh.shape["data"] > 1)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params, opt = state

        if use_zero:
            pspecs = shardlib.param_specs(params, model.cfg, plan, mesh)
            ospecs = shardlib.opt_state_specs(pspecs, params, plan, mesh)
            scatter = lambda tree: constrain_tree(tree, ospecs, mesh)
        else:
            scatter = lambda tree: tree

        if plan.microbatches > 1:
            mb = _split_microbatches(batch, plan.microbatches)

            def acc(carry, mbatch):
                g_acc, l_acc, a_acc = carry
                (loss, aux), grads = grad_fn(params, mbatch)
                # accumulate into the scattered shard: under ZeRO-1 each
                # microbatch's grads reduce-scatter here instead of
                # all-reducing into a replicated fp32 copy (g_acc's layout is
                # already pinned by the scattered g0 carry)
                g_acc = jax.tree.map(jnp.add, g_acc, scatter(grads))
                return (g_acc, l_acc + loss, a_acc + aux["moe_aux"]), None

            g0 = scatter(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (grads, loss, aux_sum), _ = jax.lax.scan(
                acc, (g0, jnp.float32(0.0), jnp.float32(0.0)), mb)
            grads = jax.tree.map(lambda g: g / plan.microbatches, grads)
            loss = loss / plan.microbatches
            aux = {"moe_aux": aux_sum / plan.microbatches}
        else:
            (loss, aux), grads = grad_fn(params, batch)
            grads = scatter(grads)

        with jax.named_scope("clip"):
            grads, gnorm = clip_by_global_norm(grads, hyper.grad_clip)
        with jax.named_scope("optimizer"):
            lr = cosine_schedule(opt.step, hyper.peak_lr, hyper.warmup_steps,
                                 hyper.total_steps)
            if use_zero:
                new_params, new_opt = adamw_update_sharded(
                    grads, opt, params, lr, mesh=mesh, param_specs=pspecs,
                    opt_specs=ospecs, weight_decay=hyper.weight_decay)
            else:
                new_params, new_opt = adamw_update(
                    grads, opt, params, lr, weight_decay=hyper.weight_decay)
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            "lr": lr,
            "moe_aux": aux["moe_aux"],
        }
        if plan.integrity == "audit":
            # SDC audit (survey §8.2): exact bitwise checksum of the updated
            # params + this step's grads, cross-checked across replicas.
            # Any nonzero divergence means some device computed different
            # bits — the recovery driver routes it through policy.sdc.
            from repro.ft.integrity import replica_divergence  # noqa: PLC0415
            cs, div = replica_divergence(
                {"params": new_params, "grads": grads}, mesh=mesh)
            metrics["integrity_checksum"] = cs
            metrics["integrity_div"] = div
        return TrainState(new_params, new_opt), metrics

    return train_step
