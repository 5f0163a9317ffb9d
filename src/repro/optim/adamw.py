"""AdamW, written against raw pytrees (optax is not in the image).

Moments are stored in fp32 regardless of compute dtype (mixed-precision
training keeps an fp32 master copy of optimizer state, survey §5.2.1). State
sharding follows ``repro.core.sharding.opt_state_specs`` — ZeRO-1 (survey
§6.2.1): moments shard over the ``data`` axis even when params replicate.

:func:`adamw_update` is the plain replicated math; :func:`adamw_update_sharded`
is the ZeRO-1 execution of the same math — grads are reduce-scattered onto the
moment shards (a sharding constraint that GSPMD lowers to reduce-scatter
instead of all-reduce), the elementwise update runs on each device's 1/DP slice
of the fp32 moments, and only the updated params are all-gathered back to
their replicated layout. Numerically identical to the replicated update;
per-device optimizer memory and update FLOPs drop by the data-axis size.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class AdamWState(NamedTuple):
    step: jax.Array          # scalar int32
    mu: Any                  # first moment, same tree as params
    nu: Any                  # second moment


def adamw_init(params: Any, *, mesh: Mesh = None,
               specs: Any = None) -> AdamWState:
    """Zero moments; with ``mesh`` + ``specs`` (PartitionSpecs from
    ``core.sharding.opt_state_specs``) they are *born* on the ZeRO-1 layout —
    data-scattered from step 0 instead of waiting for the first sharded
    update to constrain them. An elastic restore needs this: the state
    template's moment leaves must already carry the target shardings."""
    if mesh is not None and specs is not None:
        zeros = lambda p, s: jnp.zeros(p.shape, jnp.float32,
                                       device=NamedSharding(mesh, s))
        moments = lambda: jax.tree.map(zeros, params, specs)
        # replicated over the mesh, as the step returns it: a counter on one
        # device would give the second step another input layout to compile
        step = jnp.zeros((), jnp.int32, device=NamedSharding(mesh, P()))
    else:
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        moments = lambda: jax.tree.map(zeros, params)
        step = jnp.zeros((), jnp.int32)
    return AdamWState(step=step, mu=moments(), nu=moments())


def adamw_update(
    grads: Any,
    state: AdamWState,
    params: Any,
    lr,
    *,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
):
    """Returns (new_params, new_state). Decay is decoupled (AdamW)."""
    step = state.step + 1
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        mhat = m / c1
        vhat = v / c2
        delta = mhat / (jnp.sqrt(vhat) + eps)
        # no weight decay on 1-D params (norm scales, biases) — standard practice
        wd = weight_decay if p.ndim > 1 else 0.0
        newp = p.astype(jnp.float32) - lr * (delta + wd * p.astype(jnp.float32))
        return newp.astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    out = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamWState(step, new_m, new_v)


def constrain_tree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """Pin every leaf of ``tree`` to the matching PartitionSpec in ``specs``."""
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)), tree, specs)


def adamw_update_sharded(
    grads: Any,
    state: AdamWState,
    params: Any,
    lr,
    *,
    mesh: Mesh,
    param_specs: Any,
    opt_specs: Any,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
):
    """ZeRO-1 sharded AdamW step (survey §6.2.1).

    ``opt_specs`` (from ``core.sharding.opt_state_specs``) shard the fp32
    moments over the ``data`` axis; ``param_specs`` is the params' own layout.
    The grads/params inputs are constrained onto the moment shards (XLA emits
    a reduce-scatter/slice, not an all-reduce), the update math runs shard-
    local, and the updated params are constrained back to ``param_specs`` —
    the all-gather that completes the ZeRO-1 round trip.
    """
    grads = constrain_tree(grads, opt_specs, mesh)
    shard_state = AdamWState(state.step,
                             constrain_tree(state.mu, opt_specs, mesh),
                             constrain_tree(state.nu, opt_specs, mesh))
    shard_params = constrain_tree(params, opt_specs, mesh)
    new_params, new_state = adamw_update(
        grads, shard_state, shard_params, lr, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay)
    # moments stay scattered (that's the memory win); params re-replicate
    return constrain_tree(new_params, param_specs, mesh), new_state
