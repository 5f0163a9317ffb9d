"""Production mesh definition (as a function — importing this module must not
touch jax device state).

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

Axis semantics (DESIGN.md §3): ``model`` is the innermost/highest-locality axis
(TP/EP/sequence), ``data`` is DP/FSDP, ``pod`` crosses the inter-pod DCN and
carries either DP (default) or pipeline stages. ``cp`` (context parallelism,
survey §4.1.4) splits off the data axis when requested: it carves the
*sequence* dimension, so it wants locality between ``data`` and ``model`` —
ring-attention ppermutes are nearest-neighbour transfers, heavier than DP's
once-per-step grad reduction but lighter than TP's per-GEMM rings.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
from jax.sharding import AxisType


def cpu_child_env(n_devices: int = 1) -> Dict[str, str]:
    """Environment entries for a child process that runs JAX on
    ``n_devices`` virtual CPU devices.

    The child is pinned to the CPU whatever its parent holds: on a machine
    with a chip the parent may own it, and a child that reached for it would
    fail or hang. XLA:CPU keys a collective's rendezvous on its channel id,
    and every shard_map collective carries channel id 1; its default
    (concurrency-optimized) scheduler may start two data-independent
    collective-permutes of one program at once, which then share one
    rendezvous and abort ("id < num_threads"). Every device enters every
    collective, so the program is sound; the sequential scheduler runs one
    collective at a time.
    """
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (f"--xla_force_host_platform_device_count={n_devices} "
                      "--xla_cpu_enable_concurrency_optimized_scheduler=false"),
    }


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """The one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which
    ``with_sharding_constraint`` and the GSPMD constrainers refuse to run.
    This code places arrays with ``NamedSharding`` and ``shard_map``, which
    is what Auto axes are for. ``devices`` lets a compile rehearsal hand in
    the devices of a described (not attached) topology.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False, cp: int = 1):
    """``cp > 1`` splits the data axis into (data/cp, cp): same chip count,
    sequence sharded over the new "cp" axis (``ParallelPlan.cp``)."""
    if cp > 1:
        if 16 % cp:
            raise ValueError(f"cp={cp} must divide the 16-wide data axis")
        shape = (2, 16 // cp, cp, 16) if multi_pod else (16 // cp, cp, 16)
        axes = (("pod", "data", "cp", "model") if multi_pod
                else ("data", "cp", "model"))
        return make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(shape: Tuple[int, ...] = None, axes: Tuple[str, ...] = None):
    """Small mesh over whatever devices exist (tests: 8 host devices)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n) if n > 1 else (1, 1)
        axes = ("data", "model")
    return make_mesh(shape, axes)


def shrink_mesh(mesh, axis: str, lost: int = 1):
    """Rebuild ``mesh`` after simulated host loss: drop ``lost`` slices of
    ``axis`` (survey §8.3.2 elastic recovery — resume on fewer hosts).

    Keeps the surviving devices and every other axis intact, e.g. a 2×2
    ("data", "model") mesh losing one data slice becomes 1×2. The caller
    re-jits its step and reshard-restores onto the result.
    """
    from jax.sharding import Mesh  # noqa: PLC0415
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r}: {dict(mesh.shape)}")
    size = mesh.shape[axis]
    if lost >= size:
        raise ValueError(f"cannot drop {lost} of {size} {axis!r} slices")
    dim = mesh.axis_names.index(axis)
    keep = [slice(None)] * mesh.devices.ndim
    keep[dim] = slice(0, size - lost)
    return Mesh(mesh.devices[tuple(keep)], mesh.axis_names,
                axis_types=mesh.axis_types)


def batch_axes_for(mesh, global_batch: int, pp: int = 1,
                   dp_over_model: bool = False) -> Tuple[str, ...]:
    """Mesh axes to shard the batch over, largest-first, divisibility-checked.

    long_500k has global_batch=1 — the batch stays replicated and parallelism
    comes entirely from the model/sequence dimensions. With ``dp_over_model``
    (mesh remap for small models) the model axis also carries batch.
    """
    axes = []
    div = 1
    wanted = ("pod", "data", "model") if dp_over_model else ("pod", "data")
    candidates = [a for a in wanted if a in mesh.shape]
    if pp > 1 and "pod" in candidates:
        candidates.remove("pod")          # pod axis carries pipeline stages
    for a in candidates:
        if global_batch % (div * mesh.shape[a]) == 0:
            axes.append(a)
            div *= mesh.shape[a]
    return tuple(axes)
