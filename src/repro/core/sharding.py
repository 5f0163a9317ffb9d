"""Sharding-rule engine: maps parameter pytree paths -> PartitionSpec.

GSPMD-style (survey §4.2.1): parameters get explicit layout annotations; activation
layouts are propagated by XLA from a handful of strategic constraints. The rules
implement the survey's hybrid-parallelism taxonomy:

- Tensor parallelism (§4.1.2, Megatron 1-D): "column" params shard their output dim
  on the ``model`` axis, "row" params their input dim.
- Data-parallel parameter sharding factor F (§4.1.1): F=1 replication,
  F=data-axis-size full sharding (ZeRO-3/FSDP); an extra ``data`` annotation is
  placed on the largest un-sharded dim.
- Expert parallelism (§4.1.5): expert-stacked params shard the expert dim
  over the *folded* expert ring (:func:`ep_fold_axes` — the cp × model axes
  the MoE sublayer re-reads as one flat ring of ``plan.ep`` slots, MoE
  parallel folding) instead of the hidden dim; shared experts and the router
  replicate over those axes because each fold rank routes its own sequence
  shard (:func:`ep_spec_for_param` is the executor/pipeline override).
- Vocab parallelism: embedding/LM head shard the vocab dim on ``model`` when
  divisible, else fall back to hidden-dim sharding (e.g. whisper's 51865 vocab).

All rules check divisibility: GSPMD would pad uneven shards, but padded layouts
waste FLOPs and skew the roofline, so non-divisible dims stay replicated and the
hillclimb loop (§Perf) reconsiders them explicitly.

Two tensor-parallel execution modes consume these rules
(``ParallelPlan.tp_impl``):

- ``"gspmd"`` (annotation-only): :func:`param_specs` layouts + a handful of
  activation constraints; XLA's partitioner inserts a blocking all-reduce
  after every row GEMM and keeps (B, S, d) activations replicated between
  blocks.
- ``"overlap"`` (``train/tensor_parallel.py``): the same column/row/vocab
  classification feeds :func:`overlap_param_specs`, the in_specs of an
  explicit ``shard_map``. There the all-gather/reduce-scatter pair of each
  column/row GEMM is decomposed into ``ppermute`` ring steps interleaved with
  partial GEMM tiles, and activations stay **sequence-sharded**
  ``(batch, seq/tp, d)`` between blocks (Megatron-SP, survey §4.1.4) — see
  :func:`seq_activation_spec`. RMSNorm, residual adds and the embedding
  lookup run on sequence shards; the full sequence is only re-materialized
  inside a block, fused into the first GEMM's ring ticks.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .config import ModelConfig, ParallelPlan

AxisName = Optional[str]


# Leaf-name classification (see models/* for the naming convention).
# wB/wC (SSM state projections) are deliberately NOT column-sharded: sharding
# the tiny state dim would force psum-per-contraction inside the SSD scan;
# heads (via wz/wx/wdt) carry the model-parallel dim instead.
_COL_KEYS = {"wq", "wk", "wv", "gate", "up", "wz", "wx", "wdt"}
_ROW_KEYS = {"wo", "down", "out_proj"}
_REPLICATED_KEYS = {"scale", "bias", "A_log", "D", "dt_bias", "bq", "bk", "bv",
                    "wB", "wC"}
_CONV_KEYS = {"conv_x", "conv_B", "conv_C", "conv_bias_x", "conv_bias_B",
              "conv_bias_C"}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for entry in path:
        if isinstance(entry, jax.tree_util.DictKey):
            names.append(str(entry.key))
        elif isinstance(entry, jax.tree_util.GetAttrKey):
            names.append(entry.name)
        else:
            names.append(str(entry))
    return tuple(names)


def _divisible(size: int, mesh: Mesh, axis: str) -> bool:
    return (axis in mesh.shape and mesh.shape[axis] > 1
            and size % mesh.shape[axis] == 0)


def _tp_ok(size: int, mesh: Mesh, plan: ParallelPlan) -> bool:
    """Model-axis (TP) sharding is available unless the dp_over_model remap
    reassigned that axis to data parallelism."""
    return (not plan.dp_over_model) and _divisible(size, mesh, "model")


def _dp_axes(mesh: Mesh, plan: ParallelPlan):
    """Axes carrying data parallelism for parameter/optimizer sharding."""
    axes = ["data"] if "data" in mesh.shape else []
    if plan.dp_over_model and "model" in mesh.shape:
        axes.append("model")
    return tuple(axes)


def _add_fsdp(spec: list, shape: Tuple[int, ...], mesh: Mesh, plan: ParallelPlan) -> None:
    """Annotate the largest still-replicated dim with the DP axes (ZeRO-3/FSDP).
    Under the dp_over_model remap the DP domain is ("data", "model")."""
    if plan.dp_shard <= 1:
        return
    axes = _dp_axes(mesh, plan)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    if n <= 1:
        return
    candidates = [
        (shape[i], i) for i in range(len(shape))
        if spec[i] is None and shape[i] % n == 0 and shape[i] > 1
    ]
    if candidates:
        _, idx = max(candidates)
        spec[idx] = axes if len(axes) > 1 else axes[0]


def spec_for_param(
    path_names: Tuple[str, ...],
    shape: Tuple[int, ...],
    cfg: ModelConfig,
    plan: ParallelPlan,
    mesh: Mesh,
) -> P:
    name = path_names[-1]
    stacked = "layers" in path_names            # leading layer-stack dim
    is_expert = "experts" in path_names         # (L, E, ...) expert-stacked

    spec: list = [None] * len(shape)

    if name in _REPLICATED_KEYS or name in _CONV_KEYS:
        # Small tensors: replicate over model axis; FSDP may still slice them.
        _add_fsdp(spec, shape, mesh, plan)
        return P(*spec)

    if name == "tok" or (name == "w" and "lm_head" in path_names):
        # Embedding (V, d) / LM head (d, V): vocab-parallel when divisible.
        vdim = 0 if name == "tok" else 1
        ddim = 1 - vdim
        if _tp_ok(shape[vdim], mesh, plan):
            spec[vdim] = "model"
        elif _tp_ok(shape[ddim], mesh, plan):
            spec[ddim] = "model"
        _add_fsdp(spec, shape, mesh, plan)
        return P(*spec)

    if name == "router":
        # (L?, d, E): replicate over model (tiny); FSDP on d.
        _add_fsdp(spec, shape, mesh, plan)
        return P(*spec)

    if is_expert:
        # (L, E, d, de) or (L, E, de, d)
        e_dim = 1 if stacked else 0
        axes = ep_fold_axes(plan)
        n_fold = 1
        for a in axes:
            n_fold *= mesh.shape.get(a, 0)
        if axes and n_fold > 0 and shape[e_dim] % n_fold == 0:
            # expert dim over the folded expert ring (MoE parallel folding)
            spec[e_dim] = axes if len(axes) > 1 else axes[0]
        else:
            # tensor-parallel inside each expert: shard the d_expert dim
            de_dim = len(shape) - 2 if name in _ROW_KEYS else len(shape) - 1
            if _tp_ok(shape[de_dim], mesh, plan):
                spec[de_dim] = "model"
        _add_fsdp(spec, shape, mesh, plan)
        return P(*spec)

    # tensor parallelism follows the mesh: shard whenever a model axis exists
    # and divides (plan.tp is informational; the mesh is the source of truth)
    if name in _COL_KEYS:
        out_dim = len(shape) - 1
        if _tp_ok(shape[out_dim], mesh, plan):
            spec[out_dim] = "model"
        _add_fsdp(spec, shape, mesh, plan)
        return P(*spec)

    if name in _ROW_KEYS:
        in_dim = len(shape) - 2
        if _tp_ok(shape[in_dim], mesh, plan):
            spec[in_dim] = "model"
        _add_fsdp(spec, shape, mesh, plan)
        return P(*spec)

    # Unknown leaf: replicate (safe), FSDP if large.
    _add_fsdp(spec, shape, mesh, plan)
    return P(*spec)


def param_specs(params: Any, cfg: ModelConfig, plan: ParallelPlan, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs too)."""
    def one(path, leaf):
        return spec_for_param(_path_names(path), tuple(leaf.shape), cfg, plan, mesh)
    return jax.tree_util.tree_map_with_path(one, params)


def param_shardings(params: Any, cfg: ModelConfig, plan: ParallelPlan, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params, cfg, plan, mesh)
    )


# ---------------------------------------------------------------------------
# Expert parallelism (folded expert ring, survey §4.1.5)


def ep_fold_axes(plan: ParallelPlan) -> Tuple[str, ...]:
    """The mesh axes the expert ring folds onto (MoE parallel folding).

    ``plan.ep`` ranks re-read the devices of the existing cp × model ring as
    one flat expert axis: ("cp", "model") when both are engaged, just one of
    them when only it is, and ("model",) in the ep-only placement (tp == cp
    == 1 — experts ride the model axis and attention runs as a cp ring over
    it). Empty tuple when EP is off."""
    if plan.ep <= 1:
        return ()
    axes = ("cp",) if plan.cp > 1 else ()
    if plan.tp > 1 or plan.cp <= 1:
        axes = axes + ("model",)
    return axes


def ep_spec_for_param(path_names: Tuple[str, ...], shape: Tuple[int, ...],
                      plan: ParallelPlan) -> Optional[P]:
    """EP override for one leaf entering the executor/pipeline ``shard_map``.

    Returns the spec EP imposes, or ``None`` when the leaf is not
    EP-affected (the caller falls through to its tp/overlap classification).
    This is the single source of truth three consumers share — the executor
    in_specs, the pipeline's per-stage param specs, and the pipeline's
    grad-finish psum logic:

    - routed experts ((L?, E, ...) with "experts" in the path): the expert
      dim shards over :func:`ep_fold_axes`; the d_expert dim stays full, so
      each fold rank holds complete experts and its expert-grad shard needs
      **no** cp/model psum;
    - shared experts and the router: replicated *full-width* over the fold
      axes — every fold rank routes its own sequence shard, so there is no
      width-partial psum to complete them; their grads **do** psum over the
      fold axes.
    """
    axes = ep_fold_axes(plan)
    if not axes:
        return None
    if "experts" in path_names:
        e_dim = 1 if "layers" in path_names else 0
        spec: list = [None] * len(shape)
        spec[e_dim] = axes if len(axes) > 1 else axes[0]
        return P(*spec)
    if "shared" in path_names or path_names[-1] == "router":
        return P(*([None] * len(shape)))
    return None


# ---------------------------------------------------------------------------
# Overlap-TP (shard_map ring path) parameter specs


def overlap_spec_for_param(path_names: Tuple[str, ...],
                           shape: Tuple[int, ...], cfg: ModelConfig) -> P:
    """Spec for one leaf entering the overlap-TP ``shard_map``.

    Same column/row/vocab classification as :func:`spec_for_param`, but:

    - always ``model``-sharded on the classified dim (the ring path validates
      divisibility up front — ``tensor_parallel.check_overlap_support`` —
      instead of silently replicating);
    - never FSDP-annotated (params enter the shard_map replicated over
      ``data``; ZeRO handles optimizer sharding outside the loss);
    - the embedding is always vocab-sharded: the ring path does the Megatron
      masked-lookup + psum, so no hidden-dim fallback exists;
    - small SSM per-head/per-channel leaves (A_log, D, dt_bias, conv_*,
      scale) stay replicated — the executor's ``ssm_block_ex``
      (train/executor.py) slices each rank's head/channel chunk explicitly.
    """
    name = path_names[-1]
    spec: list = [None] * len(shape)
    if name == "tok" or (name == "w" and "lm_head" in path_names):
        spec[0 if name == "tok" else 1] = "model"
    elif "experts" in path_names and name in ("gate", "up"):
        spec[-1] = "model"                      # (L?, E, d, de): shard d_expert
    elif "experts" in path_names and name == "down":
        spec[-2] = "model"
    elif name in _COL_KEYS:
        spec[-1] = "model"
    elif name in _ROW_KEYS:
        spec[-2] = "model"
    return P(*spec)


def overlap_param_specs(params: Any, cfg: ModelConfig, plan: ParallelPlan,
                        mesh: Mesh) -> Any:
    """PartitionSpec pytree for ``shard_map`` in_specs on the overlap-TP path."""
    del plan, mesh  # classification is static; callers validated divisibility
    def one(path, leaf):
        return overlap_spec_for_param(_path_names(path), tuple(leaf.shape), cfg)
    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# Activation / batch specs


def batch_axes(mesh: Mesh, plan: ParallelPlan) -> Tuple[str, ...]:
    """Mesh axes the global batch is split over."""
    axes = []
    if "pod" in mesh.shape and plan.pp == 1:
        axes.append("pod")
    axes.append("data")
    return tuple(axes)


def data_spec(mesh: Mesh, plan: ParallelPlan, ndim: int = 2) -> P:
    """Spec for (batch, seq, ...) token arrays."""
    return P(batch_axes(mesh, plan), *([None] * (ndim - 1)))


def activation_spec(mesh: Mesh, plan: ParallelPlan) -> P:
    """(batch, seq, d_model) residual-stream constraint."""
    return P(batch_axes(mesh, plan), None, None)


def seq_activation_spec(mesh: Mesh, plan: ParallelPlan) -> P:
    """(batch, seq/tp, d_model) sequence-sharded residual stream — the
    between-blocks layout of the overlap-TP path (Megatron-SP, §4.1.4)."""
    return P(batch_axes(mesh, plan), "model", None)


def cp_activation_spec(mesh: Mesh, plan: ParallelPlan) -> P:
    """(batch, seq/(cp·tp), d_model) residual stream under context
    parallelism (``plan.cp > 1``, survey §4.1.4): the sequence dim carries
    the "cp" axis end to end — and composes with the overlap-TP "model"
    sharding when both are on — so no device ever holds the full context.
    The block executor (train/executor.py) owns the in-block placement
    (ring/gathered attention, SSD state chain, shard-local MoE routing)."""
    seq_axes = ("cp", "model") if (plan.tp > 1 and "model" in mesh.shape) \
        else "cp"
    return P(batch_axes(mesh, plan), seq_axes, None)


def kv_cache_spec(mesh: Mesh, plan: ParallelPlan, seq_sharded: bool = True) -> P:
    """(batch, seq, kv_heads, head_dim) decode cache: batch@data, seq@model."""
    model = "model" if (seq_sharded and plan.seq_shard_decode) else None
    return P(batch_axes(mesh, plan), model, None, None)


def logits_spec(cfg: ModelConfig, mesh: Mesh, plan: ParallelPlan) -> P:
    vocab_axis = "model" if cfg.vocab % mesh.shape.get("model", 1) == 0 else None
    return P(batch_axes(mesh, plan), None, vocab_axis)


def constrain(x, mesh: Mesh, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Decode-cache sharding (DESIGN.md §3: (batch@data, seq@model, heads, hd))

_KV_CACHE_KEYS = {"k", "v", "attn_k", "attn_v"}
_CROSS_CACHE_KEYS = {"cross_k", "cross_v"}


def cache_specs(cache: Any, plan: ParallelPlan, mesh: Mesh,
                batch_axes: Tuple[str, ...]) -> Any:
    """Spec tree for a decode cache (leaves are layer-stacked: (L, B, ...))."""
    baxes = batch_axes if batch_axes else None
    model_free = "model" not in (batch_axes or ())

    def one(path, leaf):
        name = _path_names(path)[-1]
        shape = tuple(leaf.shape)
        bdim = 1                                     # (L, B, ...)
        spec = [None] * len(shape)
        if baxes:
            spec[bdim] = baxes
        if name in _KV_CACHE_KEYS:
            # (L, B, T, H, hd): shard T on model if enabled & divisible
            if (model_free and plan.seq_shard_decode
                    and _divisible(shape[2], mesh, "model")):
                spec[2] = "model"
        elif name in _CROSS_CACHE_KEYS:
            pass                                     # enc_frames rarely divisible
        elif name == "state":
            # SSM state (L, B, nh, hp, n): shard heads on model
            if model_free and _divisible(shape[2], mesh, "model"):
                spec[2] = "model"
        elif name.startswith("conv_"):
            # (L, B, K-1, C): shard channels on model
            if model_free and _divisible(shape[-1], mesh, "model"):
                spec[-1] = "model"
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache)


# ---------------------------------------------------------------------------
# Optimizer-state sharding (ZeRO, survey §6.2)


def opt_state_specs(pspecs: Any, params: Any, plan: ParallelPlan, mesh: Mesh) -> Any:
    """Specs for per-param optimizer moments.

    zero_stage >= 1 shards moments over ``data`` even when params are replicated
    (ZeRO-1): take the param spec and add ``data`` on the largest free dim.
    """
    if plan.zero_stage == 0:
        return pspecs

    def one(spec: P, p) -> P:
        parts = list(spec) + [None] * (len(p.shape) - len(spec))
        if any(ax == "data" or (isinstance(ax, tuple) and "data" in ax) for ax in parts):
            return spec  # already data-sharded (FSDP)
        cands = [
            (p.shape[i], i) for i in range(len(p.shape))
            if parts[i] is None and _divisible(p.shape[i], mesh, "data") and p.shape[i] > 1
        ]
        if not cands:
            return spec
        _, idx = max(cands)
        parts[idx] = "data"
        return P(*parts)

    return jax.tree_util.tree_map(one, pspecs, params)


def train_state_specs(state: Any, cfg: ModelConfig, plan: ParallelPlan,
                      mesh: Mesh) -> Any:
    """PartitionSpec pytree for a whole ``train.TrainState`` (params + AdamW
    moments), matching what the jitted step's sharding constraints produce.

    This is the layout contract an elastic restore re-slices onto: params get
    :func:`param_specs`, the fp32 moments get :func:`opt_state_specs` (ZeRO-1
    scatters them over ``data``), the step counter replicates. Duck-typed on
    the NamedTuple shape ``state.params`` / ``state.opt.{step, mu, nu}`` so
    core stays import-independent of the train layer.
    """
    pspecs = param_specs(state.params, cfg, plan, mesh)
    ospecs = opt_state_specs(pspecs, state.params, plan, mesh)
    return state._replace(
        params=pspecs,
        opt=state.opt._replace(step=P(), mu=ospecs, nu=ospecs))


def train_state_shardings(state: Any, cfg: ModelConfig, plan: ParallelPlan,
                          mesh: Mesh) -> Any:
    """:func:`train_state_specs` as concrete ``NamedSharding``s — the
    ``shardings`` argument of ``CheckpointManager.restore_resharded``."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        train_state_specs(state, cfg, plan, mesh),
        is_leaf=lambda x: isinstance(x, P))


def bytes_per_device(params: Any, shardings: Any) -> int:
    """Analytic parameter bytes resident per device under the given shardings."""
    total = 0
    for p, s in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(shardings)):
        n_shards = 1
        spec = s.spec if isinstance(s, NamedSharding) else s
        mesh = s.mesh if isinstance(s, NamedSharding) else None
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                n_shards *= mesh.shape[a] if mesh else 1
        total += int(np.prod(p.shape)) * p.dtype.itemsize // max(n_shards, 1)
    return total
