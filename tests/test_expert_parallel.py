"""Expert parallelism through the block executor (survey §4.1.5).

Equivalence contract: ``plan.ep > 1`` shards the routed experts over the
*folded* cp × model device ring (MoE parallel folding — attention keeps its
cp/tp mapping while the MoE sublayer re-reads the same devices as one flat
expert axis) and computes the same math as the single-device dense-dispatch
path, for BOTH ``ep_impl`` choices: the blocking all-to-all and the
overlapped ``ppermute``-tick ring of
:func:`repro.kernels.dispatch.dispatch_ep_a2a`. Exact when no tokens drop
(capacity_factor >= E/top_k — the same shard-local-routing contract cp/tp
use); loss to ~1 ulp of fp32 and gradients at reassociation tolerance.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Family, ModelConfig, MoEConfig, ParallelPlan
from repro.kernels.dispatch import EP_IMPLS, dispatch_ep_a2a, select_ep_impl


def _moe_cfg(e=4, k=2, cap=2.0, shared=0, layers=2):
    return ModelConfig("tmoe", Family.MOE, n_layers=layers, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                       moe=MoEConfig(num_experts=e, top_k=k, d_expert=64,
                                     num_shared_experts=shared,
                                     capacity_factor=cap))


# ---------------------------------------------------------------------------
# knob / dispatch / layout units (in-process: no devices needed)


def test_ep_knob_validation():
    cfg = _moe_cfg()
    with pytest.raises(ValueError, match="ep_impl"):
        ParallelPlan(ep_impl="ring").validate(cfg)
    # the legacy bool knob is rejected with a migration hint, not coerced
    with pytest.raises(ValueError, match="use ep=<degree>"):
        ParallelPlan(ep=True).validate(cfg)
    with pytest.raises(ValueError, match="use ep=<degree>"):
        ParallelPlan(ep=False).validate(cfg)
    with pytest.raises(ValueError, match="ep must be"):
        ParallelPlan(ep=0).validate(cfg)
    dense = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 128)
    with pytest.raises(ValueError, match="MoE"):
        ParallelPlan(ep=2).validate(dense)
    # ep composes with tp only via the explicit rings
    with pytest.raises(ValueError, match="overlap"):
        ParallelPlan(ep=2, tp=2, tp_impl="gspmd").validate(cfg)
    with pytest.raises(ValueError, match="dp_over_model"):
        ParallelPlan(ep=2, dp_over_model=True).validate(cfg)
    # MoE parallel folding pins ep to cp×tp when either is engaged
    with pytest.raises(ValueError, match="must equal cp×tp"):
        ParallelPlan(ep=2, cp=2, tp=2, tp_impl="overlap").validate(cfg)
    ParallelPlan(ep=4, cp=2, tp=2, tp_impl="overlap").validate(cfg)
    ParallelPlan(ep=2, cp=2).validate(cfg)
    # expert count must split evenly over the ring
    with pytest.raises(ValueError, match="must divide num_experts"):
        ParallelPlan(ep=3).validate(_moe_cfg(e=4))
    # ep-only (mesh-checked later) and the cp-only composition are fine
    ParallelPlan(ep=2).validate(cfg)


def test_ep_token_dropping_divergence_is_flagged():
    """Shard-local routing with a token-dropping capacity factor warns at
    validation time (same documented divergence as cp / overlap-tp)."""
    dropping = _moe_cfg(cap=1.0)
    with pytest.warns(UserWarning, match="token-dropping"):
        ParallelPlan(ep=2).validate(dropping)
    # no-drop capacity (>= E/top_k) is exact: no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ParallelPlan(ep=2).validate(_moe_cfg(cap=2.0))


def test_select_ep_impl_rules():
    assert EP_IMPLS == ("auto", "blocking", "overlap")
    assert select_ep_impl("auto") == "overlap"
    assert select_ep_impl("blocking") == "blocking"
    assert select_ep_impl("overlap") == "overlap"
    with pytest.raises(ValueError, match="ep_impl"):
        select_ep_impl("bogus")


def test_dispatch_ep_a2a_degenerate_cases():
    """size == 1 delegates straight to fn; a non-divisible expert dim is a
    loud error before any collective is traced."""
    w = jnp.ones((4, 8, 8), jnp.float32)
    h = jnp.ones((4, 3, 8), jnp.float32)
    fn = lambda w_, h_: h_ + 1.0
    out = dispatch_ep_a2a(fn, w, h, axis="model", size=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(h) + 1.0)
    with pytest.raises(ValueError, match="divide"):
        dispatch_ep_a2a(fn, w, h, axis="model", size=3)
    with pytest.raises(ValueError, match="ep_impl"):
        dispatch_ep_a2a(fn, w, h, axis="model", size=2, impl="nope")


def test_ep_fold_layout_units():
    """ep_fold_axes / ep_spec_for_param are the single source of truth for
    the folded expert layout."""
    from jax.sharding import PartitionSpec as P
    from repro.core.sharding import ep_fold_axes, ep_spec_for_param

    assert ep_fold_axes(ParallelPlan()) == ()
    assert ep_fold_axes(ParallelPlan(ep=2)) == ("model",)
    assert ep_fold_axes(ParallelPlan(ep=2, cp=2)) == ("cp",)
    assert ep_fold_axes(ParallelPlan(ep=4, cp=2, tp=2, tp_impl="overlap")) \
        == ("cp", "model")
    assert ep_fold_axes(ParallelPlan(ep=2, tp=2, tp_impl="overlap")) \
        == ("model",)

    plan = ParallelPlan(ep=4, cp=2, tp=2, tp_impl="overlap")
    # stacked (layers) expert leaves shard the expert dim (dim 1)
    assert ep_spec_for_param(("layers", "moe", "experts", "gate"),
                             (2, 4, 64, 64), plan) \
        == P(None, ("cp", "model"), None, None)
    # unstacked expert leaves shard dim 0
    assert ep_spec_for_param(("moe", "experts", "down"), (4, 64, 64),
                             ParallelPlan(ep=2)) == P("model", None, None)
    # shared experts and the router replicate full-width
    assert ep_spec_for_param(("layers", "moe", "shared", "gate"),
                             (2, 64, 64), plan) == P(None, None, None)
    assert ep_spec_for_param(("layers", "moe", "router"), (2, 64, 4), plan) \
        == P(None, None, None)
    # non-MoE leaves keep their base (tp / replicated) classification
    assert ep_spec_for_param(("layers", "attn", "wq"), (2, 64, 64), plan) \
        is None
    assert ep_spec_for_param(("layers", "moe", "experts", "gate"),
                             (2, 4, 64, 64), ParallelPlan()) is None


def test_ep_dispatch_routing():
    """resolve_context folds the expert ring onto the resolved placement."""
    from repro.train.executor import resolve_context
    cfg = _moe_cfg(cap=2.0)

    class M:
        shape = {"data": 1, "model": 2}
    # ep-only: experts ride the model axis, attention becomes a cp ring on it
    ctx = resolve_context(cfg, ParallelPlan(ep=2), M, ("data",))
    assert ctx.tp is None and ctx.ep is not None
    assert ctx.ep.size == 2 and ctx.ep.axis == "model"
    assert ctx.cp is not None and ctx.cp.axis == "model" and ctx.cp.size == 2
    assert ctx.ep_impl == "overlap" and ctx.n_rep == 2
    assert ctx.aux_axes == ("data", "model")

    class M2:
        shape = {"data": 1, "cp": 2, "model": 2}
    ctx = resolve_context(
        cfg, ParallelPlan(ep=4, cp=2, tp=2, tp_impl="overlap",
                          ep_impl="blocking"), M2, ("data",))
    assert ctx.ep.size == 4 and ctx.ep.axis == ("cp", "model")
    assert ctx.tp.size == 2 and ctx.cp.axis == "cp" and ctx.cp.size == 2
    assert ctx.ep_impl == "blocking"
    assert ctx.aux_axes == ("data", "cp", "model") and ctx.n_rep == 4

    # a fold-size mismatch against the actual mesh is an error, not a
    # silent re-mapping
    with pytest.raises(ValueError, match="folded"):
        resolve_context(cfg, ParallelPlan(ep=2, cp=2, tp=2,
                                          tp_impl="overlap"), M2, ("data",))
    # ep-only needs a model axis of exactly that size to ride
    with pytest.raises(ValueError, match="model"):
        resolve_context(cfg, ParallelPlan(ep=4), M, ("data",))


def test_train_step_routes_ep():
    """make_train_step raises loudly when plan.ep has no mesh to fold onto
    (no silent GSPMD fallback for an explicit ep request)."""
    from repro.models import build_model
    from repro.train import Hyper, make_train_step
    cfg = _moe_cfg()
    plan = ParallelPlan(ep=2, compute_dtype="float32")
    model = build_model(cfg, plan)
    with pytest.raises(ValueError, match="ep"):
        make_train_step(model, plan, Hyper(), mesh=None)


# ---------------------------------------------------------------------------
# overlap == blocking == dense single-device, per MoE flavor


_FAMILY_EQUIV_TEMPLATE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import (Family, InputShape, ModelConfig, MoEConfig,
                        ParallelPlan)
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.executor import make_executor_loss_fn
from repro.launch.mesh import make_mesh

cfg = {cfg}
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {{k: jnp.asarray(v) for k, v in ds.batch(0).items()}}
Z = 1e-4   # nonzero: z_loss must thread through the sharded nll reduction

plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))
lf = make_loss_fn(model, Hyper(z_loss=Z))
ref_loss, ref_g = jax.jit(
    jax.value_and_grad(lambda p, b: lf(p, b)[0]))(params, batch)

def check(tag, plan, mesh, baxes, atol):
    elf = make_executor_loss_fn(cfg, plan, mesh, baxes, z_loss=Z)
    el, eg = jax.jit(jax.value_and_grad(lambda p, b: elf(p, b)[0]))(
        params, batch)
    assert abs(float(ref_loss) - float(el)) < 2e-6, (
        tag, float(ref_loss), float(el))
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ref_g),
                                 jax.tree_util.tree_leaves_with_path(eg)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=atol,
            err_msg=f"{{tag}} {{jax.tree_util.keystr(path)}}")
    print(tag, "== single-device, loss", float(el))

# ep-only: 1x2 and 2x2 (data, model) meshes — experts ride the model axis
for mesh_shape in [(1, 2), (2, 2)]:
    mesh = make_mesh(mesh_shape, ("data", "model"))
    for impl in ("blocking", "overlap"):
        plan = ParallelPlan(remat="none", compute_dtype="float32", ep=2,
                            ep_impl=impl{extra_knobs})
        check(("ep-only", mesh_shape, impl), plan, mesh, ("data",), 1e-6)

# folded: ep == cp x tp == 4 on a (data, cp, model) mesh — attention and
# MoE use different mappings of the same four devices
mesh = make_mesh((1, 2, 2), ("data", "cp", "model"))
for impl in ("blocking", "overlap"):
    plan = ParallelPlan(remat="none", compute_dtype="float32", cp=2, tp=2,
                        tp_impl="overlap", cp_impl="ring", ep=4,
                        ep_impl=impl{extra_knobs})
    check(("folded", impl), plan, mesh, ("data",), 3e-6)
print("EP_EQUIV_OK")
"""

# capacity_factor >= E/top_k -> no drops: ep routes per shard while the
# baseline routes globally, so drop *decisions* could differ; with no drops
# the per-token math is identical (the dropping case warns at validation —
# see test_ep_token_dropping_divergence_is_flagged)
_OLMOE_CFG = """ModelConfig("tmoe", Family.MOE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                 moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                               capacity_factor=2.0))"""
_DEEPSEEK_CFG = """ModelConfig("tmoe", Family.MOE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                 moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                               num_shared_experts=1, capacity_factor=2.0))"""


def test_ep_matches_single_device_olmoe(multidevice):
    """OLMoE-style routed-only MoE: overlap == blocking == dense."""
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_OLMOE_CFG, extra_knobs=""))


def test_ep_matches_single_device_deepseek_shared(multidevice):
    """DeepSeek-style shared experts stay replicated full-width next to the
    fold-sharded routed experts."""
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_DEEPSEEK_CFG,
                                              extra_knobs=""))


def test_ep_matches_single_device_scatter_dispatch(multidevice):
    """The MegaBlocks-style scatter dispatch feeds the same (E, C, d)
    buffers into the a2a seam as the einsum dispatch."""
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(
        cfg=_DEEPSEEK_CFG, extra_knobs=', moe_dispatch="scatter"'))


# ---------------------------------------------------------------------------
# EP x TP x CP x PP composition


def test_ep_pp_composition(multidevice):
    """The expert ring inside each pipeline tick, under both schedules, vs
    the per-microbatch single-device oracle (routing/aux are microbatch-local
    statistics — grad-accumulation semantics)."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import Family, InputShape, ModelConfig, MoEConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.pipeline import pipelined_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tmoe", Family.MOE, n_layers=4, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                  moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                                num_shared_experts=1, capacity_factor=2.0))
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
Z = 1e-4
M = 4
plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))
lf = make_loss_fn(model, Hyper(z_loss=Z))
mb = {k: v.reshape((M, v.shape[0] // M) + v.shape[1:])
      for k, v in batch.items()}
vg = jax.jit(jax.value_and_grad(lambda p, b: lf(p, b)[0]))
ref_losses, ref_gs = [], []
for i in range(M):
    l, g = vg(params, {k: v[i] for k, v in mb.items()})
    ref_losses.append(float(l)); ref_gs.append(g)
ref_loss = np.mean(ref_losses)
ref_g = jax.tree.map(lambda *x: sum(x) / M, *ref_gs)

def check(tag, plan, mesh, baxes, atol):
    plf = pipelined_loss_fn(cfg, plan, mesh, baxes, z_loss=Z)
    pl, pg = jax.jit(jax.value_and_grad(lambda p, b: plf(p, b)[0]))(
        params, batch)
    assert abs(float(ref_loss) - float(pl)) < 2e-6, (tag, float(pl))
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ref_g),
                                 jax.tree_util.tree_leaves_with_path(pg)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=atol,
            err_msg=f"{tag} {jax.tree_util.keystr(path)}")
    print(tag, "== per-microbatch oracle, loss", float(pl))

# EP x CP x PP: the expert ring folds onto cp alone, both schedules
mesh = make_mesh((2, 1, 2), ("pod", "data", "cp"))
for sched in ("gpipe", "1f1b"):
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2, cp=2,
                        ep=2, ep_impl="overlap", microbatches=M,
                        pp_schedule=sched, cp_impl="ring")
    check(("ep x cp x pp", sched), plan, mesh, ("data",), 1e-6)

# EP x TP x CP x PP: all four explicit axes in one 1F1B tick
mesh = make_mesh((2, 2, 2), ("pod", "cp", "model"))
plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2, cp=2, tp=2,
                    ep=4, ep_impl="overlap", microbatches=M,
                    tp_impl="overlap", cp_impl="ring")
check("ep x tp x cp x pp (1f1b)", plan, mesh, (), 3e-6)

# ep-only has no axis to fold onto under pp — rejected, not mislaid
mesh = make_mesh((2, 1), ("pod", "data"))
try:
    pipelined_loss_fn(cfg, ParallelPlan(pp=2, ep=2, microbatches=M),
                      mesh, ("data",))
    raise SystemExit("expected ep-only x pp to raise")
except ValueError as e:
    assert "ep-only" in str(e), e
print("EP_PP_OK")
""")


# ---------------------------------------------------------------------------
# checkpoint: the folded expert layout round-trips and reshards


def test_ep_checkpoint_reshard(multidevice):
    """EP-sharded state saves per-device expert shards, the manifest records
    ep + ep_impl, a mismatched ep layout is refused for replay, and
    restore_resharded re-places the experts onto a *different* ep fold."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np, json, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.core import Family, ModelConfig, MoEConfig, ParallelPlan
from repro.core.sharding import ep_spec_for_param
from repro.models.moe import init_moe
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tmoe", Family.MOE, 2, 64, 4, 2, 0, 128,
                  moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                                num_shared_experts=1, capacity_factor=2.0))
params = init_moe(jax.random.PRNGKey(0), cfg)

# save under the ep-only layout: experts over a 2-wide model axis
mesh_a = make_mesh((1, 2), ("data", "model"))
plan_a = ParallelPlan(ep=2, ep_impl="overlap")

def place(params, plan, mesh):
    def one(path, leaf):
        names = tuple(str(getattr(p, "key", p)) for p in path)
        spec = ep_spec_for_param(names, tuple(leaf.shape), plan)
        return jax.device_put(
            leaf, NamedSharding(mesh, spec if spec is not None else P()))
    return jax.tree_util.tree_map_with_path(one, params)

placed = place(params, plan_a, mesh_a)
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d, async_persist=False)
    path = mgr.save(5, placed, blocking=True, plan=plan_a, mesh=mesh_a)
    man = json.loads(path.with_suffix(".json").read_text())
    assert man["plan"]["ep"] == 2 and man["plan"]["ep_impl"] == "overlap"
    # the expert leaves persisted as per-device expert shards
    gi = man["names"].index("experts/gate")
    assert len(man["shards"][gi]) == 2, man["shards"][gi]
    data = np.load(str(path) + ".npz")
    for m in man["shards"][gi]:
        assert data[m["key"]].shape == (2, 64, 64), data[m["key"]].shape

    # same layout replays; a different ep fold is a layout mismatch
    mgr.check_plan(plan_a)
    mgr.check_plan(ParallelPlan(ep=2, ep_impl="blocking"))  # impl-only: fine
    try:
        mgr.check_plan(ParallelPlan(ep=4, cp=2, tp=2, tp_impl="overlap"))
        raise SystemExit("expected ep layout mismatch to raise")
    except ValueError as e:
        assert "layout mismatch" in str(e)

    # elastic reshard: restore onto the folded ep=4 layout (cp x model)
    plan_b = ParallelPlan(ep=4, cp=2, tp=2, tp_impl="overlap")
    mesh_b = make_mesh((1, 2, 2), ("data", "cp", "model"))
    def shardings(path, leaf):
        names = tuple(str(getattr(p, "key", p)) for p in path)
        spec = ep_spec_for_param(names, tuple(leaf.shape), plan_b)
        return NamedSharding(mesh_b, spec if spec is not None else P())
    tgt = jax.tree_util.tree_map_with_path(shardings, params)
    step, back = mgr.restore_resharded(placed, tgt)
    assert step == 5
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # routed experts landed 4-way fold-sharded on the new mesh
    assert back["experts"]["gate"].sharding.spec == P(("cp", "model"),
                                                      None, None)
print("EP_CKPT_OK")
""")
