"""Overlap-aware tensor parallelism (survey §4.1.2/§5.2): ring collective
matmuls + sequence-sharded activations vs the GSPMD baseline.

Equivalence contract: ``tp_impl="overlap"`` computes the *same math* as
``tp_impl="gspmd"`` — same per-token contractions, two-term partial sums, and
psum-of-sums loss reduction. The loss usually reproduces bitwise and is
asserted to ~1 ulp of fp32; gradients are asserted at float-reassociation
tolerance (measured worst ≈ 1e-6 relative) since XLA fuses the ring tiles and
the partitioned GSPMD program differently, which legitimately reassociates
fp32 accumulations.
"""

import jax
import numpy as np
import pytest

from repro.core import Family, ModelConfig, MoEConfig, ParallelPlan, SSMConfig
from repro.kernels.dispatch import select_tp_impl


# ---------------------------------------------------------------------------
# dispatch rules (in-process: no devices needed)


def test_tp_impl_knob_validation():
    cfg = ModelConfig("t", Family.DENSE, 2, 64, 4, 4, 128, 128)
    with pytest.raises(ValueError, match="tp_impl"):
        ParallelPlan(tp_impl="bogus").validate(cfg)
    ParallelPlan(tp_impl="overlap").validate(cfg)   # knob itself is legal


def test_select_tp_impl_resolves_by_backend(monkeypatch):
    with pytest.raises(ValueError, match="tp_impl"):
        select_tp_impl("pallas")                    # not a TP impl name
    assert select_tp_impl("gspmd") == "gspmd"
    assert select_tp_impl("overlap") == "overlap"
    # auto: overlap only on TPU backends (ring ppermutes compile to async
    # DMAs there); gspmd elsewhere
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert select_tp_impl("auto") == "gspmd"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert select_tp_impl("auto") == "overlap"


def test_overlap_support_preconditions():
    from repro.train.tensor_parallel import check_overlap_support
    ok = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 128)
    check_overlap_support(ok, ParallelPlan(tp_impl="overlap"), 2)
    # odd kv-head count can't shard 2 ways
    bad_heads = ModelConfig("t", Family.DENSE, 2, 64, 4, 1, 128, 128)
    with pytest.raises(ValueError, match="heads"):
        check_overlap_support(bad_heads, ParallelPlan(), 2)
    # vocab must divide tp (or be padded to it)
    bad_vocab = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 129)
    with pytest.raises(ValueError, match="vocab"):
        check_overlap_support(bad_vocab, ParallelPlan(), 2)
    check_overlap_support(bad_vocab, ParallelPlan(pad_vocab_to_multiple=2), 2)
    # hybrid family stays on the GSPMD path
    hyb = ModelConfig("t", Family.HYBRID, 2, 64, 4, 2, 128, 128,
                      ssm=SSMConfig(d_state=16), shared_attn_every=2)
    with pytest.raises(ValueError, match="family"):
        check_overlap_support(hyb, ParallelPlan(), 2)
    # multi-group Mamba2 B/C can't replicate per-head
    ssm2 = ModelConfig("t", Family.SSM, 2, 64, 0, 0, 0, 128,
                       ssm=SSMConfig(d_state=16, head_dim=16, n_groups=2))
    with pytest.raises(ValueError, match="n_groups"):
        check_overlap_support(ssm2, ParallelPlan(), 2)


def test_overlap_param_specs_classification():
    from jax.sharding import PartitionSpec as P
    from repro.core.sharding import overlap_spec_for_param
    cfg = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 128)
    assert overlap_spec_for_param(("layers", "attn", "wq"), (2, 64, 64),
                                  cfg) == P(None, None, "model")
    assert overlap_spec_for_param(("layers", "attn", "wo"), (2, 64, 64),
                                  cfg) == P(None, "model", None)
    assert overlap_spec_for_param(("embed", "tok"), (128, 64),
                                  cfg) == P("model", None)
    assert overlap_spec_for_param(("lm_head", "w"), (64, 128),
                                  cfg) == P(None, "model")
    assert overlap_spec_for_param(("layers", "moe", "experts", "gate"),
                                  (2, 4, 64, 64), cfg) == \
        P(None, None, None, "model")
    assert overlap_spec_for_param(("layers", "moe", "experts", "down"),
                                  (2, 4, 64, 64), cfg) == \
        P(None, None, "model", None)
    # norm scales / SSM per-head leaves stay replicated (sliced in-block)
    assert overlap_spec_for_param(("layers", "norm1", "scale"), (2, 64),
                                  cfg) == P(None, None)
    assert overlap_spec_for_param(("layers", "ssm", "A_log"), (2, 8),
                                  cfg) == P(None, None)


# ---------------------------------------------------------------------------
# ring primitive unit tests


def test_ring_collective_matmuls(multidevice):
    """all_gather_matmul / matmul_reduce_scatter / ring_reduce_scatter against
    the dense references, forward and grad, on a 2-rank ring."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.compat import shard_map
from repro.train.tensor_parallel import (RingCtx, all_gather_matmul,
                                         matmul_reduce_scatter,
                                         ring_all_gather, ring_reduce_scatter)
from repro.launch.mesh import make_mesh

rng = np.random.default_rng(0)
B, S, D, F, T = 2, 8, 6, 10, 2
# small integers: every product and partial sum is exact in fp32, so a tile
# GEMM equals the full GEMM bitwise whichever order the CPU's GEMM sums in
x = jnp.asarray(rng.integers(-4, 5, (B, S, D)), jnp.float32)
w1 = jnp.asarray(rng.integers(-4, 5, (D, F)), jnp.float32)
w2 = jnp.asarray(rng.integers(-4, 5, (F, D)), jnp.float32)
mesh = make_mesh((T,), ("model",))
ctx = RingCtx("model", T)

def fwd(xl, w1l, w2l):
    (o1,), xg = all_gather_matmul(ctx, xl, (w1l,))
    o2 = matmul_reduce_scatter(ctx, o1, w2l)
    rs = ring_reduce_scatter(ctx, xg)      # sum of T identical copies = T*x
    return o1, o2, xg, rs

o1, o2, xg, rs = jax.jit(shard_map(fwd, mesh=mesh,
    in_specs=(P(None, "model", None), P(None, "model"), P("model", None)),
    out_specs=(P(None, None, "model"), P(None, "model", None), P(),
               P(None, "model", None))))(x, w1, w2)
# column GEMM tiles reproduce the full GEMM bitwise (row-blocking only)
np.testing.assert_array_equal(np.asarray(o1), np.asarray(x @ w1))
np.testing.assert_array_equal(np.asarray(xg), np.asarray(x))
np.testing.assert_array_equal(np.asarray(rs), T * np.asarray(x))
# row GEMM: two-term ring sum vs one fused chain — reassociation only
np.testing.assert_allclose(np.asarray(o2), np.asarray((x @ w1) @ w2),
                           rtol=1e-5, atol=1e-6)

def ring_loss(x, w1, w2):
    def l(xl, w1l, w2l):
        (o1,), _ = all_gather_matmul(ctx, xl, (w1l,))
        o2 = matmul_reduce_scatter(ctx, o1, w2l)
        return jax.lax.psum(jnp.sum(jnp.sin(o2)), "model")[None]
    return shard_map(l, mesh=mesh,
                     in_specs=(P(None, "model", None), P(None, "model"),
                               P("model", None)),
                     out_specs=P())(x, w1, w2)[0]

ref = lambda x, w1, w2: jnp.sum(jnp.sin((x @ w1) @ w2))
ga = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(x, w1, w2)
gb = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(x, w1, w2)
for name, a, b in zip("x w1 w2".split(), ga, gb):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-6, err_msg=name)
print("ring collective matmuls OK")
""")


# ---------------------------------------------------------------------------
# overlap == gspmd, per family


_FAMILY_EQUIV_TEMPLATE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import (Family, InputShape, ModelConfig, MoEConfig, SSMConfig,
                        ParallelPlan, sharding)
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.tensor_parallel import make_tp_loss_fn
from repro.launch.mesh import make_mesh

cfg = {cfg}
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {{k: jnp.asarray(v) for k, v in ds.batch(0).items()}}
Z = 1e-4   # nonzero: the z_loss threading through cross_entropy_vp matters

for mesh_shape in [(1, 2), (2, 2)]:
    mesh = make_mesh(mesh_shape, ("data", "model"))
    plan = ParallelPlan(remat="none", compute_dtype="float32", tp=2,
                        tp_impl="overlap", moe_dispatch={dispatch!r})
    model = build_model(cfg, plan, mesh, ("data",))
    params = model.init(jax.random.PRNGKey(0))
    # gspmd baseline: annotation-sharded params/batch through XLA's partitioner
    pspecs = sharding.param_specs(params, cfg, plan, mesh)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    gp = jax.device_put(params, shard)
    gb = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    lf_g = make_loss_fn(model, Hyper(z_loss=Z))
    g_loss, g_grads = jax.jit(
        jax.value_and_grad(lambda p, b: lf_g(p, b)[0]))(gp, gb)
    lf_o = make_tp_loss_fn(cfg, plan, mesh, ("data",), z_loss=Z)
    o_loss, o_grads = jax.jit(
        jax.value_and_grad(lambda p, b: lf_o(p, b)[0]))(gp, gb)
    assert abs(float(g_loss) - float(o_loss)) < 2e-6, (
        mesh_shape, float(g_loss), float(o_loss))
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_grads),
            jax.tree_util.tree_leaves_with_path(o_grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=f"{{mesh_shape}} {{jax.tree_util.keystr(path)}}")
    print(mesh_shape, "overlap == gspmd, loss", float(o_loss))
"""

_DENSE_CFG = """ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab=128)"""
# capacity_factor >= E/top_k -> no drops: overlap routes per data shard while
# gspmd routes globally, so drop *decisions* may differ; with no drops the
# per-token math is identical (tested), and the aux loss reduces globally
_MOE_CFG = """ModelConfig("tmoe", Family.MOE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                 moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                               num_shared_experts=1, capacity_factor=2.0))"""
_SSM_CFG = """ModelConfig("tssm", Family.SSM, n_layers=2, d_model=64,
                 n_heads=0, n_kv_heads=0, d_ff=0, vocab=128,
                 ssm=SSMConfig(d_state=16, head_dim=16, expand=2, chunk=8))"""


def test_overlap_matches_gspmd_dense(multidevice):
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_DENSE_CFG,
                                              dispatch="einsum"))


def test_overlap_matches_gspmd_moe(multidevice):
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_MOE_CFG,
                                              dispatch="einsum"))


def test_overlap_matches_gspmd_moe_scatter(multidevice):
    """The MegaBlocks-style scatter dispatch path through the executor's
    moe_block_ex."""
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_MOE_CFG,
                                              dispatch="scatter"))


def test_overlap_matches_gspmd_mamba2(multidevice):
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_SSM_CFG,
                                              dispatch="einsum"))


# ---------------------------------------------------------------------------
# TP x PP composition + train-step routing


def test_tp_pp_composition(multidevice):
    """Overlap rings inside each pipeline tick: TP x PP under both schedules
    reproduces the single-device loss/grads (the 1F1B custom-VJP backward
    splits its replicated-loss seed across the tp ranks)."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.pipeline import pipelined_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=4, d_model=64, n_heads=4,
                  n_kv_heads=4, d_ff=128, vocab=128)
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
Z = 1e-4
plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))
ref_loss, _ = make_loss_fn(model, Hyper(z_loss=Z))(params, batch)
ref_g = jax.grad(lambda p, b: make_loss_fn(model, Hyper(z_loss=Z))(p, b)[0])(
    params, batch)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
for sched in ("gpipe", "1f1b"):
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2, tp=2,
                        microbatches=4, pp_schedule=sched, tp_impl="overlap")
    lf = pipelined_loss_fn(cfg, plan, mesh, ("data",), z_loss=Z)
    loss, grads = jax.jit(jax.value_and_grad(lambda p, b: lf(p, b)[0]))(
        params, batch)
    assert abs(float(loss) - float(ref_loss)) < 2e-6, (sched, float(loss))
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ref_g),
                                 jax.tree_util.tree_leaves_with_path(grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=f"{sched} {jax.tree_util.keystr(path)}")
    print(sched, "TP x PP == single-device OK")
""")


def test_tp_pp_moe_aux(multidevice):
    """Pipelined MoE counts every stage's load-balancing aux (each stage owns
    its own routers), matching the per-microbatch single-device reference —
    under both schedules, with the overlap rings inside the ticks."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import Family, InputShape, ModelConfig, MoEConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.pipeline import pipelined_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tmoe", Family.MOE, n_layers=4, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=0, vocab=128,
                  moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                                capacity_factor=2.0))   # no drops
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))

# reference: per-microbatch losses averaged (routing/aux are microbatch-local
# statistics, the same semantics grad accumulation uses)
M = 4
lf = make_loss_fn(model, Hyper(z_loss=0.0))
mb = {k: v.reshape((M, v.shape[0] // M) + v.shape[1:]) for k, v in batch.items()}
ref = np.mean([float(lf(params, {k: v[i] for k, v in mb.items()})[0])
               for i in range(M)])

mesh = make_mesh((2, 1, 2), ("pod", "data", "model"))
for sched in ("gpipe", "1f1b"):
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2, tp=2,
                        microbatches=M, pp_schedule=sched, tp_impl="overlap")
    pf = pipelined_loss_fn(cfg, plan, mesh, ("data",), z_loss=0.0)
    loss, aux = jax.jit(pf)(params, batch)
    assert float(aux["moe_aux"]) > 0.0, (sched, aux)   # all stages counted
    assert abs(float(loss) - ref) < 5e-5, (sched, float(loss), ref)
    print(sched, "pipelined MoE loss+aux ==", float(loss), "ref", ref)
""")


def test_train_step_routes_overlap(multidevice):
    """make_train_step(mesh=...) with tp_impl='overlap' swaps in the ring
    loss and still matches the GSPMD step (params after one ZeRO-1 update),
    and remat policies compose with the ring custom-VJPs."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import Family, InputShape, ModelConfig, ParallelPlan, sharding
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.optim import adamw_init
from repro.train import Hyper, TrainState, make_train_step
from repro.train.tensor_parallel import make_tp_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128)
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
mesh = make_mesh((2, 2), ("data", "model"))
hyper = Hyper(peak_lr=1e-3, total_steps=10, z_loss=1e-4)

plan_g = ParallelPlan(remat="none", compute_dtype="float32", tp=2, zero_stage=1)
plan_o = ParallelPlan(remat="none", compute_dtype="float32", tp=2, zero_stage=1,
                      tp_impl="overlap")
model_g = build_model(cfg, plan_g, mesh, ("data",))
params = model_g.init(jax.random.PRNGKey(0))
pspecs = sharding.param_specs(params, cfg, plan_g, mesh)
shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                     is_leaf=lambda x: isinstance(x, P))
gp = jax.device_put(params, shard)
gb = jax.device_put(batch, NamedSharding(mesh, P("data", None)))

sg, _ = jax.jit(make_train_step(model_g, plan_g, hyper, mesh=mesh))(
    TrainState(gp, adamw_init(gp)), gb)
model_o = build_model(cfg, plan_o, mesh, ("data",))
so, met = jax.jit(make_train_step(model_o, plan_o, hyper, mesh=mesh))(
    TrainState(gp, adamw_init(gp)), gb)
for a, b in zip(jax.tree.leaves(sg.params), jax.tree.leaves(so.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)
print("overlap train step == gspmd train step, loss", float(met["loss"]))

# remat policies through the ring custom-VJPs
g0 = None
for remat in ("none", "selective", "full"):
    pl = ParallelPlan(remat=remat, compute_dtype="float32", tp=2,
                      tp_impl="overlap")
    lf = make_tp_loss_fn(cfg, pl, mesh, ("data",), z_loss=0.0)
    g = jax.jit(jax.grad(lambda p, b: lf(p, b)[0]))(params, batch)
    if g0 is None:
        g0 = g
    else:
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6, err_msg=remat)
print("remat none == selective == full under overlap OK")
""")
