"""Unified block executor + ring context parallelism (survey §4.1.4).

Equivalence contract: ``plan.cp > 1`` shards the *sequence* over the "cp"
mesh axis end to end and computes the same math as the single-device path —
ring attention merges per-chunk (out, lse) partials exactly (chunked
softmax), the SSD entering-state chain reproduces the sequential scan, MoE
routes on local shards (exact when no tokens drop). Loss is asserted to ~1
ulp of fp32 and gradients at float-reassociation tolerance (the same ≤1e-6
contract the overlap-TP suite uses; the cp×tp composition gets 3e-6 atol —
two ring reductions' reassociations stack).
"""

import warnings

import jax
import numpy as np
import pytest

from repro.core import Family, ModelConfig, MoEConfig, ParallelPlan, SSMConfig
from repro.kernels.dispatch import select_cp_impl


# ---------------------------------------------------------------------------
# knob / dispatch / layout units (in-process: no devices needed)


def test_cp_knob_validation():
    cfg = ModelConfig("t", Family.DENSE, 2, 64, 4, 4, 128, 128)
    with pytest.raises(ValueError, match="cp_impl"):
        ParallelPlan(cp_impl="bogus").validate(cfg)
    with pytest.raises(ValueError, match="cp must be"):
        ParallelPlan(cp=0).validate(cfg)
    ParallelPlan(cp=2, cp_impl="ring").validate(cfg)
    # cp composes with tp only via the explicit rings
    with pytest.raises(ValueError, match="overlap"):
        ParallelPlan(cp=2, tp=2, tp_impl="gspmd").validate(cfg)
    ParallelPlan(cp=2, tp=2, tp_impl="overlap").validate(cfg)
    # unsupported families are rejected up front
    hyb = ModelConfig("t", Family.HYBRID, 2, 64, 4, 2, 128, 128,
                      ssm=SSMConfig(d_state=16), shared_attn_every=2)
    with pytest.raises(ValueError, match="dense/moe/ssm"):
        ParallelPlan(cp=2).validate(hyb)


def test_cp_token_dropping_divergence_is_flagged():
    """Documented divergence (PR 4 / cp): shard-local routing with a
    token-dropping capacity factor must warn at validation time instead of
    silently differing from the global-routing baseline."""
    dropping = ModelConfig("t", Family.MOE, 2, 64, 4, 2, 0, 128,
                           moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                                         capacity_factor=1.0))
    with pytest.warns(UserWarning, match="token-dropping"):
        ParallelPlan(cp=2).validate(dropping)
    with pytest.warns(UserWarning, match="token-dropping"):
        ParallelPlan(tp=2, tp_impl="overlap").validate(dropping)
    # no-drop capacity (>= E/top_k) is exact: no warning
    nodrop = ModelConfig("t", Family.MOE, 2, 64, 4, 2, 0, 128,
                         moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                                       capacity_factor=2.0))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ParallelPlan(cp=2).validate(nodrop)
        ParallelPlan(tp=2, tp_impl="overlap").validate(nodrop)
    # GSPMD global routing never warns, dropping or not
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ParallelPlan().validate(dropping)


def test_select_cp_impl_rules():
    with pytest.raises(ValueError, match="cp_impl"):
        select_cp_impl("pallas")
    assert select_cp_impl("auto") == "ring"
    assert select_cp_impl("gather") == "gather"
    # sliding windows force gather (ring's per-pair masks are static)
    assert select_cp_impl("auto", window=128) == "gather"
    assert select_cp_impl("auto", local_global_alternating=True) == "gather"
    with pytest.raises(ValueError, match="ring"):
        select_cp_impl("ring", window=128)
    # the SSM family always runs the state chain (no KV to gather)
    assert select_cp_impl("gather", family=Family.SSM) == "ring"


def test_zigzag_layout_units():
    from repro.train.executor import zigzag_pair_counts, zigzag_permutation
    for seq, cp in [(16, 2), (32, 4), (48, 2)]:
        perm = zigzag_permutation(seq, cp)
        # a bijection over positions
        assert sorted(perm.tolist()) == list(range(seq))
        # rank r owns sub-chunks r and 2cp-1-r, each contiguous
        lc = seq // (2 * cp)
        for r in range(cp):
            chunk = perm[r * (seq // cp):(r + 1) * (seq // cp)]
            np.testing.assert_array_equal(chunk[:lc],
                                          np.arange(r * lc, (r + 1) * lc))
            np.testing.assert_array_equal(
                chunk[lc:], np.arange((2 * cp - 1 - r) * lc,
                                      (2 * cp - r) * lc))
        # load balance: every rank attends exactly the same number of causal
        # (q, k) pairs — the point of the zigzag
        counts = zigzag_pair_counts(seq, cp)
        assert counts.min() == counts.max(), counts
    # contiguous chunks are badly imbalanced by comparison (sanity)
    seq, cp = 32, 4
    contiguous = [int(np.sum(np.arange(r * 8, (r + 1) * 8) + 1))
                  for r in range(cp)]
    assert max(contiguous) > 3 * min(contiguous)


def test_executor_dispatch_routing():
    """The executor context resolves placement from plan + mesh shape."""
    from repro.train.executor import (ParallelContext, local_context,
                                      resolve_context)
    cfg = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 128)

    class M:
        shape = {"data": 1, "cp": 2}
    ctx = resolve_context(cfg, ParallelPlan(cp=2), M, ("data",))
    assert ctx.tp is None and ctx.cp is not None and ctx.cp.size == 2
    assert ctx.cp_impl == "ring" and ctx.n_rep == 2

    class M2:
        shape = {"data": 2, "model": 2}
    ctx = resolve_context(cfg, ParallelPlan(tp=2, tp_impl="overlap"), M2,
                          ("data",))
    assert ctx.cp is None and ctx.tp is not None and ctx.tp.size == 2

    class M3:
        shape = {"data": 1, "cp": 2, "model": 2}
    ctx = resolve_context(
        cfg, ParallelPlan(cp=2, tp=2, tp_impl="overlap"), M3, ("data",))
    assert ctx.tp.size == 2 and ctx.cp.size == 2
    assert ctx.aux_axes == ("data", "cp")

    # plan.cp without a cp mesh axis is an error, not a silent fallback
    with pytest.raises(ValueError, match="cp"):
        resolve_context(cfg, ParallelPlan(cp=2), M2, ("data",))

    # the local context is the identity placement
    lc = local_context()
    assert isinstance(lc, ParallelContext)
    assert lc.tp is None and lc.cp is None and lc.n_tp == lc.n_cp == 1

    # the residual-stream layout contract: seq carries cp (and model when
    # the tp rings are on too)
    from jax.sharding import PartitionSpec as P
    from repro.core.sharding import cp_activation_spec
    assert cp_activation_spec(M, ParallelPlan(cp=2)) == \
        P(("data",), "cp", None)
    assert cp_activation_spec(
        M3, ParallelPlan(cp=2, tp=2, tp_impl="overlap")) == \
        P(("data",), ("cp", "model"), None)


def test_train_step_routes_cp():
    """make_train_step raises loudly when plan.cp has no cp mesh axis."""
    from repro.models import build_model
    from repro.train import Hyper, make_train_step
    cfg = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 128)
    plan = ParallelPlan(cp=2, compute_dtype="float32")
    model = build_model(cfg, plan)
    with pytest.raises(ValueError, match="cp"):
        make_train_step(model, plan, Hyper(), mesh=None)


def test_chunk_attention_lse_entries():
    """The lse-merging chunk entries: pallas (interpret) == XLA twins, and
    two merged chunks == one full-KV call (the chunked-softmax identity)."""
    from repro.kernels.dispatch import (dispatch_attention,
                                        dispatch_attention_chunk_bwd,
                                        dispatch_attention_lse)
    from repro.train.executor import _merge_lse
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    b, s, hq, hkv, hd = 1, 32, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, s, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, hd)), jnp.float32)

    o_x, lse_x = dispatch_attention_lse(q, k, v, impl="xla", causal=True)
    o_p, lse_p = dispatch_attention_lse(q, k, v, impl="pallas", causal=True,
                                        block_q=16, block_k=16,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_x),
                               rtol=1e-5, atol=1e-5)

    # chunked-softmax merge: [full(K0) ; diag(K1)] partials == full attention
    half = s // 2
    o0, l0 = dispatch_attention_lse(q[:, half:], k[:, :half], v[:, :half],
                                    impl="xla", causal=False)
    o1, l1 = dispatch_attention_lse(q[:, half:], k[:, half:], v[:, half:],
                                    impl="xla", causal=True)
    om, lm = _merge_lse(jnp.zeros_like(o0, dtype=jnp.float32),
                        jnp.full(l0.shape, -1e30, jnp.float32), o0, l0)
    om, lm = _merge_lse(om, lm, o1, l1)
    ref = dispatch_attention(q, k, v, impl="xla", causal=True)
    np.testing.assert_allclose(np.asarray(om), np.asarray(ref[:, half:]),
                               rtol=1e-5, atol=1e-6)

    # chunk backward vs autodiff of the full call, summed over chunks
    do = jnp.asarray(rng.standard_normal((b, s, hq, hd)), jnp.float32)
    full_o, full_lse = dispatch_attention_lse(q, k, v, impl="xla",
                                              causal=True)
    delta = jnp.sum(do * full_o.astype(jnp.float32), axis=-1)
    ref_dq, ref_dk, ref_dv = jax.vjp(
        lambda q_, k_, v_: dispatch_attention(q_, k_, v_, impl="xla",
                                              causal=True), q, k, v)[1](do)
    for impl, kw in [("xla", {}), ("pallas", dict(block_q=16, block_k=16,
                                                  interpret=True))]:
        dq = np.zeros(q.shape, np.float32)
        dk = np.zeros(k.shape, np.float32)
        dv = np.zeros(v.shape, np.float32)
        # chunk 0 (diag for q0, full-past for q1) + chunk 1 (diag for q1)
        g = dispatch_attention_chunk_bwd(
            q[:, :half], k[:, :half], v[:, :half], do[:, :half],
            full_lse[:, :half], delta[:, :half], impl=impl, causal=True, **kw)
        dq[:, :half] += g[0]; dk[:, :half] += g[1]; dv[:, :half] += g[2]
        g = dispatch_attention_chunk_bwd(
            q[:, half:], k[:, :half], v[:, :half], do[:, half:],
            full_lse[:, half:], delta[:, half:], impl=impl, causal=False,
            **kw)
        dq[:, half:] += g[0]; dk[:, :half] += g[1]; dv[:, :half] += g[2]
        g = dispatch_attention_chunk_bwd(
            q[:, half:], k[:, half:], v[:, half:], do[:, half:],
            full_lse[:, half:], delta[:, half:], impl=impl, causal=True, **kw)
        dq[:, half:] += g[0]; dk[:, half:] += g[1]; dv[:, half:] += g[2]
        np.testing.assert_allclose(dq, np.asarray(ref_dq), rtol=1e-4,
                                   atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(dk, np.asarray(ref_dk), rtol=1e-4,
                                   atol=1e-5, err_msg=impl)
        np.testing.assert_allclose(dv, np.asarray(ref_dv), rtol=1e-4,
                                   atol=1e-5, err_msg=impl)


# ---------------------------------------------------------------------------
# ring == gather == single-device, per family


_FAMILY_EQUIV_TEMPLATE = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import (Family, InputShape, ModelConfig, MoEConfig, SSMConfig,
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

for mesh_shape in [(1, 2), (2, 2)]:
    mesh = make_mesh(mesh_shape, ("data", "cp"))
    for impl in ("gather", "ring"):
        plan = ParallelPlan(remat="none", compute_dtype="float32", cp=2,
                            cp_impl=impl)
        clf = make_executor_loss_fn(cfg, plan, mesh, ("data",), z_loss=Z)
        cl, cg = jax.jit(
            jax.value_and_grad(lambda p, b: clf(p, b)[0]))(params, batch)
        assert abs(float(ref_loss) - float(cl)) < 2e-6, (
            mesh_shape, impl, float(ref_loss), float(cl))
        for (path, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(ref_g),
                jax.tree_util.tree_leaves_with_path(cg)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
                err_msg=f"{{mesh_shape}} {{impl}} "
                        f"{{jax.tree_util.keystr(path)}}")
        print(mesh_shape, impl, "== single-device, loss", float(cl))
"""

_DENSE_CFG = """ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab=128)"""
# capacity_factor >= E/top_k -> no drops: cp routes per sequence shard while
# the baseline routes globally, so drop *decisions* could differ; with no
# drops the per-token math is identical (and the dropping case warns at
# validation — see test_cp_token_dropping_divergence_is_flagged)
_MOE_CFG = """ModelConfig("tmoe", Family.MOE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                 moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                               num_shared_experts=1, capacity_factor=2.0))"""
_SSM_CFG = """ModelConfig("tssm", Family.SSM, n_layers=2, d_model=64,
                 n_heads=0, n_kv_heads=0, d_ff=0, vocab=128,
                 ssm=SSMConfig(d_state=16, head_dim=16, expand=2, chunk=8))"""


def test_cp_matches_single_device_dense(multidevice):
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_DENSE_CFG))


def test_cp_matches_single_device_moe(multidevice):
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_MOE_CFG))


def test_cp_matches_single_device_mamba2(multidevice):
    """The SSD entering-state chain + conv halo across cp shards."""
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_SSM_CFG))


def test_cp_matches_single_device_mamba2_groups_conv_bias(multidevice):
    """Two groups (the gated norm per group) and the conv bias (Zamba2's
    Mamba-2 layer) across cp shards."""
    multidevice(_FAMILY_EQUIV_TEMPLATE.format(cfg=_SSM_CFG.replace(
        "chunk=8)", "chunk=8, n_groups=2, conv_bias=True)")))


def test_cp_tp_composition(multidevice):
    """CP × TP: cp ring attention inside tp-ring-gathered blocks (dense),
    loss/grads vs the single-device oracle on a (data, cp, model) mesh."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.executor import make_executor_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128)
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
Z = 1e-4
plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))
lf = make_loss_fn(model, Hyper(z_loss=Z))
ref_loss, ref_g = jax.jit(
    jax.value_and_grad(lambda p, b: lf(p, b)[0]))(params, batch)

for mesh_shape in [(1, 2, 2), (2, 2, 2)]:
    mesh = make_mesh(mesh_shape, ("data", "cp", "model"))
    plan = ParallelPlan(remat="none", compute_dtype="float32", cp=2, tp=2,
                        tp_impl="overlap", cp_impl="ring")
    clf = make_executor_loss_fn(cfg, plan, mesh, ("data",), z_loss=Z)
    cl, cg = jax.jit(
        jax.value_and_grad(lambda p, b: clf(p, b)[0]))(params, batch)
    assert abs(float(ref_loss) - float(cl)) < 2e-6, (
        mesh_shape, float(ref_loss), float(cl))
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ref_g),
                                 jax.tree_util.tree_leaves_with_path(cg)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=3e-6,
            err_msg=f"{mesh_shape} {jax.tree_util.keystr(path)}")
    print(mesh_shape, "cp x tp == single-device, loss", float(cl))
""")


# ---------------------------------------------------------------------------
# CP × PP composition + remat + train-step routing


def test_cp_pp_composition(multidevice):
    """CP inside each pipeline tick, under both schedules, vs single-device
    (the 1F1B backward splits its replicated-loss seed across cp ranks)."""
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

mesh = make_mesh((2, 2, 2), ("pod", "data", "cp"))
for sched in ("gpipe", "1f1b"):
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2, cp=2,
                        microbatches=4, pp_schedule=sched, cp_impl="ring")
    lf = pipelined_loss_fn(cfg, plan, mesh, ("data",), z_loss=Z)
    loss, grads = jax.jit(jax.value_and_grad(lambda p, b: lf(p, b)[0]))(
        params, batch)
    assert abs(float(loss) - float(ref_loss)) < 2e-6, (sched, float(loss))
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ref_g),
                                 jax.tree_util.tree_leaves_with_path(grads)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6,
            err_msg=f"{sched} {jax.tree_util.keystr(path)}")
    print(sched, "CP x PP == single-device OK")

# CP x TP x PP: all three explicit axes in one 1F1B tick
mesh = make_mesh((2, 2, 2), ("pod", "cp", "model"))
plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2, cp=2, tp=2,
                    microbatches=4, tp_impl="overlap", cp_impl="ring")
lf = pipelined_loss_fn(cfg, plan, mesh, (), z_loss=Z)
loss, grads = jax.jit(jax.value_and_grad(lambda p, b: lf(p, b)[0]))(
    params, batch)
assert abs(float(loss) - float(ref_loss)) < 2e-6, float(loss)
for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(ref_g),
                             jax.tree_util.tree_leaves_with_path(grads)):
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=3e-6,
        err_msg=f"cp x tp x pp {jax.tree_util.keystr(path)}")
print("CP x TP x PP (1f1b) == single-device OK")
""")


def test_cp_remat_and_train_step(multidevice):
    """Remat policies compose with the cp ring custom-VJPs, and
    make_train_step(mesh=...) with plan.cp routes the executor loss."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.optim import adamw_init
from repro.train import Hyper, TrainState, make_loss_fn, make_train_step
from repro.train.executor import make_executor_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128)
shape = InputShape("t", 16, 8, "train")
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
mesh = make_mesh((2, 2), ("data", "cp"))

g0 = None
for remat in ("none", "selective", "full"):
    plan = ParallelPlan(remat=remat, compute_dtype="float32", cp=2,
                        cp_impl="ring")
    lf = make_executor_loss_fn(cfg, plan, mesh, ("data",), z_loss=0.0)
    model = build_model(cfg, plan)
    params = model.init(jax.random.PRNGKey(0))
    g = jax.jit(jax.grad(lambda p, b: lf(p, b)[0]))(params, batch)
    if g0 is None:
        g0 = g
    else:
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6, err_msg=remat)
print("remat none == selective == full under cp OK")

# one train step through make_train_step's cp routing == the GSPMD step
hyper = Hyper(peak_lr=1e-3, total_steps=10, z_loss=1e-4)
plan_c = ParallelPlan(remat="none", compute_dtype="float32", cp=2,
                      cp_impl="ring", zero_stage=0)
plan_r = ParallelPlan(remat="none", compute_dtype="float32", zero_stage=0)
model = build_model(cfg, plan_r)
params = model.init(jax.random.PRNGKey(0))
s_ref, _ = jax.jit(make_train_step(model, plan_r, hyper))(
    TrainState(params, adamw_init(params)), batch)
model_c = build_model(cfg, plan_c, mesh, ("data",))
s_cp, met = jax.jit(make_train_step(model_c, plan_c, hyper, mesh=mesh))(
    TrainState(params, adamw_init(params)), batch)
for a, b in zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_cp.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-6)
print("cp train step == replicated train step, loss", float(met["loss"]))
""")


def test_cp_sharded_checkpoint_roundtrip(multidevice):
    """Shard-aware checkpointing under a cp mesh: save writes per-device
    shards (no host gather), the manifest records the ParallelPlan axes, and
    restore reassembles + re-places bit-identically; a mismatched plan is
    refused (ft replay safety)."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np, json, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.core import Family, ModelConfig, ParallelPlan
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "cp"))
plan = ParallelPlan(cp=2, cp_impl="ring")
cfg = ModelConfig("t", Family.DENSE, 2, 64, 4, 2, 128, 128)
rng = np.random.default_rng(0)
tree = {
    "w": jax.device_put(jnp.asarray(rng.standard_normal((8, 64)), jnp.float32),
                        NamedSharding(mesh, P("data", None))),
    "x": jax.device_put(jnp.asarray(rng.standard_normal((4, 16)), jnp.float32),
                        NamedSharding(mesh, P("data", "cp"))),
    "r": jnp.asarray(rng.standard_normal((6,)), jnp.float32),   # replicated
    "s": jnp.float32(3.0),                                       # scalar
}
with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d, async_persist=False)
    path = mgr.save(3, tree, blocking=True, plan=plan, mesh=mesh)
    man = json.loads(path.with_suffix(".json").read_text())
    assert man["plan"]["cp"] == 2 and man["plan"]["cp_impl"] == "ring"
    assert man["mesh_axes"] == {"data": 2, "cp": 2}
    # the sharded leaf persisted as per-device shards, not one full array
    xi = man["names"].index("x")
    assert len(man["shards"][xi]) == 4, man["shards"][xi]
    data = np.load(str(path) + ".npz")
    x_keys = [m["key"] for m in man["shards"][xi]]
    assert all(data[k].shape == (2, 8) for k in x_keys), \
        {k: data[k].shape for k in x_keys}
    step, back = mgr.restore(tree)
    assert step == 3
    for k in tree:
        np.testing.assert_array_equal(np.asarray(tree[k]), np.asarray(back[k]))
    # restored leaves keep their shardings (shard-to-shard restore)
    assert back["x"].sharding == tree["x"].sharding
    # a different layout is refused for replay
    mgr.check_plan(plan)                      # same plan: fine
    try:
        mgr.check_plan(ParallelPlan(cp=1))
        raise SystemExit("expected layout mismatch to raise")
    except ValueError as e:
        assert "layout mismatch" in str(e)
print("CP_CKPT_OK")
""")
