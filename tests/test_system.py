"""End-to-end system tests: training learns, microbatching is exact, the
multi-device train step + pipeline parallelism agree with the references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_loss_fn, make_train_step


def _tiny(**kw):
    cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab=128, **kw)
    return cfg


def test_training_learns_markov_structure():
    cfg = _tiny()
    plan = ParallelPlan(remat="selective", compute_dtype="float32")
    shape = InputShape("t", 32, 8, "train")
    model = build_model(cfg, plan)
    ds = SyntheticDataset(cfg, shape)
    state = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(
        model, plan, Hyper(peak_lr=1e-2, warmup_steps=10, total_steps=60)))
    losses = []
    for i in range(60):
        state, m = step(state, {k: jnp.asarray(v) for k, v in ds.batch(i).items()})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_microbatch_accumulation_matches_full_batch():
    cfg = _tiny()
    shape = InputShape("t", 16, 8, "train")
    ds = SyntheticDataset(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    hyper = Hyper(peak_lr=1e-3, total_steps=10, z_loss=0.0)

    outs = {}
    for mb in (1, 4):
        plan = ParallelPlan(remat="none", compute_dtype="float32",
                            microbatches=mb)
        model = build_model(cfg, plan)
        state = init_train_state(model, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(model, plan, hyper))
        new_state, metrics = step(state, batch)
        outs[mb] = (new_state, metrics)

    np.testing.assert_allclose(float(outs[1][1]["loss"]),
                               float(outs[4][1]["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][0].params),
                    jax.tree.leaves(outs[4][0].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_remat_policies_do_not_change_loss():
    cfg = _tiny()
    shape = InputShape("t", 16, 4, "train")
    ds = SyntheticDataset(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    losses = {}
    grads = {}
    for remat in ("none", "selective", "full"):
        plan = ParallelPlan(remat=remat, compute_dtype="float32")
        model = build_model(cfg, plan)
        params = model.init(jax.random.PRNGKey(0))
        loss_fn = make_loss_fn(model, Hyper(z_loss=0.0))
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        losses[remat] = float(l)
        grads[remat] = g
    assert abs(losses["none"] - losses["full"]) < 1e-5
    assert abs(losses["none"] - losses["selective"]) < 1e-5
    for a, b in zip(jax.tree.leaves(grads["none"]), jax.tree.leaves(grads["full"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


def test_sharded_train_step_matches_single_device(multidevice):
    """The pjit'd train step on a (2,4) mesh must reproduce the single-device
    result (parallelism is an implementation detail, not a math change)."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import Family, InputShape, ModelConfig, ParallelPlan, sharding
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, TrainState, init_train_state, make_train_step
from repro.optim import adamw_init
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=4, d_ff=128, vocab=128)
shape = InputShape("t", 16, 8, "train")
hyper = Hyper(peak_lr=1e-3, total_steps=10, z_loss=0.0)
ds = SyntheticDataset(cfg, shape)
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}

# reference: single device
plan0 = ParallelPlan(remat="none", compute_dtype="float32")
m0 = build_model(cfg, plan0)
s0 = init_train_state(m0, jax.random.PRNGKey(0))
ref_state, ref_metrics = jax.jit(make_train_step(m0, plan0, hyper))(s0, batch)

# sharded: (data=2, model=4) mesh with TP+ZeRO1
mesh = make_mesh((2, 4), ("data", "model"))
plan = ParallelPlan(remat="none", compute_dtype="float32", tp=4, zero_stage=1)
m1 = build_model(cfg, plan, mesh, ("data",))
s1 = init_train_state(m1, jax.random.PRNGKey(0))
pspecs = sharding.param_specs(s1.params, cfg, plan, mesh)
shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                     is_leaf=lambda x: isinstance(x, P))
params = jax.device_put(s1.params, shard)
state = TrainState(params, adamw_init(params))
new_state, metrics = jax.jit(make_train_step(m1, plan, hyper))(state, batch)

assert abs(float(metrics["loss"]) - float(ref_metrics["loss"])) < 1e-4, (
    float(metrics["loss"]), float(ref_metrics["loss"]))
for a, b in zip(jax.tree.leaves(new_state.params), jax.tree.leaves(ref_state.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)
print("sharded == single-device OK, loss", float(metrics["loss"]))
""")


def test_pipeline_parallel_loss_matches(multidevice):
    """GPipe over the pod axis == non-pipelined loss (same math)."""
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
hyper = Hyper(z_loss=0.0)

plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))
ref_loss, _ = make_loss_fn(model, hyper)(params, batch)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
# pin the gpipe schedule: this test covers reverse-AD through the forward
# scan; tests/test_train_memory.py covers 1f1b (and both against gpipe)
plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2,
                    microbatches=4, pp_schedule="gpipe")
pipe_loss_fn = pipelined_loss_fn(cfg, plan, mesh, ("data",))
pipe_loss, _ = jax.jit(pipe_loss_fn)(params, batch)
print("ref", float(ref_loss[0] if isinstance(ref_loss, tuple) else ref_loss),
      "pipe", float(pipe_loss))
assert abs(float(ref_loss) - float(pipe_loss)) < 2e-4

# gradients flow end to end
g = jax.grad(lambda p, b: pipe_loss_fn(p, b)[0])(params, batch)
gn = sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(g))
assert np.isfinite(gn) and gn > 0
print("pipeline grad norm OK", gn)
""")
