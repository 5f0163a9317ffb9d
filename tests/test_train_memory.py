"""Memory-lean training path (survey §4.1.3 / §6.1 / §6.2): 1F1B pipeline
schedule vs GPipe vs single-stage equivalence + compiled-memory ordering,
remat-policy gradient equivalence across families, and the ZeRO-1 sharded
update vs the replicated-AdamW oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import InputShape, ParallelPlan, get_smoke_config
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_loss_fn, make_train_step


# ---------------------------------------------------------------------------
# 1F1B pipeline schedule


def test_1f1b_matches_gpipe_and_single_stage(multidevice):
    """Both pipeline schedules reproduce the single-stage loss and grads
    (z_loss threaded through the per-microbatch cross-entropy), and the
    compiled 1F1B backward peaks at less live memory than GPipe's
    reverse-AD-through-the-scan at M >= 2·P."""
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
Z = 1e-4   # nonzero so the z_loss threading is actually exercised

plan0 = ParallelPlan(remat="none", compute_dtype="float32")
model = build_model(cfg, plan0)
params = model.init(jax.random.PRNGKey(0))
hyper = Hyper(z_loss=Z)
ref_loss, _ = make_loss_fn(model, hyper)(params, batch)
ref_g = jax.grad(lambda p, b: make_loss_fn(model, hyper)(p, b)[0])(params, batch)

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
mems, grads = {}, {}
for sched in ("gpipe", "1f1b"):
    # M = 4 = 2·P microbatches: the acceptance point for the memory claim
    plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2,
                        microbatches=4, pp_schedule=sched)
    lf = pipelined_loss_fn(cfg, plan, mesh, ("data",), z_loss=Z)
    loss, _ = jax.jit(lf)(params, batch)
    assert abs(float(loss) - float(ref_loss)) < 2e-4, (sched, float(loss))
    gf = jax.jit(jax.value_and_grad(lambda p, b: lf(p, b)[0]))
    compiled = gf.lower(params, batch).compile()
    ma = compiled.memory_analysis()
    if ma is not None:
        mems[sched] = ma.temp_size_in_bytes
    grads[sched] = jax.block_until_ready(gf(params, batch)[1])

for sched in ("gpipe", "1f1b"):
    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(grads[sched])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5, err_msg=sched)
for a, b in zip(jax.tree.leaves(grads["gpipe"]), jax.tree.leaves(grads["1f1b"])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-3, atol=1e-6)
print("1f1b == gpipe == single-stage OK")

if mems:
    assert mems["1f1b"] < mems["gpipe"], mems
    print(f"peak temp bytes: 1f1b {mems['1f1b']} < gpipe {mems['gpipe']} "
          f"({mems['1f1b']/mems['gpipe']:.2f}x)")
""")


# ---------------------------------------------------------------------------
# remat policies


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "olmoe-1b-7b", "mamba2-370m"])
def test_remat_policies_grad_equivalence(arch):
    """remat in {selective, full} must reproduce remat="none" grads exactly
    (recomputation never changes math) on dense, MoE and Mamba2 smokes."""
    cfg = get_smoke_config(arch)
    shape = InputShape("t", 16, 4, "train")
    ds = SyntheticDataset(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    out = {}
    for remat in ("none", "selective", "full"):
        plan = ParallelPlan(remat=remat, compute_dtype="float32")
        model = build_model(cfg, plan)
        params = model.init(jax.random.PRNGKey(0))
        loss_fn = make_loss_fn(model, Hyper(z_loss=0.0))
        (l, _), g = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            params, batch)
        out[remat] = (float(l), g)
    for remat in ("selective", "full"):
        assert abs(out["none"][0] - out[remat][0]) < 1e-5, (arch, remat)
        for a, b in zip(jax.tree.leaves(out["none"][1]),
                        jax.tree.leaves(out[remat][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"{arch}/{remat}")


def test_invalid_remat_and_schedule_rejected():
    cfg = get_smoke_config("qwen1.5-4b")
    with pytest.raises(ValueError, match="remat"):
        ParallelPlan(remat="sometimes").validate(cfg)
    with pytest.raises(ValueError, match="pp_schedule"):
        ParallelPlan(pp_schedule="interleaved").validate(cfg)


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "gemma2-9b", "olmoe-1b-7b",
                                  "deepseek-moe-16b", "mamba2-370m",
                                  "zamba2-1.2b", "whisper-small",
                                  "pixtral-12b"])
def test_train_step_smoke_selective_remat(arch):
    """One jitted train step per family under remat="selective" — the
    production default recipe — stays finite and actually updates params."""
    cfg = get_smoke_config(arch)
    plan = ParallelPlan(remat="selective", compute_dtype="float32")
    model = build_model(cfg, plan)
    ds = SyntheticDataset(cfg, InputShape("t", 32, 4, "train"))
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    state = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, plan, Hyper(total_steps=10)))
    new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"]), arch
    assert jnp.isfinite(metrics["grad_norm"]), arch
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(new_state.params)))
    assert delta > 0.0, arch


# ---------------------------------------------------------------------------
# ZeRO-1 sharded update


def test_zero1_update_matches_replicated_oracle(multidevice):
    """The mesh-aware train step (reduce-scattered grad accumulator + sharded
    AdamW + param all-gather) must be bit-compatible with the replicated
    update, and the new moments must come out data-sharded."""
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

# oracle: replicated AdamW with grad accumulation
plan0 = ParallelPlan(remat="none", compute_dtype="float32", microbatches=4)
m0 = build_model(cfg, plan0)
s0 = init_train_state(m0, jax.random.PRNGKey(0))
ref_state, ref_metrics = jax.jit(make_train_step(m0, plan0, hyper))(s0, batch)

# ZeRO-1 on a (data=2, model=2) mesh, same microbatching
mesh = make_mesh((2, 2), ("data", "model"))
plan = ParallelPlan(remat="none", compute_dtype="float32", zero_stage=1,
                    microbatches=4)
m1 = build_model(cfg, plan, mesh, ("data",))
s1 = init_train_state(m1, jax.random.PRNGKey(0))
pspecs = sharding.param_specs(s1.params, cfg, plan, mesh)
shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                     is_leaf=lambda x: isinstance(x, P))
params = jax.device_put(s1.params, shard)
state = TrainState(params, adamw_init(params))
new_state, metrics = jax.jit(make_train_step(m1, plan, hyper, mesh=mesh))(
    state, batch)

assert abs(float(metrics["loss"]) - float(ref_metrics["loss"])) < 1e-4
for a, b in zip(jax.tree.leaves(new_state.params),
                jax.tree.leaves(ref_state.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                               atol=2e-4)
for ref_m, new_m in [(ref_state.opt.mu, new_state.opt.mu),
                     (ref_state.opt.nu, new_state.opt.nu)]:
    for a, b in zip(jax.tree.leaves(ref_m), jax.tree.leaves(new_m)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)
print("ZeRO-1 == replicated oracle OK, loss", float(metrics["loss"]))

mu_wq = new_state.opt.mu["layers"]["attn"]["wq"]
assert not mu_wq.sharding.is_fully_replicated, mu_wq.sharding
print("moments data-sharded OK:", mu_wq.sharding.spec)
""")


def test_mesh_state_layout_needs_one_compile(multidevice):
    """init_train_state places the state on a mesh exactly as the jitted step
    returns it (canonical specs, a mesh-replicated step counter), so the
    second step reuses the first step's executable: on a chip each extra
    compile of a real model costs tens of seconds."""
    multidevice("""
import jax, jax.numpy as jnp
from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step

cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128)
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
mesh = make_mesh((2, 2), ("data", "model"))
for impl in ("gspmd", "overlap"):
    plan = ParallelPlan(remat="none", compute_dtype="float32", tp=2,
                        zero_stage=1, tp_impl=impl)
    model = build_model(cfg, plan, mesh, ("data",))
    state = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh,
                             plan=plan)
    step = jax.jit(make_train_step(model, plan, Hyper(), mesh=mesh))
    for _ in range(3):
        state, _ = step(state, batch)
    assert step._cache_size() == 1, (impl, step._cache_size())
print("ONE_COMPILE_OK")
""", n_devices=4)
