"""Elastic fault tolerance (survey §8.3): anomaly-driven recovery policies,
double-buffered snapshots, and cross-mesh reshard-restore.

The fault matrix runs {nan, spike, repeated-spike, hang} × {dense, MoE,
Mamba2}: each case asserts the policy table chose the expected action AND
that the recovered run is numerically indistinguishable from the matching
clean run (the deterministic pipeline makes these comparisons exact).
The multidevice test is the §8.3.2 acceptance: k steps on a 2×2 mesh,
simulated host loss to 1×2, reshard-restore (params + ZeRO-1 moments), and
a bit-matching resumed loss sequence.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import (Family, InputShape, ModelConfig, ParallelPlan,
                        RecoveryPolicy)
from repro.core.config import MoEConfig, SSMConfig
from repro.data import SyntheticDataset
from repro.ft import Monitor, run_with_recovery
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step

FAULT_STEP = 13
N_STEPS = 20
CKPT_EVERY = 5


def _arch(family: str):
    if family == "dense":
        cfg = ModelConfig("tiny-d", Family.DENSE, n_layers=2, d_model=32,
                          n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    elif family == "moe":
        cfg = ModelConfig("tiny-m", Family.MOE, n_layers=2, d_model=32,
                          n_heads=2, n_kv_heads=2, d_ff=0, vocab=64,
                          moe=MoEConfig(num_experts=4, top_k=2, d_expert=32,
                                        capacity_factor=2.0))
    else:
        cfg = ModelConfig("tiny-s", Family.SSM, n_layers=2, d_model=32,
                          n_heads=0, n_kv_heads=0, d_ff=0, vocab=64,
                          ssm=SSMConfig(d_state=8, head_dim=16, expand=2,
                                        chunk=8))
    plan = ParallelPlan(remat="none", compute_dtype="float32")
    return cfg, plan, build_model(cfg, plan)


def _world(family):
    cfg, plan, model = _arch(family)
    ds = SyntheticDataset(cfg, InputShape("t", 16, 4, "train"))
    get_batch = lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()}
    step_fn = jax.jit(make_train_step(model, plan, Hyper(total_steps=30)))
    state = init_train_state(model, jax.random.PRNGKey(0))
    return model, step_fn, get_batch, state


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Fault matrix


@pytest.mark.parametrize("family", ["dense", "moe", "ssm"])
@pytest.mark.parametrize("fault", ["nan", "spike", "repeated_spike", "hang"])
def test_fault_matrix(tmp_path, family, fault):
    model, step_fn, get_batch, state = _world(family)
    _, plan, _ = _arch(family)

    fired = {"n": 0}

    def injector(step, st):
        if step != FAULT_STEP:
            return st
        fired["n"] += 1
        if fault == "nan" and fired["n"] == 1:
            return st._replace(params=jax.tree.map(
                lambda x: x * jnp.float32("nan"), st.params))
        if fault == "spike" and fired["n"] == 1:
            return st._replace(params=jax.tree.map(
                lambda x: x * 8.0, st.params))
        if fault == "repeated_spike":   # persistent: fires on every replay
            return st._replace(params=jax.tree.map(
                lambda x: x * 8.0, st.params))
        if fault == "hang" and fired["n"] == 1:
            time.sleep(1.0)
        return st

    # hang tests need a low absolute floor; everything else pins it high so
    # scheduler jitter can never inject a hang into an unrelated case
    monitor = Monitor(min_history=4,
                      hang_min_seconds=0.3 if fault == "hang" else 30.0)
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    final, report = run_with_recovery(
        state, step_fn, get_batch, N_STEPS, ckpt, monitor,
        ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
        policy=RecoveryPolicy())

    # clean reference on the same jitted step; repeated_spike escalates to
    # skip-batch (no rescue_step given), so its reference skips the update
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        if fault == "repeated_spike" and s == FAULT_STEP:
            continue
        ref, _ = step_fn(ref, get_batch(s))

    if fault == "nan":
        assert report.actions == [(FAULT_STEP, "nan", "rollback")]
        assert report.restores == 1
    elif fault == "spike":
        assert report.actions == [(FAULT_STEP, "spike", "rollback")]
        assert report.restores == 1
    elif fault == "repeated_spike":
        assert report.actions == [(FAULT_STEP, "spike", "rollback"),
                                  (FAULT_STEP, "spike", "lr_rescue")]
        assert report.restores == 2
        assert np.isnan(report.losses[FAULT_STEP])   # the skipped batch
    else:
        assert (FAULT_STEP, "hang", "ignore") in report.actions
        assert report.restores == 0

    assert report.steps_done == N_STEPS
    assert len(report.losses) == N_STEPS
    _assert_trees_equal(final.params, ref.params)
    _assert_trees_equal(final.opt.mu, ref.opt.mu)


def test_lr_rescue_uses_rescue_step(tmp_path):
    """With a rescue_step provided, the second spike at a step rolls back and
    replays that step with the damped-LR twin instead of skipping it."""
    model, step_fn, get_batch, state = _world("dense")
    _, plan, _ = _arch("dense")
    rescue_fn = jax.jit(make_train_step(
        model, plan, Hyper(peak_lr=3e-4 * 0.1, total_steps=30)))

    fired = {"n": 0}

    def injector(step, st):   # transient bad host: fires on first 2 attempts
        if step == FAULT_STEP and fired["n"] < 2:
            fired["n"] += 1
            return st._replace(params=jax.tree.map(
                lambda x: x * 8.0, st.params))
        return st

    monitor = Monitor(min_history=4, hang_min_seconds=30.0)
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    final, report = run_with_recovery(
        state, step_fn, get_batch, N_STEPS, ckpt, monitor,
        ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
        policy=RecoveryPolicy(), rescue_step=rescue_fn)

    assert report.actions == [(FAULT_STEP, "spike", "rollback"),
                              (FAULT_STEP, "spike", "lr_rescue")]
    assert report.restores == 2

    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        fn = rescue_fn if s == FAULT_STEP else step_fn
        ref, _ = fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)


def test_recovery_gives_up_after_max_restores(tmp_path):
    """A persistent NaN exhausts max_restores and raises instead of looping."""
    model, step_fn, get_batch, state = _world("dense")

    def injector(step, st):
        if step == FAULT_STEP:
            return st._replace(params=jax.tree.map(
                lambda x: x * jnp.float32("nan"), st.params))
        return st

    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    with pytest.raises(RuntimeError, match="giving up after 2"):
        run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt,
            Monitor(min_history=4, hang_min_seconds=30.0),
            ckpt_every=CKPT_EVERY, fault_injector=injector,
            policy=RecoveryPolicy(max_restores=2))


def test_resume_continues_from_latest(tmp_path):
    """resume=True picks up at the latest checkpoint and the completed run
    matches an uninterrupted one (same-layout replay route)."""
    model, step_fn, get_batch, state = _world("dense")
    _, plan, _ = _arch("dense")
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    run_with_recovery(state, step_fn, get_batch, 10, ckpt,
                      Monitor(hang_min_seconds=30.0), ckpt_every=5, plan=plan)
    assert ckpt.latest_step() == 10

    tmpl = init_train_state(model, jax.random.PRNGKey(0))
    final, report = run_with_recovery(
        tmpl, step_fn, get_batch, N_STEPS, ckpt,
        Monitor(hang_min_seconds=30.0), ckpt_every=5, plan=plan, resume=True)

    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    assert report.steps_done == N_STEPS
    _assert_trees_equal(final.params, ref.params)


# ---------------------------------------------------------------------------
# Monitor units


def test_monitor_hang_window_not_contaminated():
    """A hang's wall-time must not enter the trailing median — otherwise one
    hang inflates the threshold and masks the next one."""
    m = Monitor(min_history=4, hang_factor=5.0)
    t = 0.0
    for s in range(8):
        m.record(s, 2.0, 1.0, now=t)
        t += 1.0
    a = m.record(8, 2.0, 1.0, now=t + 30.0)     # 31s vs 1s median
    assert a is not None and a.kind == "hang"
    assert max(m.times) == pytest.approx(1.0)   # 31s never entered the window
    # an identical second hang right after is still detected (median intact)
    a = m.record(9, 2.0, 1.0, now=t + 61.0)
    assert a is not None and a.kind == "hang"


def test_monitor_heartbeat_reset():
    """reset_heartbeat() absorbs non-step wall-time (checkpoint restore) —
    without it the next record() sees the gap as a hung step."""
    m = Monitor(min_history=4)
    t = 0.0
    for s in range(8):
        m.record(s, 2.0, 1.0, now=t)
        t += 1.0
    m.reset_heartbeat(now=t + 120.0)            # a 2-minute restore
    assert m.record(8, 2.0, 1.0, now=t + 121.0) is None


def test_monitor_hang_min_seconds_floor():
    m = Monitor(min_history=2, hang_min_seconds=10.0)
    t = 0.0
    for s in range(6):
        assert m.record(s, 2.0, 1.0, now=t) is None
        t += 0.01
    # 100x the median but under the absolute floor: not a hang
    assert m.record(6, 2.0, 1.0, now=t + 1.0) is None


# ---------------------------------------------------------------------------
# Checkpoint store: async snapshot, failure surfacing, reshard routing


def test_async_snapshot_isolated_from_donation(tmp_path):
    """The double-buffered snapshot clones on device before save() returns,
    so deleting the source buffers (what donation does) while the background
    copy drains must not corrupt the checkpoint."""
    tree = {"w": jnp.arange(4096, dtype=jnp.float32).reshape(64, 64),
            "step": jnp.asarray(7, jnp.int32)}
    want = {k: np.asarray(v) for k, v in tree.items()}
    mgr = CheckpointManager(tmp_path, async_snapshot=True)
    mgr.save(1, tree)
    assert mgr.snapshot_seconds < 1.0
    tree["w"].delete()                          # simulate donation
    tree["step"].delete()
    mgr.wait()
    fresh = {"w": jnp.zeros((64, 64), jnp.float32),
             "step": jnp.asarray(0, jnp.int32)}
    _, restored = mgr.restore(fresh)
    np.testing.assert_array_equal(np.asarray(restored["w"]), want["w"])
    assert int(restored["step"]) == 7


def test_async_snapshot_matches_blocking(tmp_path):
    tree = {"w": jnp.arange(128, dtype=jnp.float32)}
    a = CheckpointManager(tmp_path / "a", async_snapshot=True)
    b = CheckpointManager(tmp_path / "b", async_snapshot=False)
    a.save(3, tree)
    b.save(3, tree, blocking=True)
    a.wait()
    za = np.load(tmp_path / "a" / "ckpt_00000003.npz")
    zb = np.load(tmp_path / "b" / "ckpt_00000003.npz")
    assert sorted(za.files) == sorted(zb.files)
    for k in za.files:
        np.testing.assert_array_equal(za[k], zb[k])


def test_persist_failure_surfaces_at_next_call(tmp_path):
    """A background persist failure must raise at the next save()/wait(),
    not vanish with the daemon thread."""
    import shutil
    d = tmp_path / "ckpts"
    mgr = CheckpointManager(d)
    tree = {"w": jnp.ones((8,))}
    shutil.rmtree(d)
    d.write_text("not a directory")             # make every write fail
    mgr.save(1, tree)
    with pytest.raises(RuntimeError, match="background checkpoint persist"):
        mgr.wait()
    mgr.wait()                                  # error raised once, then clear
    mgr.save(2, tree)
    with pytest.raises(RuntimeError, match="background checkpoint persist"):
        mgr.save(3, tree)                       # save() also surfaces it


def test_check_plan_routes_replay_reshard(tmp_path):
    tree = {"w": jnp.ones((8, 8))}
    mgr = CheckpointManager(tmp_path, async_persist=False)
    plan = ParallelPlan(cp=1)
    mgr.save(1, tree, blocking=True, plan=plan)
    assert mgr.check_plan(plan) == "replay"
    assert mgr.check_plan(ParallelPlan(cp=1), elastic=True) == "replay"
    # layout change: strict call refuses, elastic routes to reshard
    with pytest.raises(ValueError, match="layout mismatch"):
        mgr.check_plan(ParallelPlan(zero_stage=0))
    assert mgr.check_plan(ParallelPlan(zero_stage=0), elastic=True) == "reshard"
    # schedule/impl knobs are not layout: still replay
    assert mgr.check_plan(ParallelPlan(pp_schedule="gpipe")) == "replay"


def test_restore_resharded_matches_restore_single_device(tmp_path):
    """With no layout change, restore_resharded degrades to restore."""
    _, _, model = _arch("dense")
    state = init_train_state(model, jax.random.PRNGKey(0))
    mgr = CheckpointManager(tmp_path, async_persist=False)
    mgr.save(4, state, blocking=True)
    _, a = mgr.restore(state)
    _, b = mgr.restore_resharded(state)
    _assert_trees_equal(a, b)


# ---------------------------------------------------------------------------
# Multidevice: cross-mesh reshard + the elastic 2×2 -> 1×2 acceptance run


def test_restore_resharded_cross_mesh(multidevice):
    """A checkpoint written row-sharded on a (4,) mesh restores column-
    sharded on a (2,2) mesh with identical values and the target layout."""
    multidevice("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh

devs = jax.devices()
m1 = make_mesh((4,), ("data",))
m2 = make_mesh((2, 2), ("data", "model"))
x = jax.device_put(jnp.arange(32 * 32, dtype=jnp.float32).reshape(32, 32),
                   NamedSharding(m1, P("data", None)))
mgr = CheckpointManager(tempfile.mkdtemp(), async_persist=False)
mgr.save(1, {"w": x}, blocking=True, mesh=m1)

tgt = NamedSharding(m2, P(None, ("data", "model")))
step, out = mgr.restore_resharded({"w": x}, shardings={"w": tgt})
np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x))
assert out["w"].sharding == tgt
assert len(out["w"].sharding.device_set) == 4
# every device now holds a (32, 8) column slice
assert out["w"].addressable_shards[0].data.shape == (32, 8)
print("cross-mesh reshard OK")
""", n_devices=4)


def test_elastic_remesh_2x2_to_1x2(multidevice):
    """The §8.3.2 acceptance: train on a 2×2 (data, model) mesh with ZeRO-1,
    hang at step 13 (simulated host loss), remesh to the surviving 1×2,
    reshard-restore params + data-scattered AdamW moments, and finish. The
    whole loss sequence and the final state must bit-match a reference that
    ran the same schedule with a direct device_put re-layout at the same
    boundary — i.e. the checkpoint/reshard path adds zero numerical
    perturbation."""
    multidevice("""
import time, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.core import (Family, InputShape, ModelConfig, ParallelPlan,
                        RecoveryPolicy, sharding)
from repro.data import SyntheticDataset
from repro.ft import Monitor, RemeshSpec, run_with_recovery
from repro.launch.mesh import shrink_mesh
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=4, d_ff=128, vocab=128)
plan = ParallelPlan(remat="none", compute_dtype="float32", zero_stage=1)
hyper = Hyper(peak_lr=1e-3, total_steps=40, z_loss=0.0)
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
get_batch = lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()}
N, FAULT, EVERY = 20, 13, 5

mesh = make_mesh((2, 2), ("data", "model"))
model = build_model(cfg, plan, mesh, ("data",))
state0 = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)
step_big = jax.jit(make_train_step(model, plan, hyper, mesh=mesh))

# the surviving world: one data slice lost -> 1x2
mesh2 = shrink_mesh(mesh, "data", lost=1)
assert dict(mesh2.shape) == {"data": 1, "model": 2}
model2 = build_model(cfg, plan, mesh2, ("data",))
tmpl = init_train_state(model2, jax.random.PRNGKey(1), mesh=mesh2, plan=plan)
shardings = sharding.train_state_shardings(tmpl, cfg, plan, mesh2)
step_small = jax.jit(make_train_step(model2, plan, hyper, mesh=mesh2))
# warm the 1x2 compile now, on exactly the layout restore_resharded will
# produce (every leaf committed to its target sharding): the first
# post-remesh step's wall-time feeds the hang watchdog, and a cold compile
# there would read as another hang
tmpl = jax.tree.map(jax.device_put, tmpl, shardings)
jax.block_until_ready(step_small(tmpl, get_batch(0))[0].params)

def remesh():
    return RemeshSpec(train_step=step_small, state_template=tmpl,
                      shardings=shardings, plan=plan, mesh=mesh2)

fired = {"n": 0}
def injector(step, st):
    if step == FAULT and fired["n"] == 0:
        fired["n"] = 1
        time.sleep(1.0)          # the lost host: one step hangs
    return st

ckpt = CheckpointManager(tempfile.mkdtemp(), keep=3, async_persist=False)
final, report = run_with_recovery(
    state0, step_big, get_batch, N, ckpt,
    Monitor(min_history=4, hang_min_seconds=0.3),
    ckpt_every=EVERY, plan=plan, mesh=mesh,
    policy=RecoveryPolicy(hang="remesh"), fault_injector=injector,
    remesh=remesh)

assert report.remeshes == 1, report
assert report.restores == 1, report
assert report.actions == [(FAULT, "hang", "remesh")], report.actions
assert report.steps_done == N

# post-remesh checkpoints record the shrunken mesh
assert ckpt.manifest()["mesh_axes"] == {"data": 1, "model": 2}

# reference: same prefix on 2x2 (identical program), direct device_put
# re-layout at the rollback boundary (step 10), same continuation program
ref = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)
ref_losses = []
for s in range(2 * EVERY):
    ref, m = step_big(ref, get_batch(s))
    ref_losses.append(float(m["loss"]))
ref = jax.tree.map(jax.device_put, ref, shardings)
for s in range(2 * EVERY, N):
    ref, m = step_small(ref, get_batch(s))
    ref_losses.append(float(m["loss"]))

assert report.losses == ref_losses, (report.losses, ref_losses)
for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(ref)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# the restored moments really live on the new layout (ZeRO-1 re-scatter)
mu_wq = final.opt.mu["layers"]["attn"]["wq"]
assert mu_wq.sharding.mesh.shape == mesh2.shape
print("elastic 2x2 -> 1x2 OK: losses bit-match, remeshes=1")
""", n_devices=4)
