"""Deterministic fault injection + SDC defense (survey §8.1/§8.2).

Unit level: FaultSpec determinism, corrupt_array semantics, faulty-twin
tracing, kernel-dispatch fault points, Monitor inf handling, atomic
checkpoint writes, persist retry/backoff, and newest-intact fallback
restores through ``run_with_recovery``.

The headline acceptance is the **chaos matrix** at the bottom: every fault
class — state spike, host hang, NaN ring-payload corruption, rank-masked
SDC at the integrity checksum, and a silently dropped shard write — is
injected at a scheduled step into a 2×2-mesh run of each model family
(dense, MoE, Mamba2) with ``plan.integrity = "audit"`` + ZeRO-1; every
fault is detected, recovered per the policy table, and the final state
bit-matches the fault-free schedule.

The ``slow`` rows extend the matrix with the fail-slow class (survey
§8.1): a seeded, rank-masked delay on one context-parallel ring rank per
family, detected and attributed to ``(rank=1, cp.ring, comm)`` by the
straggler telemetry within its confirm window; delays cost wall clock but
corrupt nothing, so the run still bit-matches the fault-free schedule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.checkpoint.store import CorruptCheckpointError
from repro.core import (Family, InputShape, ModelConfig, ParallelPlan,
                        RecoveryPolicy)
from repro.data import SyntheticDataset
from repro.ft import Monitor, RecoveryExhausted, run_with_recovery
from repro.ft.inject import (CONTROLLER, FaultSpec, InjectedFault, armed,
                             corrupt_array, make_injector, taint,
                             trace_with_faults)
from repro.ft.integrity import replica_divergence, tree_checksum
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step

N_STEPS = 20
CKPT_EVERY = 5


def _world():
    cfg = ModelConfig("tiny-d", Family.DENSE, n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    plan = ParallelPlan(remat="none", compute_dtype="float32")
    model = build_model(cfg, plan)
    ds = SyntheticDataset(cfg, InputShape("t", 16, 4, "train"))
    get_batch = lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()}
    step_fn = jax.jit(make_train_step(model, plan, Hyper(total_steps=30)))
    state = init_train_state(model, jax.random.PRNGKey(0))
    return model, plan, step_fn, get_batch, state


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# FaultSpec / corrupt_array / taint units


def test_fault_spec_validates_point_and_kind():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultSpec("no.such.point", "nan")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("train.step", "gremlin")


def test_fault_spec_key_is_stable():
    a = FaultSpec("train.step", "bitflip", step=7, seed=3)
    b = FaultSpec("train.step", "bitflip", step=7, seed=3)
    c = FaultSpec("train.step", "bitflip", step=7, seed=4)
    assert a.key() == b.key() != c.key()


def test_corrupt_array_bitflip_deterministic():
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) + 1.0
    sp = FaultSpec("kernel.attention", "bitflip", step=5, seed=1)
    a = np.asarray(corrupt_array(x, sp))
    b = np.asarray(corrupt_array(x, sp))
    np.testing.assert_array_equal(a, b)          # replayable bit-for-bit
    diff = (a != np.asarray(x)).sum()
    assert diff == 1                              # exactly one element flipped
    # the flip hits a high exponent bit: the damage is loud, not subtle
    bad = a[a != np.asarray(x)][0]
    ref = np.asarray(x)[a != np.asarray(x)][0]
    assert abs(bad) > 4 * abs(ref) or abs(bad) < abs(ref) / 4


def test_corrupt_array_nan_poisons_one_element():
    x = jnp.ones((4, 4), jnp.float32)
    out = np.asarray(corrupt_array(
        x, FaultSpec("kernel.attention", "nan", step=3)))
    assert np.isnan(out).sum() == 1


def test_taint_is_identity_when_unarmed():
    x = jnp.ones((3,))
    np.testing.assert_array_equal(np.asarray(taint("tp.ring.tick", x)),
                                  np.asarray(x))
    with pytest.raises(ValueError, match="unknown fault point"):
        taint("not.registered", x)


def test_trace_with_faults_builds_faulty_twin_and_disarms():
    def fn(x):
        return taint("tp.ring.tick", x) * 2.0

    x = jnp.ones((4,), jnp.float32)
    twin = trace_with_faults(
        fn, x, specs=[FaultSpec("tp.ring.tick", "nan", step=0, tick=None)])
    assert np.isnan(np.asarray(twin(x))).any()
    # the controller is clean on exit: a fresh trace is the identity
    assert not CONTROLLER._specs
    assert not np.isnan(np.asarray(jax.jit(fn)(x))).any()


@pytest.mark.parametrize("which", ["attention", "expert_gemm", "ssd"])
def test_kernel_dispatch_fault_points(which):
    """Each dispatcher's output routes through its named fault point: a nan
    armed at trace time lands in the faulty twin's output and nowhere else."""
    from repro.kernels.dispatch import (dispatch_attention,
                                        dispatch_expert_gemm,
                                        dispatch_ssd_scan)
    if which == "attention":
        q = jnp.ones((1, 8, 2, 8), jnp.float32)
        fn = lambda: dispatch_attention(q, q, q, impl="xla")
    elif which == "expert_gemm":
        x = jnp.ones((2, 4, 8), jnp.float32)
        w = jnp.ones((2, 8, 8), jnp.float32)
        fn = lambda: dispatch_expert_gemm(x, w, impl="xla")
    else:
        xs = jnp.ones((1, 8, 2, 4), jnp.float32)
        dt = jnp.full((1, 8, 2), 0.1, jnp.float32)
        A = -jnp.ones((2,), jnp.float32)
        B = jnp.ones((1, 8, 1, 4), jnp.float32)
        fn = lambda: dispatch_ssd_scan(xs, dt, A, B, B, chunk=4, impl="xla")[0]
    point = {"attention": "kernel.attention",
             "expert_gemm": "kernel.expert_gemm",
             "ssd": "kernel.ssd"}[which]
    clean = np.asarray(jax.jit(fn)())
    assert not np.isnan(clean).any()
    twin = trace_with_faults(
        fn, specs=[FaultSpec(point, "nan", step=0, tick=None)])
    assert np.isnan(np.asarray(twin())).any()


def test_make_injector_fires_once_per_times():
    model, _, _, _, state = _world()
    inj = make_injector([FaultSpec("train.step", "nan", step=3, times=1)])
    poisoned = inj(3, state)
    assert any(np.isnan(np.asarray(l)).any()
               for l in jax.tree.leaves(poisoned.params))
    again = inj(3, state)                        # times=1: second pass clean
    _assert_trees_equal(again.params, state.params)


# ---------------------------------------------------------------------------
# Integrity checksums


def test_tree_checksum_exact_single_bit():
    t = {"w": jnp.arange(256, dtype=jnp.float32)}
    base = int(tree_checksum(t))
    flipped = np.asarray(t["w"]).copy()
    flipped_view = flipped.view(np.uint32)
    flipped_view[17] ^= 1                         # lowest mantissa bit
    assert int(tree_checksum({"w": jnp.asarray(flipped)})) != base


def test_replica_divergence_trivial_mesh_is_zero():
    cs, div = replica_divergence({"w": jnp.ones((8,))}, mesh=None)
    assert float(div) == 0.0
    assert int(cs) == int(tree_checksum({"w": jnp.ones((8,))}))


def test_plan_integrity_knob_validated():
    cfg = ModelConfig("tiny-d", Family.DENSE, n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    ParallelPlan(integrity="audit").validate(cfg)
    with pytest.raises(ValueError, match="integrity"):
        ParallelPlan(integrity="paranoid").validate(cfg)


def test_integrity_audit_metrics_single_device():
    cfg = ModelConfig("tiny-d", Family.DENSE, n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    plan = ParallelPlan(remat="none", compute_dtype="float32",
                        integrity="audit")
    model = build_model(cfg, plan)
    ds = SyntheticDataset(cfg, InputShape("t", 16, 4, "train"))
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    step_fn = jax.jit(make_train_step(model, plan, Hyper(total_steps=30)))
    state = init_train_state(model, jax.random.PRNGKey(0))
    _, metrics = step_fn(state, batch)
    assert float(metrics["integrity_div"]) == 0.0
    assert "integrity_checksum" in metrics


# ---------------------------------------------------------------------------
# Monitor: inf is as dead as nan


def test_monitor_inf_loss_is_nan_kind():
    m = Monitor()
    a = m.record(0, float("inf"), 1.0, now=0.0)
    assert a is not None and a.kind == "nan"


def test_monitor_neg_inf_grad_norm_is_nan_kind():
    m = Monitor()
    a = m.record(0, 2.0, float("-inf"), now=0.0)
    assert a is not None and a.kind == "nan"
    assert len(m.losses) == 0     # an anomalous step never enters the window


# ---------------------------------------------------------------------------
# Checkpoint store: atomicity, manifest digests, retry/backoff


def test_persist_is_atomic_no_temp_residue(tmp_path):
    mgr = CheckpointManager(tmp_path, async_persist=False)
    mgr.save(1, {"w": jnp.ones((16,))}, blocking=True)
    names = sorted(p.name for p in tmp_path.iterdir())
    assert names == ["ckpt_00000001.json", "ckpt_00000001.npz"]
    man = mgr.manifest(1)
    m0 = man["shards"][0][0]
    assert {"crc32", "dtype", "shape", "checksum"} <= set(m0)


def test_truncated_shard_file_detected(tmp_path):
    mgr = CheckpointManager(tmp_path, async_persist=False)
    mgr.save(1, {"w": jnp.arange(4096, dtype=jnp.float32)}, blocking=True)
    npz = tmp_path / "ckpt_00000001.npz"
    npz.write_bytes(npz.read_bytes()[: npz.stat().st_size // 2])
    with pytest.raises(CorruptCheckpointError):
        mgr.restore({"w": jnp.zeros((4096,), jnp.float32)})


def test_corrupted_manifest_detected(tmp_path):
    mgr = CheckpointManager(tmp_path, async_persist=False)
    mgr.save(1, {"w": jnp.ones((8,))}, blocking=True)
    (tmp_path / "ckpt_00000001.json").write_text("{ not json")
    with pytest.raises(CorruptCheckpointError, match="manifest"):
        mgr.restore({"w": jnp.zeros((8,), jnp.float32)})


def test_bitflipped_shard_detected_as_checksum_mismatch(tmp_path):
    mgr = CheckpointManager(tmp_path, async_persist=False)
    mgr.save(1, {"w": jnp.arange(64, dtype=jnp.float32)}, blocking=True)
    npz = tmp_path / "ckpt_00000001.npz"
    data = dict(np.load(npz))
    bits = data["a0"].view(np.uint32)
    bits[7] ^= 1 << 30                            # one flipped bit on disk
    np.savez(str(npz)[:-4], **data)
    with pytest.raises(IOError, match="checksum"):
        mgr.restore({"w": jnp.zeros((64,), jnp.float32)})


def test_persist_retry_recovers_transient_failure(tmp_path):
    """One injected persist exception with io_retries=3: the retry loop
    absorbs it and the checkpoint lands intact."""
    mgr = CheckpointManager(tmp_path, async_persist=False, io_retries=3,
                            io_backoff=0.01)
    tree = {"w": jnp.arange(32, dtype=jnp.float32)}
    with armed([FaultSpec("ckpt.persist", "persist_exc", step=1, times=1)]):
        mgr.save(1, tree, blocking=True)
    _, got = mgr.restore({"w": jnp.zeros((32,), jnp.float32)})
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_persist_retry_exhaustion_raises(tmp_path):
    mgr = CheckpointManager(tmp_path, async_persist=False, io_retries=2,
                            io_backoff=0.01)
    with armed([FaultSpec("ckpt.persist", "persist_exc", step=1, times=99)]):
        with pytest.raises(InjectedFault):
            mgr.save(1, {"w": jnp.ones((8,))}, blocking=True)
    assert mgr.latest_step() is None              # nothing half-written


def test_dropped_shard_write_leaves_listed_but_corrupt(tmp_path):
    """drop_write is *silent*: the manifest lists the checkpoint (that is the
    point — the writer saw no error), restore detects the missing npz."""
    mgr = CheckpointManager(tmp_path, async_persist=False)
    with armed([FaultSpec("ckpt.shard_write", "drop_write", step=1)]):
        mgr.save(1, {"w": jnp.ones((8,))}, blocking=True)
    assert mgr.steps() == [1]
    with pytest.raises(CorruptCheckpointError):
        mgr.restore({"w": jnp.zeros((8,), jnp.float32)})


# ---------------------------------------------------------------------------
# run_with_recovery: fallback restores, ckpt_io policy, exhaustion


def test_recovery_falls_back_to_intact_checkpoint(tmp_path):
    """A dropped shard write at the step-10 save + a NaN at step 13: the
    rollback skips the corrupt latest (10) and replays from 5."""
    model, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    injector = make_injector([FaultSpec("train.step", "nan", step=13)])
    with armed([FaultSpec("ckpt.shard_write", "drop_write", step=10)]):
        final, report = run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt,
            Monitor(min_history=4, hang_min_seconds=30.0),
            ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
            policy=RecoveryPolicy())

    assert report.restores == 1
    assert report.ckpt_fallbacks == 1
    assert (13, "nan", "rollback") in report.actions
    assert any(a.kind == "ckpt_corrupt" for a in report.anomalies)
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)
    _assert_trees_equal(final.opt.mu, ref.opt.mu)


def test_recovery_truncated_latest_falls_back(tmp_path):
    model, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    injector = make_injector([FaultSpec("train.step", "nan", step=13)])
    with armed([FaultSpec("ckpt.shard_write", "truncate_write", step=10)]):
        final, report = run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt,
            Monitor(min_history=4, hang_min_seconds=30.0),
            ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
            policy=RecoveryPolicy())
    assert report.restores == 1 and report.ckpt_fallbacks == 1
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)


def test_recovery_ckpt_io_anomaly_ignored_by_default(tmp_path):
    """Exhausted persist retries surface as a ckpt_io anomaly; the default
    policy keeps training (the run itself is healthy)."""
    model, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False,
                             io_retries=2, io_backoff=0.01)
    with armed([FaultSpec("ckpt.persist", "persist_exc", step=5, times=99)]):
        final, report = run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt,
            Monitor(min_history=4, hang_min_seconds=30.0),
            ckpt_every=CKPT_EVERY, plan=plan, policy=RecoveryPolicy())
    assert (5, "ckpt_io", "ignore") in report.actions
    assert any(a.kind == "ckpt_io" for a in report.anomalies)
    assert report.restores == 0
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)


def test_recovery_exhaustion_attaches_anomaly(tmp_path):
    """max_restores exhaustion raises RecoveryExhausted carrying the anomaly
    that forced the refused restore (kind + step for postmortems)."""
    _, plan, step_fn, get_batch, state = _world()
    injector = make_injector(
        [FaultSpec("train.step", "nan", step=13, times=99)])
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    with pytest.raises(RecoveryExhausted, match="giving up after 2") as ei:
        run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt,
            Monitor(min_history=4, hang_min_seconds=30.0),
            ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
            policy=RecoveryPolicy(max_restores=2))
    assert ei.value.restores == 2
    assert ei.value.anomaly is not None
    assert ei.value.anomaly.kind == "nan"
    assert ei.value.anomaly.step == 13


def test_recovery_all_checkpoints_corrupt_raises(tmp_path):
    _, plan, step_fn, get_batch, state = _world()
    injector = make_injector([FaultSpec("train.step", "nan", step=7)])
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    with armed([FaultSpec("ckpt.shard_write", "drop_write", step=0),
                FaultSpec("ckpt.shard_write", "drop_write", step=5)]):
        with pytest.raises(CorruptCheckpointError):
            run_with_recovery(
                state, step_fn, get_batch, N_STEPS, ckpt,
                Monitor(min_history=4, hang_min_seconds=30.0),
                ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
                policy=RecoveryPolicy())


# ---------------------------------------------------------------------------
# The chaos matrix (multidevice acceptance)

_CHAOS_TEMPLATE = """
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.core import (Family, InputShape, ModelConfig, MoEConfig, SSMConfig,
                        ParallelPlan, RecoveryPolicy)
from repro.data import SyntheticDataset
from repro.ft import Monitor, run_with_recovery
from repro.ft.inject import FaultSpec, armed, make_injector, trace_with_faults
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step
from repro.launch.mesh import make_mesh

cfg = {cfg}
plan = ParallelPlan(remat="none", compute_dtype="float32", cp=2,
                    zero_stage=1, integrity="audit"{plan_extra})
mesh = make_mesh((2, 2), ("data", "cp"))
model = build_model(cfg, plan, mesh, ("data",))
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
get_batch = lambda s: {{k: jnp.asarray(v) for k, v in ds.batch(s).items()}}
hyper = Hyper(peak_lr=1e-3, total_steps=40, z_loss=0.0)
N, EVERY = 20, 5

raw_step = make_train_step(model, plan, hyper, mesh=mesh)
step_fn = jax.jit(raw_step)
state0 = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)

# fixed-point layouts: trace the faulty twins on a state the step itself
# produced, so mid-run twin calls hit the compiled trace, never a re-trace
# (a re-trace outside the armed window would silently drop the corruption)
probe, _ = step_fn(state0, get_batch(0))
jax.block_until_ready(jax.tree.leaves(probe))

# scheduled faults: one per class, replayable bit-identically
nan_twin = trace_with_faults(
    raw_step, probe, get_batch(12),
    specs=[FaultSpec("{payload_point}", "nan", step=12, tick=None)])
sdc_twin = trace_with_faults(
    raw_step, probe, get_batch(14),
    specs=[FaultSpec("integrity.checksum", "bitflip", step=14, tick=None,
                     rank=0, axis="cp")])

used = {{12: 0, 14: 0, 17: 0}}
def fault_step_fn(step):
    if step in (12, 17) and used[step] < 1:
        used[step] += 1
        return nan_twin
    if step == 14 and used[14] < 1:
        used[14] += 1
        return sdc_twin
    return None

injector = make_injector([
    FaultSpec("train.step", "spike", step=8, scale=8.0),
    FaultSpec("train.step", "hang", step=18, sleep_s=1.0),
])

ckpt = CheckpointManager(tempfile.mkdtemp(), keep=3, async_persist=False)
monitor = Monitor(min_history=4, hang_min_seconds=0.3)
with armed([FaultSpec("ckpt.shard_write", "drop_write", step=15)]):
    final, report = run_with_recovery(
        state0, step_fn, get_batch, N, ckpt, monitor, ckpt_every=EVERY,
        plan=plan, mesh=mesh, policy=RecoveryPolicy(max_restores=8),
        fault_injector=injector, fault_step_fn=fault_step_fn)

assert report.actions == [
    (8, "spike", "rollback"),      # state spike -> statistical detector
    (12, "nan", "rollback"),       # ring-payload NaN -> nan detector
    (14, "sdc", "rollback"),       # rank-masked checksum flip -> sdc
    (17, "nan", "rollback"),       # second payload fault, after the
                                   # silently-dropped step-15 shard write
    (18, "hang", "ignore"),        # host hang -> watchdog, advisory
], report.actions
assert report.restores == 4, report
assert report.ckpt_fallbacks == 1, report      # corrupt 15 skipped -> 10
assert any(a.kind == "ckpt_corrupt" for a in report.anomalies)
assert report.steps_done == N
assert len(report.losses) == N

# the recovered schedule bit-matches the fault-free one, losses included
ref = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)
ref_losses = []
for s in range(N):
    ref, m = step_fn(ref, get_batch(s))
    assert float(m["integrity_div"]) == 0.0, (s, m)
    ref_losses.append(float(m["loss"]))
assert report.losses == ref_losses, (report.losses, ref_losses)
for a, b in zip(jax.tree.leaves(final.params), jax.tree.leaves(ref.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(final.opt.mu), jax.tree.leaves(ref.opt.mu)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("chaos matrix OK: 5 faults detected, recovered, bit-matched")
"""

_DENSE_CFG = """ModelConfig("tiny", Family.DENSE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab=128)"""
_MOE_CFG = """ModelConfig("tmoe", Family.MOE, n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=0, vocab=128,
                 moe=MoEConfig(num_experts=4, top_k=2, d_expert=64,
                               num_shared_experts=1, capacity_factor=2.0))"""
_SSM_CFG = """ModelConfig("tssm", Family.SSM, n_layers=2, d_model=64,
                 n_heads=0, n_kv_heads=0, d_ff=0, vocab=128,
                 ssm=SSMConfig(d_state=16, head_dim=16, expand=2, chunk=8))"""


def test_chaos_matrix_dense(multidevice):
    multidevice(_CHAOS_TEMPLATE.format(
        cfg=_DENSE_CFG, payload_point="cp.ring.kv",
        plan_extra=', cp_impl="ring"'), n_devices=4)


def test_chaos_matrix_moe(multidevice):
    multidevice(_CHAOS_TEMPLATE.format(
        cfg=_MOE_CFG, payload_point="cp.ring.kv",
        plan_extra=', cp_impl="ring"'), n_devices=4)


def test_chaos_matrix_mamba2(multidevice):
    """The SSD entering-state chain is the corrupted link for Mamba2."""
    multidevice(_CHAOS_TEMPLATE.format(
        cfg=_SSM_CFG, payload_point="cp.ring.state",
        plan_extra=""), n_devices=4)


_SLOW_TEMPLATE = """
import tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.core import (Family, InputShape, ModelConfig, MoEConfig, SSMConfig,
                        ParallelPlan, RecoveryPolicy)
from repro.data import SyntheticDataset
from repro.ft import Monitor, StragglerDetector, StragglerTimer, \\
    run_with_recovery
from repro.launch.mesh import make_mesh
from repro.ft.inject import FaultSpec, armed
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step

cfg = {cfg}
plan = ParallelPlan(remat="none", compute_dtype="float32", cp=2,
                    zero_stage=1, integrity="audit"{plan_extra})
mesh = make_mesh((2, 2), ("data", "cp"))
model = build_model(cfg, plan, mesh, ("data",))
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
get_batch = lambda s: {{k: jnp.asarray(v) for k, v in ds.batch(s).items()}}
hyper = Hyper(peak_lr=1e-3, total_steps=40, z_loss=0.0)
N = 16

step_fn = jax.jit(make_train_step(model, plan, hyper, mesh=mesh))
state0 = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)

detector = StragglerDetector(factor=2.0, confirm=2, min_seconds=5e-3)
timer = StragglerTimer(cfg=cfg, plan=plan, detector=detector)
ckpt = CheckpointManager(tempfile.mkdtemp(), keep=3, async_persist=False)
# the injected delay lands in the next step's wall-clock interval too —
# keep the hang watchdog out of the straggler ladder's way
monitor = Monitor(min_history=4, hang_min_seconds=60.0)

# rank 1 of the context-parallel ring degrades from step 6 onward
with armed([FaultSpec("{slow_point}", "slow", step=6, span=999, rank=1,
                      sleep_s=0.05)]):
    final, report = run_with_recovery(
        state0, step_fn, get_batch, N, ckpt, monitor, ckpt_every=5,
        plan=plan, mesh=mesh, policy=RecoveryPolicy(),    # straggler: ignore
        straggler=timer)

assert report.steps_done == N, report
strag = [a for a in report.anomalies if a.kind == "straggler"]
assert strag, report.anomalies
assert strag[0].step <= 6 + 2, strag[0]         # within the confirm window
assert "rank=1" in strag[0].detail and "class=comm" in strag[0].detail, \\
    strag[0].detail
assert "cp.ring" in strag[0].detail, strag[0].detail
assert all(k == "straggler" and act == "ignore"
           for _, k, act in report.actions), report.actions
assert report.restores == 0 and report.rebalances == 0, report

# fail-slow delays cost wall clock but corrupt nothing: the run bit-matches
# the fault-free schedule
ref = init_train_state(model, jax.random.PRNGKey(0), mesh=mesh, plan=plan)
ref_losses = []
for s in range(N):
    ref, m = step_fn(ref, get_batch(s))
    ref_losses.append(float(m["loss"]))
assert report.losses == ref_losses, (report.losses, ref_losses)
for a, b in zip(jax.tree.leaves(final.params), jax.tree.leaves(ref.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("SLOW_OK: attributed (rank=1, cp.ring, comm), run bit-matched")
"""


def test_chaos_slow_dense(multidevice):
    out = multidevice(_SLOW_TEMPLATE.format(
        cfg=_DENSE_CFG, slow_point="cp.ring.kv",
        plan_extra=', cp_impl="ring"'), n_devices=4)
    assert "SLOW_OK" in out


def test_chaos_slow_moe(multidevice):
    out = multidevice(_SLOW_TEMPLATE.format(
        cfg=_MOE_CFG, slow_point="cp.ring.kv",
        plan_extra=', cp_impl="ring"'), n_devices=4)
    assert "SLOW_OK" in out


def test_chaos_slow_mamba2(multidevice):
    """For Mamba2 the degraded link is the SSD entering-state ring."""
    out = multidevice(_SLOW_TEMPLATE.format(
        cfg=_SSM_CFG, slow_point="cp.ring.state",
        plan_extra=""), n_devices=4)
    assert "SLOW_OK" in out


def test_sdc_detected_multidevice(multidevice):
    """plan.integrity='audit' end to end: a rank-masked bitflip on the
    checksum input produces nonzero integrity_div on a real mesh, and the
    clean step reports exactly 0.0."""
    multidevice("""
import jax, jax.numpy as jnp
from repro.ft.inject import FaultSpec, trace_with_faults
from repro.ft.integrity import replica_divergence
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2), ("data", "cp"))
tree = {"w": jnp.arange(64, dtype=jnp.float32)}

def audit(t):
    return replica_divergence(t, mesh=mesh)

cs, div = jax.jit(audit)(tree)
assert float(div) == 0.0, float(div)

twin = trace_with_faults(
    audit, tree,
    specs=[FaultSpec("integrity.checksum", "bitflip", step=0, tick=None,
                     rank=1, axis="data")])
_, div2 = twin(tree)
assert float(div2) != 0.0, float(div2)
print("sdc divergence detected:", float(div2))
""", n_devices=4)
