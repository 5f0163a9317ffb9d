"""Fast-recovery tier (survey §8.3.1): in-memory peer-redundant checkpoints,
verify-before-evict GC, the always-flushed persist fence, and the crash
flight recorder.

Covers the tentpole acceptance at unit/integration level:

- the RAM ring restores bit-identically, and a peer rebuild after a
  simulated lost host-group bit-matches the disk restore of the same step;
- the recovery driver restores memory-tier-first (``mem_restores``) and
  falls back to the verified disk walk when the tier is lost;
- ``CheckpointManager._gc`` never evicts the newest *intact* checkpoint
  even when a burst of silently-dropped shard writes makes every kept
  checkpoint corrupt (the regression the keep-floor exists for);
- background persist failures surface on *every* exit path (the ``finally``
  fence), including exception exits;
- every failure mode leaves a parseable flight-recorder JSON naming the
  anomaly, step, and action (``RecoveryExhausted`` carries the path).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, CorruptCheckpointError,
                              MemoryCheckpointTier)
from repro.checkpoint.store import layout_diffs
from repro.core import (Family, InputShape, ModelConfig, ParallelPlan,
                        RecoveryPolicy)
from repro.data import SyntheticDataset
from repro.ft import (FlightRecorder, Monitor, RecoveryExhausted,
                      run_with_recovery)
from repro.ft.inject import FaultSpec, armed, make_injector
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


def _quiet():
    return Monitor(min_history=1000, hang_min_seconds=60.0)


# ---------------------------------------------------------------------------
# Memory tier units


def test_memory_tier_roundtrip_and_ring_eviction():
    tree = {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
            "b": jnp.ones((6,), jnp.float32)}
    mem = MemoryCheckpointTier(keep=2, groups=2)
    for s in (3, 6, 9):
        mem.save(s, tree)
    assert mem.steps() == [6, 9]           # ring maxlen evicted step 3
    assert mem.latest_step() == 9
    step, got = mem.restore(tree)
    assert step == 9
    _assert_trees_equal(got, tree)
    assert mem.last_rebuild == 0           # pure primary fast path
    step, _ = mem.restore(tree, step=6)
    assert step == 6
    with pytest.raises(CorruptCheckpointError, match="not in memory tier"):
        mem.restore(tree, step=3)
    mem.clear()
    with pytest.raises(CorruptCheckpointError, match="empty"):
        mem.restore(tree)


def test_memory_tier_evicts_before_building(monkeypatch):
    """A save never holds keep + 1 snapshots in RAM: the oldest entry goes
    before the new one's host copies are made."""
    import repro.checkpoint.memory as M

    tree = {"w": jnp.ones((4, 6), jnp.float32)}
    mem = MemoryCheckpointTier(keep=2, groups=2)
    held = []
    orig = M._leaf_shards

    def spy(x, copy=False):
        held.append(len(mem._ring))
        return orig(x, copy=copy)

    monkeypatch.setattr(M, "_leaf_shards", spy)
    for s in range(4):
        mem.save(s, tree)
    assert held == [0, 1, 1, 1]
    assert mem.steps() == [2, 3]


def test_memory_tier_peer_rebuild_bit_matches_disk(tmp_path):
    """Acceptance: after a simulated lost host-group, the peer-rebuilt RAM
    restore bit-matches the disk restore of the same step — on a real train
    state (params + ZeRO opt moments), not a toy tree."""
    model, plan, step_fn, get_batch, state = _world()
    for s in range(3):
        state, _ = step_fn(state, get_batch(s))
    disk = CheckpointManager(tmp_path, async_persist=False)
    disk.save(3, state, blocking=True, plan=plan)
    mem = MemoryCheckpointTier(keep=2, groups=4)
    mem.save(3, state, plan=plan)

    template = init_train_state(model, jax.random.PRNGKey(0))
    lost = mem.lose_group(1)
    assert lost > 0
    s_mem, from_mem = mem.restore(template, plan=plan)
    assert mem.last_rebuild > 0            # mirrors actually served shards
    s_disk, from_disk = disk.restore(template)
    assert s_mem == s_disk == 3
    _assert_trees_equal(from_mem.params, from_disk.params)
    _assert_trees_equal(from_mem.opt.mu, from_disk.opt.mu)
    _assert_trees_equal(from_mem.opt.nu, from_disk.opt.nu)


def test_memory_tier_double_loss_unrecoverable():
    tree = {"w": jnp.ones((8, 8), jnp.float32)}
    mem = MemoryCheckpointTier(keep=1, groups=3)
    mem.save(1, tree)
    mem.lose_group(0)                      # primary gone
    mem.lose_group(1)                      # ...and its mirror holder
    with pytest.raises(CorruptCheckpointError, match="lost from memory"):
        mem.restore(tree)


def test_memory_tier_without_redundancy_single_loss_fatal():
    tree = {"w": jnp.ones((8, 8), jnp.float32)}
    mem = MemoryCheckpointTier(keep=1, groups=2, peer_redundancy=False)
    mem.save(1, tree)
    mem.lose_group(0)
    with pytest.raises(CorruptCheckpointError):
        mem.restore(tree)


def test_memory_tier_mirror_is_digest_verified():
    """Rebuilt bytes crossed a (simulated) host loss: a corrupted mirror
    must be detected, never silently restored."""
    tree = {"w": jnp.arange(16, dtype=jnp.float32)}
    mem = MemoryCheckpointTier(keep=1, groups=2)
    mem.save(1, tree)
    mem.lose_group(0)
    for buf in mem._ring[0]["mirror"][1].values():
        buf[...] = 0.0                     # flip the surviving mirror bytes
    with pytest.raises(CorruptCheckpointError, match="digest mismatch"):
        mem.restore(tree)


def test_memory_tier_layout_mismatch_refuses():
    tree = {"w": jnp.ones((8, 8), jnp.float32)}
    mem = MemoryCheckpointTier(keep=1, groups=2)
    mem.save(1, tree, plan=ParallelPlan(cp=1))
    with pytest.raises(ValueError, match="layout mismatch"):
        mem.restore(tree, plan=ParallelPlan(cp=2))


def test_layout_diffs_helper():
    man = {"plan": {"tp": 1, "cp": 2, "dp_shard": 1, "zero_stage": 1,
                    "ep": False, "pp": 1},
           "mesh_axes": {"data": 2, "cp": 2}}
    assert layout_diffs(man, ParallelPlan(cp=2)) == {}
    assert "cp" in layout_diffs(man, ParallelPlan(cp=4))
    assert layout_diffs({"plan": None, "mesh_axes": None},
                        ParallelPlan(cp=4)) == {}


# ---------------------------------------------------------------------------
# Driver integration: memory-tier-first restore, disk fallback


def test_rollback_served_by_memory_tier(tmp_path):
    """A NaN rollback restores from RAM (mem_restores) and the finished run
    bit-matches the fault-free schedule — no disk read on the hot path."""
    model, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    mem = MemoryCheckpointTier(keep=2, groups=2)
    injector = make_injector([FaultSpec("train.step", "nan", step=13)])
    final, report = run_with_recovery(
        state, step_fn, get_batch, N_STEPS, ckpt, _quiet(),
        ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
        policy=RecoveryPolicy(), mem_ckpt=mem)
    assert report.restores == 1
    assert report.mem_restores == 1        # served from RAM, not disk
    assert (13, "nan", "rollback") in report.actions
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)
    _assert_trees_equal(final.opt.mu, ref.opt.mu)


def test_lost_memory_tier_falls_back_to_disk(tmp_path):
    """Both host-groups of the RAM ring die before the anomaly: the tiered
    restore drops to the verified disk walk and still bit-matches.

    ``mem_every=CKPT_EVERY`` so the ring is not repopulated between the
    simulated host loss (step 12) and the NaN (step 13)."""
    model, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    mem = MemoryCheckpointTier(keep=2, groups=2)
    nan_inj = make_injector([FaultSpec("train.step", "nan", step=13)])

    def injector(step, st):
        if step == 12:                     # simulated total host loss
            mem.lose_group(0)
            mem.lose_group(1)
        return nan_inj(step, st)

    final, report = run_with_recovery(
        state, step_fn, get_batch, N_STEPS, ckpt, _quiet(),
        ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
        policy=RecoveryPolicy(), mem_ckpt=mem, mem_every=CKPT_EVERY)
    assert report.restores == 1
    assert report.mem_restores == 0        # RAM couldn't serve: disk did
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)


# ---------------------------------------------------------------------------
# GC keep-floor regression (satellite): a drop_write burst must not evict
# the last restorable checkpoint


def test_gc_spares_newest_intact_under_drop_write_burst(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_persist=False)
    tree = {"w": jnp.arange(32, dtype=jnp.float32)}
    mgr.save(0, tree, blocking=True)
    mgr.save(5, tree, blocking=True)
    with armed([FaultSpec("ckpt.shard_write", "drop_write", step=10),
                FaultSpec("ckpt.shard_write", "drop_write", step=15),
                FaultSpec("ckpt.shard_write", "drop_write", step=20)]):
        mgr.save(10, tree, blocking=True)
        mgr.save(15, tree, blocking=True)
        mgr.save(20, tree, blocking=True)
    # pre-fix GC kept only the newest `keep` (15, 20 — both corrupt) and
    # deleted every restorable checkpoint; the keep-floor spares intact 5
    steps = set(mgr.steps())
    assert 5 in steps, steps
    _, got = mgr.restore({"w": jnp.zeros((32,), jnp.float32)}, step=5)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
    for bad in steps - {5}:
        with pytest.raises(CorruptCheckpointError):
            mgr.restore({"w": jnp.zeros((32,), jnp.float32)}, step=bad)


def test_gc_still_trims_when_newest_is_intact(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2, async_persist=False)
    tree = {"w": jnp.ones((8,), jnp.float32)}
    for s in range(0, 25, 5):
        mgr.save(s, tree, blocking=True)
    assert mgr.steps() == [15, 20]         # healthy runs GC exactly as before


def test_recovery_survives_drop_write_burst_via_keep_floor(tmp_path):
    """Driver-level regression: burst-corrupt the newest checkpoints, then a
    NaN — the fallback walk lands on the GC-spared intact checkpoint and the
    run still bit-matches the fault-free schedule."""
    model, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=2, async_persist=False)
    injector = make_injector([FaultSpec("train.step", "nan", step=17)])
    with armed([FaultSpec("ckpt.shard_write", "drop_write", step=10),
                FaultSpec("ckpt.shard_write", "drop_write", step=15)]):
        final, report = run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt, _quiet(),
            ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
            policy=RecoveryPolicy())
    assert report.restores == 1
    assert report.ckpt_fallbacks == 2      # corrupt 15 and 10 both skipped
    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))
    _assert_trees_equal(final.params, ref.params)


# ---------------------------------------------------------------------------
# Exit discipline (satellite): ckpt.wait() in finally on every exit path


def test_persist_failure_surfaces_on_exception_exit(tmp_path):
    """An async persist failure used to vanish when the loop exited via an
    exception; the finally-fence converts it to a ckpt_io anomaly."""
    _, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=True,
                             io_retries=1, io_backoff=0.01)
    monitor = _quiet()

    def bomb(step, st):
        if step == 7:
            raise RuntimeError("unrelated crash")
        return st

    with armed([FaultSpec("ckpt.persist", "persist_exc", step=5, times=99)]):
        with pytest.raises(RuntimeError, match="unrelated crash"):
            run_with_recovery(
                state, step_fn, get_batch, N_STEPS, ckpt, monitor,
                ckpt_every=CKPT_EVERY, plan=plan, fault_injector=bomb,
                policy=RecoveryPolicy())
    assert any(a.kind == "ckpt_io" for a in monitor.anomalies)


# ---------------------------------------------------------------------------
# Flight recorder


def test_flight_ring_is_bounded(tmp_path):
    fl = FlightRecorder(maxlen=8, path=str(tmp_path / "f.json"))
    for i in range(20):
        fl.record("step", i, loss=float(i))
    assert len(fl.events) == 8
    fl.dump("test")
    d = json.loads((tmp_path / "f.json").read_text())
    assert d["n_events"] == 8
    assert [e["step"] for e in d["events"]] == list(range(12, 20))


def test_flight_dump_sanitizes_nonfinite(tmp_path):
    fl = FlightRecorder(maxlen=8, path=str(tmp_path / "f.json"))
    fl.record("step", 0, loss=float("nan"), grad_norm=float("inf"),
              arr=np.float32(2.5))
    p = fl.dump("test")
    d = json.loads(open(p).read())         # must parse: no bare nan tokens
    e = d["events"][0]
    assert e["loss"] == "nan" and e["grad_norm"] == "inf"
    assert e["arr"] == 2.5


def test_flight_dump_without_path_is_noop():
    fl = FlightRecorder(maxlen=8)
    fl.record("step", 0)
    assert fl.dump("test") is None


def test_recovery_exhausted_leaves_parseable_flight_json(tmp_path):
    """Acceptance: a failure mode that kills the run leaves a flight JSON
    naming the anomaly, the step, and the recovery action taken."""
    _, plan, step_fn, get_batch, state = _world()
    ckpt = CheckpointManager(tmp_path, keep=3, async_persist=False)
    fl = FlightRecorder(maxlen=128, path=str(tmp_path / "flight.json"))
    injector = make_injector(
        [FaultSpec("train.step", "nan", step=13, times=99)])
    with pytest.raises(RecoveryExhausted) as ei:
        run_with_recovery(
            state, step_fn, get_batch, N_STEPS, ckpt, _quiet(),
            ckpt_every=CKPT_EVERY, plan=plan, fault_injector=injector,
            policy=RecoveryPolicy(max_restores=2), flight=fl)
    assert ei.value.flight_path == str(tmp_path / "flight.json")
    d = json.loads((tmp_path / "flight.json").read_text())
    assert d["reason"] == "RecoveryExhausted"
    assert d["extra"]["step"] == 13
    anomalies = [e for e in d["events"] if e["kind"] == "anomaly"]
    policies = [e for e in d["events"] if e["kind"] == "policy"]
    faults = [e for e in d["events"] if e["kind"] == "fault"]
    restores = [e for e in d["events"] if e["kind"] == "restore"]
    assert anomalies and anomalies[0]["anomaly"] == "nan" \
        and anomalies[0]["step"] == 13
    assert policies and policies[0]["action"] == "rollback"
    assert faults and faults[0]["fault_kind"] == "nan"
    assert restores and restores[0]["tier"] == "disk"


def test_flight_logs_gc_and_persist_events(tmp_path):
    fl = FlightRecorder(maxlen=64, path=str(tmp_path / "f.json"))
    mgr = CheckpointManager(tmp_path, keep=1, async_persist=False, flight=fl)
    tree = {"w": jnp.ones((8,), jnp.float32)}
    mgr.save(1, tree, blocking=True)
    mgr.save(2, tree, blocking=True)
    kinds = [e["kind"] for e in fl.events]
    assert kinds.count("ckpt.persist") == 2
    persists = [e for e in fl.events if e["kind"] == "ckpt.persist"]
    assert all(e["tier"] == "disk" for e in persists)
