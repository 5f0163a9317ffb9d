"""Preemption-aware graceful shutdown (survey §8, spot/preemptible fleets).

Unit level: the PreemptionGuard handler lifecycle, the grace-budget tier
choice, marker read/write/clear, and a real in-process SIGTERM (os.kill)
through ``run_with_recovery`` — clean exit, PREEMPTED marker, flight dump,
and a ``--resume``-style second run landing bit-identical to the
uninterrupted schedule.

The matrix at the bottom delivers SIGTERM mid-run to a 2×2-mesh run of each
model family (dense, MoE, Mamba2) — once between steps and once with a
double-buffered async snapshot in flight — and asserts the same contract:
clean exit + marker + parseable flight JSON, then a bit-identical resume.
"""

import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, MemoryCheckpointTier
from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.ft import FlightRecorder, Monitor, run_with_recovery
from repro.ft.preempt import (PreemptionGuard, choose_tier, clear_marker,
                              marker_path, read_marker, write_marker)
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step

N_STEPS = 20
CKPT_EVERY = 5
PREEMPT_AT = 13


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
# Guard / tier choice / marker units


def test_guard_installs_and_restores_handlers():
    before = signal.getsignal(signal.SIGTERM)
    with PreemptionGuard(grace=5.0) as g:
        assert signal.getsignal(signal.SIGTERM) == g._handler
        assert not g.requested
    assert signal.getsignal(signal.SIGTERM) == before


def test_guard_real_signal_sets_flag_and_clock():
    with PreemptionGuard(grace=5.0) as g:
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.time() + 2.0
        while not g.requested and time.time() < deadline:
            time.sleep(0.01)
        assert g.requested and g.signum == signal.SIGUSR1
        assert 0.0 < g.remaining() <= 5.0


def test_guard_trigger_without_signal():
    g = PreemptionGuard(grace=9.0, signals=())
    assert g.remaining() == 9.0            # clock idle until the notice
    g.trigger()
    assert g.requested and g.signum == signal.SIGTERM


class _FakeCkpt:
    def __init__(self, snap, d2h, persist):
        self.snapshot_seconds = snap
        self.d2h_seconds = d2h
        self.persist_seconds = persist


def test_choose_tier_prefers_disk_when_it_fits():
    g = PreemptionGuard(grace=30.0, signals=())
    g.trigger()
    mem = object()
    assert choose_tier(g, _FakeCkpt(0.1, 0.1, 0.5), mem) == "disk"
    # measured disk time blows the grace budget -> RAM snapshot
    assert choose_tier(g, _FakeCkpt(10.0, 10.0, 50.0), mem) == "memory"
    # no memory tier: disk is the only option, whatever the estimate
    assert choose_tier(g, _FakeCkpt(10.0, 10.0, 50.0), None) == "disk"
    # nothing measured yet (first checkpoint): no basis to distrust disk
    assert choose_tier(g, _FakeCkpt(0.0, 0.0, 0.0), mem) == "disk"


def test_marker_roundtrip(tmp_path):
    assert read_marker(tmp_path) is None
    write_marker(tmp_path, step=17, tier="disk", signum=15,
                 flight_path="/tmp/f.json")
    mk = read_marker(tmp_path)
    assert mk["step"] == 17 and mk["tier"] == "disk" and mk["signum"] == 15
    assert not marker_path(tmp_path).with_name("PREEMPTED.tmp").exists()
    clear_marker(tmp_path)
    assert read_marker(tmp_path) is None


def test_marker_unreadable_is_none(tmp_path):
    marker_path(tmp_path).write_text("{ not json")
    assert read_marker(tmp_path) is None


# ---------------------------------------------------------------------------
# In-process SIGTERM through the driver: clean exit + marker + bit-identical
# resume (single device; the matrix below covers families on a mesh)


def test_sigterm_mid_run_resumes_bit_identical(tmp_path):
    model, plan, step_fn, get_batch, state0 = _world()

    ref = init_train_state(model, jax.random.PRNGKey(0))
    for s in range(N_STEPS):
        ref, _ = step_fn(ref, get_batch(s))

    flight = FlightRecorder(maxlen=128, path=str(tmp_path / "flight.json"))
    ckpt = CheckpointManager(tmp_path, keep=3, flight=flight)
    mem = MemoryCheckpointTier(keep=2, groups=2, flight=flight)

    def deliver(step, st):
        if step == PREEMPT_AT:
            os.kill(os.getpid(), signal.SIGTERM)
        return st

    with PreemptionGuard(grace=60.0) as guard:
        mid, report = run_with_recovery(
            state0, step_fn, get_batch, N_STEPS, ckpt, _quiet(),
            ckpt_every=CKPT_EVERY, plan=plan, fault_injector=deliver,
            mem_ckpt=mem, preempt=guard, flight=flight)

    assert report.preempted
    # the notice lands mid-step PREEMPT_AT; the driver exits at the next
    # between-steps check, so the snapshot is at PREEMPT_AT + 1
    assert report.preempt_step == PREEMPT_AT + 1
    assert report.steps_done == report.preempt_step < N_STEPS
    mk = read_marker(tmp_path)
    assert mk is not None and mk["step"] == report.preempt_step
    assert mk["tier"] == "disk"            # 60s grace: disk always fits
    assert mk["signum"] == signal.SIGTERM

    # flight black box: parseable, and it names the preemption
    fj = json.loads((tmp_path / "flight.json").read_text())
    assert fj["reason"] == "preempt"
    pe = [e for e in fj["events"] if e["kind"] == "preempt"]
    assert pe and pe[0]["step"] == report.preempt_step

    # resume (fresh process stand-in: new manager, RAM tier gone)
    resumed, report2 = run_with_recovery(
        init_train_state(model, jax.random.PRNGKey(0)), step_fn, get_batch,
        N_STEPS, CheckpointManager(tmp_path, keep=3), _quiet(),
        ckpt_every=CKPT_EVERY, plan=plan, resume=True)
    assert read_marker(tmp_path) is None   # consumed on resume
    assert report2.steps_done == N_STEPS and not report2.preempted
    _assert_trees_equal(resumed.params, ref.params)
    _assert_trees_equal(resumed.opt.mu, ref.opt.mu)


def test_preempt_short_grace_takes_memory_tier(tmp_path):
    """A grace window smaller than the measured disk persist time routes the
    just-in-time snapshot to the RAM tier (the Gemini path: on a fleet the
    peer mirrors survive the host loss)."""
    _, plan, step_fn, get_batch, state0 = _world()
    ckpt = CheckpointManager(tmp_path, keep=3)
    mem = MemoryCheckpointTier(keep=2, groups=2)
    guard = PreemptionGuard(grace=1e-9, signals=())

    def deliver(step, st):
        if step == PREEMPT_AT:
            guard.trigger()
        return st

    _, report = run_with_recovery(
        state0, step_fn, get_batch, N_STEPS, ckpt, _quiet(),
        ckpt_every=CKPT_EVERY, plan=plan, fault_injector=deliver,
        mem_ckpt=mem, preempt=guard)
    assert report.preempted
    mk = read_marker(tmp_path)
    assert mk["tier"] == "memory"
    assert mem.latest_step() == report.preempt_step


# ---------------------------------------------------------------------------
# The preemption matrix (multidevice acceptance): SIGTERM per family on a
# 2×2 mesh, between steps and mid-async-snapshot, then bit-identical resume

_PREEMPT_TEMPLATE = """
import json, os, signal, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager, MemoryCheckpointTier
from repro.core import (Family, InputShape, ModelConfig, MoEConfig, SSMConfig,
                        ParallelPlan, RecoveryPolicy)
from repro.data import SyntheticDataset
from repro.ft import FlightRecorder, Monitor, run_with_recovery
from repro.ft.preempt import PreemptionGuard, read_marker
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step
from repro.launch.mesh import make_mesh

cfg = {cfg}
plan = ParallelPlan(remat="none", compute_dtype="float32", cp=2,
                    zero_stage=1{plan_extra})
mesh = make_mesh((2, 2), ("data", "cp"))
model = build_model(cfg, plan, mesh, ("data",))
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
get_batch = lambda s: {{k: jnp.asarray(v) for k, v in ds.batch(s).items()}}
hyper = Hyper(peak_lr=1e-3, total_steps=40, z_loss=0.0)
N, EVERY, PRE = 20, 5, {preempt_at}
quiet = lambda: Monitor(min_history=1000, hang_min_seconds=60.0)

step_fn = jax.jit(make_train_step(model, plan, hyper, mesh=mesh))
fresh = lambda: init_train_state(model, jax.random.PRNGKey(0),
                                 mesh=mesh, plan=plan)

ref = fresh()
for s in range(N):
    ref, _ = step_fn(ref, get_batch(s))

d = tempfile.mkdtemp()
flight = FlightRecorder(maxlen=256, path=d + "/flight.json")
ckpt = CheckpointManager(d, keep=3, async_snapshot={async_snapshot},
                         flight=flight)
mem = MemoryCheckpointTier(keep=2, groups=4, flight=flight)

def deliver(step, st):
    if step == PRE:
        os.kill(os.getpid(), signal.SIGTERM)
    return st

with PreemptionGuard(grace=120.0) as guard:
    _, report = run_with_recovery(
        fresh(), step_fn, get_batch, N, ckpt, quiet(), ckpt_every=EVERY,
        plan=plan, mesh=mesh, fault_injector=deliver,
        mem_ckpt=mem, preempt=guard, flight=flight)

assert report.preempted and report.preempt_step == PRE + 1, report
mk = read_marker(d)
assert mk is not None and mk["step"] == PRE + 1 and mk["tier"] == "disk", mk
fj = json.load(open(report.flight_path))
assert fj["reason"] == "preempt"
kinds = [e["kind"] for e in fj["events"]]
assert "preempt" in kinds and "step" in kinds, kinds

resumed, r2 = run_with_recovery(
    fresh(), step_fn, get_batch, N, CheckpointManager(d, keep=3), quiet(),
    ckpt_every=EVERY, plan=plan, mesh=mesh, resume=True)
assert read_marker(d) is None
assert r2.steps_done == N and not r2.preempted, r2
for a, b in zip(jax.tree.leaves(resumed.params), jax.tree.leaves(ref.params)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(resumed.opt.mu), jax.tree.leaves(ref.opt.mu)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("preempt matrix OK: clean exit, marker, flight, bit-identical resume")
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


def test_preempt_matrix_dense(multidevice):
    multidevice(_PREEMPT_TEMPLATE.format(
        cfg=_DENSE_CFG, plan_extra="", preempt_at=13,
        async_snapshot="False"), n_devices=4)


def test_preempt_matrix_moe(multidevice):
    multidevice(_PREEMPT_TEMPLATE.format(
        cfg=_MOE_CFG, plan_extra="", preempt_at=13,
        async_snapshot="False"), n_devices=4)


def test_preempt_matrix_mamba2(multidevice):
    multidevice(_PREEMPT_TEMPLATE.format(
        cfg=_SSM_CFG, plan_extra="", preempt_at=13,
        async_snapshot="False"), n_devices=4)


def test_preempt_mid_async_snapshot(multidevice):
    """SIGTERM lands one step after a ckpt_every boundary with
    async_snapshot=True, so the double-buffered snapshot+persist of step 10
    is still in flight when the notice arrives: the driver's preemption
    flush (ckpt.wait) must drain it before the just-in-time snapshot."""
    multidevice(_PREEMPT_TEMPLATE.format(
        cfg=_DENSE_CFG, plan_extra="", preempt_at=10,
        async_snapshot="True"), n_devices=4)
