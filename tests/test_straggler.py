"""Fail-slow defense (survey §8.1): straggler attribution + rebalancing.

Unit level: the ``slow`` fault class (windowed, rank-maskable, replayable),
the cross-rank and own-history detectors (work-share normalization keeps an
intentionally uneven ``pp_layout`` quiet), :func:`choose_pp_layout`'s greedy
min-max re-partition, ``pp_layout`` config validation, the Monitor's
compile-interval discard, the vectorized synthetic-token generator's
bit-identity with the reference loop, the prefetcher, the
KeyboardInterrupt flight dump, and ``check_plan`` routing a ``pp_layout``
change as an elastic reshard.

Multidevice acceptance at the bottom: (a) uneven layouts ((3,1), (1,3))
produce the same loss/grads as even (2,2) and single-device, under both
schedules; (b) the end-to-end ladder — a seeded ``slow`` fault pinned to
one pipeline stage is detected, attributed to (rank, compute), the
``rebalance`` policy re-partitions ``pp_layout`` through a checkpoint
reshard restore, and the run completes on the new layout.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import (Family, InputShape, ModelConfig, ParallelPlan,
                        RecoveryPolicy)
from repro.data import Prefetcher, SyntheticDataset
from repro.ft import (FlightRecorder, Monitor, StragglerDetector,
                      choose_pp_layout, effective_layout, run_with_recovery)
from repro.ft.inject import CONTROLLER, FaultSpec, armed, slow_spec_for
from repro.ft.straggler import SECTION_CLASSES, SECTION_POINTS, StragglerTimer
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step


# ---------------------------------------------------------------------------
# the "slow" fault class


def test_slow_spec_window_and_rank_mask():
    sp = FaultSpec("pp.stage.tick", "slow", step=5, span=3, rank=1,
                   sleep_s=0.01)
    with armed([sp]):
        assert slow_spec_for("pp.stage.tick", 4, rank=1) is None   # before
        assert slow_spec_for("pp.stage.tick", 5, rank=1) is sp
        assert slow_spec_for("pp.stage.tick", 7, rank=1) is sp     # last in
        assert slow_spec_for("pp.stage.tick", 8, rank=1) is None   # after
        assert slow_spec_for("pp.stage.tick", 6, rank=0) is None   # masked
        assert slow_spec_for("data.fetch", 6, rank=1) is None      # point
    assert ("pp.stage.tick", "slow", 5) in CONTROLLER.fired


def test_slow_spec_unmasked_hits_every_rank():
    sp = FaultSpec("cp.ring.kv", "slow", step=0, span=1000, sleep_s=0.01)
    with armed([sp]):
        assert slow_spec_for("cp.ring.kv", 3, rank=0) is sp
        assert slow_spec_for("cp.ring.kv", 3, rank=7) is sp
        assert slow_spec_for("cp.ring.kv", 3, rank=None) is sp


def test_slow_spec_validates():
    with pytest.raises(ValueError, match="span"):
        FaultSpec("train.step", "slow", span=0)
    with pytest.raises(ValueError, match="unknown fault point"):
        slow_spec_for("no.such.point", 0)


def test_section_tables_agree():
    assert set(SECTION_POINTS) == set(SECTION_CLASSES)
    from repro.ft.inject import FAULT_POINTS
    for pts in SECTION_POINTS.values():
        for p in pts:
            assert p in FAULT_POINTS, p


# ---------------------------------------------------------------------------
# detector units


def test_detector_cross_rank_confirm_latency():
    det = StragglerDetector(factor=2.0, confirm=3, min_seconds=1e-3)
    for step in range(5):
        shares = {0: 0.01, 1: 0.01, 2: 0.01, 3: 0.05}
        ev = det.observe_group("pp.stage", step, shares)
        if step < 2:
            assert ev is None, step       # streak still building
        elif step == 2:
            assert ev is not None         # confirm=3 -> third slow step
            assert ev.rank == 3 and ev.section == "pp.stage"
            assert ev.cls == "compute" and ev.slowdown > 2.0


def test_detector_streak_resets_on_healthy_sample():
    det = StragglerDetector(factor=2.0, confirm=3, min_seconds=1e-3)
    slow = {0: 0.01, 1: 0.05}
    ok = {0: 0.01, 1: 0.01}
    assert det.observe_group("tp.ring", 0, slow) is None
    assert det.observe_group("tp.ring", 1, slow) is None
    assert det.observe_group("tp.ring", 2, ok) is None     # streak broken
    assert det.observe_group("tp.ring", 3, slow) is None
    assert det.observe_group("tp.ring", 4, slow) is None
    assert det.observe_group("tp.ring", 5, slow) is not None


def test_detector_work_share_normalization_uneven_layout_quiet():
    """An intentionally uneven pp_layout must not read as a straggler."""
    det = StragglerDetector(factor=2.0, confirm=1, min_seconds=1e-3)
    layout = (3, 1)
    weights = {0: 3.0, 1: 1.0}
    for step in range(6):
        # stage 0 takes 3x stage 1's time — exactly its work share
        ev = det.observe_group("pp.stage", step, {0: 0.03, 1: 0.01},
                               weights=weights)
        assert ev is None, (step, ev)
    # the same raw times WITHOUT weights would fire immediately
    det2 = StragglerDetector(factor=2.0, confirm=1, min_seconds=1e-3)
    assert det2.observe_group("pp.stage", 0, {0: 0.03, 1: 0.01}) is not None
    # and a degraded rank fires even under normalization: slow per layer
    ev = det.observe_group("pp.stage", 9, {0: 0.03, 1: 0.025},
                           weights=weights)
    assert ev is not None and ev.rank == 1


def test_detector_own_history_and_grace():
    det = StragglerDetector(factor=2.0, confirm=1, min_seconds=1e-3,
                            min_history=3)
    # step 0 is the compile step: a huge time must be discarded, not learned
    assert det.observe("step.compute", None, 10.0, 0) is None
    for s in range(1, 5):
        assert det.observe("step.compute", None, 0.01, s) is None
    ev = det.observe("step.compute", None, 0.05, 5)
    assert ev is not None and ev.cls == "compute" and ev.rank is None
    det.reset()
    # post-reset grace re-arms: the next observation is discarded again
    assert det.observe("step.compute", None, 10.0, 6) is None
    assert ("step.compute", None) not in det._hist


def test_detector_recent_reflects_degraded_regime():
    det = StragglerDetector(window=16, confirm=3)
    for s in range(10):
        det.observe_group("pp.stage", s, {0: 0.01, 1: 0.01})
    for s in range(10, 13):
        det.observe_group("pp.stage", s, {0: 0.01, 1: 0.07})
    recent = det.recent("pp.stage")
    assert recent[1] == pytest.approx(0.07)   # degraded values, not the
    assert recent[0] == pytest.approx(0.01)   # healthy full-window median


# ---------------------------------------------------------------------------
# choose_pp_layout


def test_choose_pp_layout_sheds_from_slow_stage():
    # stage 1 is 2x slower per layer -> it gives up a layer
    assert choose_pp_layout({0: 1.0, 1: 2.0}, (2, 2)) == (3, 1)
    assert choose_pp_layout({0: 2.0, 1: 1.0}, (2, 2)) == (1, 3)


def test_choose_pp_layout_balanced_is_identity():
    assert choose_pp_layout({0: 1.0, 1: 1.0}, (2, 2)) == (2, 2)
    # (3,1) with stage 1 paying 3x per layer: keeping the skew IS optimal
    assert choose_pp_layout({0: 3.0, 1: 3.0}, (3, 1)) == (3, 1)
    # equal per-layer costs under a skewed layout: evening out wins
    assert choose_pp_layout({0: 3.0, 1: 1.0}, (3, 1)) == (2, 2)
    assert choose_pp_layout({}, (2, 2)) == (2, 2)


def test_choose_pp_layout_one_layer_floor():
    # however degraded, every stage keeps >= 1 layer
    out = choose_pp_layout({0: 1.0, 1: 1000.0}, (4, 4))
    assert out == (7, 1)
    assert sum(out) == 8 and min(out) >= 1


def test_effective_layout():
    cfg = ModelConfig("t", Family.DENSE, n_layers=4, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=64)
    assert effective_layout(ParallelPlan(), cfg) is None            # no pp
    assert effective_layout(ParallelPlan(pp=2, microbatches=2), cfg) == (2, 2)
    p = ParallelPlan(pp=2, microbatches=2, pp_layout=(3, 1))
    assert effective_layout(p) == (3, 1)                            # no cfg
    assert effective_layout(None) is None


def test_pp_layout_config_validation():
    cfg = ModelConfig("t", Family.DENSE, n_layers=4, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=64)
    ParallelPlan(pp=2, microbatches=2, pp_layout=(3, 1)).validate(cfg)
    with pytest.raises(ValueError, match="pp_layout"):
        ParallelPlan(pp_layout=(4,)).validate(cfg)          # needs pp > 1
    with pytest.raises(ValueError, match="pp_layout"):
        ParallelPlan(pp=2, microbatches=2, pp_layout=(4,)).validate(cfg)
    with pytest.raises(ValueError, match="pp_layout"):
        ParallelPlan(pp=2, microbatches=2, pp_layout=(4, 0)).validate(cfg)
    with pytest.raises(ValueError, match="pp_layout"):
        ParallelPlan(pp=2, microbatches=2, pp_layout=(2, 3)).validate(cfg)
    # odd split without an explicit layout still refuses
    cfg5 = dataclasses.replace(cfg, n_layers=5)
    with pytest.raises(ValueError, match="pp_layout"):
        ParallelPlan(pp=2, microbatches=2).validate(cfg5)
    # lists normalize to tuples (hashable; JSON round-trip comparable)
    assert ParallelPlan(pp=2, microbatches=2, pp_layout=[3, 1]).pp_layout \
        == (3, 1)


# ---------------------------------------------------------------------------
# Monitor: compile interval must not poison the wall-time window


def test_monitor_discards_first_interval():
    mon = Monitor(min_history=2, hang_factor=4.0, hang_min_seconds=1e-3)
    t = 100.0
    mon.record(0, 1.0, 1.0, now=t)            # arms the heartbeat
    mon.record(1, 1.0, 1.0, now=t + 10.0)     # the 10s JIT-compile interval
    assert 10.0 not in mon.times              # discarded, not learned
    mon.record(2, 1.0, 1.0, now=t + 10.1)
    mon.record(3, 1.0, 1.0, now=t + 10.2)
    out = mon.record(4, 1.0, 1.0, now=t + 10.7)   # 0.5s vs 0.1s median
    assert out is not None and out.kind == "hang"


def test_monitor_without_discard_would_mask():
    """The regression shape: with the compile interval in the window the
    median is poisoned and the same slowdown passes silently."""
    mon = Monitor(min_history=2, hang_factor=4.0, hang_min_seconds=1e-3)
    mon._skip_next_interval = False           # simulate the old behaviour
    t = 100.0
    mon.record(0, 1.0, 1.0, now=t)
    mon.record(1, 1.0, 1.0, now=t + 10.0)     # compile spike enters times
    mon.record(2, 1.0, 1.0, now=t + 10.1)
    out = mon.record(3, 1.0, 1.0, now=t + 10.6)
    assert out is None                        # masked by the poisoned median
    assert 10.0 in mon.times


def test_monitor_reset_rearms_discard():
    mon = Monitor(min_history=2, hang_min_seconds=1e-3)
    t = 50.0
    mon.record(0, 1.0, 1.0, now=t)
    mon.record(1, 1.0, 1.0, now=t + 0.1)      # first interval: discarded
    mon.record(2, 1.0, 1.0, now=t + 0.2)
    assert len(mon.times) == 1
    mon.reset_heartbeat(now=t + 5.0)          # e.g. after a restore
    mon.record(3, 1.0, 1.0, now=t + 15.0)     # re-JIT interval: discarded
    assert len(mon.times) == 1


# ---------------------------------------------------------------------------
# StragglerTimer: sections, modeled shares, armed slow delays


def test_timer_section_times_and_attributes_host_io():
    det = StragglerDetector(factor=2.0, confirm=1, min_seconds=1e-3,
                            min_history=2)
    timer = StragglerTimer(detector=det)
    for s in range(4):
        with timer.section("data.fetch", s):
            pass
    with armed([FaultSpec("data.fetch", "slow", step=4, span=2,
                          sleep_s=0.02)]):
        with timer.section("data.fetch", 4):
            pass
    ev = timer.after_step(4, 0.001)
    assert ev is not None and ev.section == "data.fetch"
    assert ev.cls == "host-io"


def test_timer_models_stage_shares_and_sleeps_per_layer():
    cfg = ModelConfig("t", Family.DENSE, n_layers=4, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=64)
    plan = ParallelPlan(pp=2, microbatches=2)
    det = StragglerDetector(factor=2.0, confirm=2, min_seconds=1e-3)
    timer = StragglerTimer(cfg=cfg, plan=plan, detector=det)
    with armed([FaultSpec("pp.stage.tick", "slow", step=0, span=100, rank=1,
                          sleep_s=0.01)]):
        assert timer.after_step(0, 0.004) is None     # streak 1 of 2
        ev = timer.after_step(1, 0.004)
    assert ev is not None and ev.rank == 1 and ev.section == "pp.stage"
    assert ev.cls == "compute"
    # the degraded stage's recent time includes the injected delay
    # (2 layers x 0.01s), so the rebalancer plans against reality
    times = timer.stage_times()
    assert times[1] > times[0]
    assert choose_pp_layout(times, (2, 2)) == (3, 1)


def test_timer_ring_attribution():
    plan = ParallelPlan(cp=2)
    det = StragglerDetector(factor=2.0, confirm=2, min_seconds=1e-3)
    timer = StragglerTimer(plan=plan, detector=det)
    with armed([FaultSpec("cp.ring.kv", "slow", step=0, span=100, rank=1,
                          sleep_s=0.02)]):
        timer.after_step(0, 0.004)
        ev = timer.after_step(1, 0.004)
    assert ev is not None and ev.rank == 1
    assert ev.section == "cp.ring" and ev.cls == "comm"


# ---------------------------------------------------------------------------
# data pipeline: vectorized generator bit-identity + prefetcher


def test_tokens_vectorized_bit_identical_to_loop():
    cfg = ModelConfig("t", Family.DENSE, n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=128)
    for b, s in [(4, 16), (8, 1), (3, 2), (1, 33)]:
        ds = SyntheticDataset(cfg, InputShape("t", s, b, "train"), seed=3)
        for step in range(3):
            r1 = np.random.default_rng((3, step))
            r2 = np.random.default_rng((3, step))
            np.testing.assert_array_equal(ds._tokens(r1, b, s),
                                          ds._tokens_loop(r2, b, s))
            # the generator state must match too, or downstream draws
            # (AUDIO frames, VLM embeds) would diverge
            assert r1.bit_generator.state == r2.bit_generator.state


def test_prefetcher_identical_including_random_access():
    cfg = ModelConfig("t", Family.DENSE, n_layers=2, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=128)
    ds = SyntheticDataset(cfg, InputShape("t", 16, 4, "train"), seed=1)
    with Prefetcher(ds) as pf:
        # sequential, a forward jump, and a rollback-style backward jump
        for step in [0, 1, 2, 7, 3, 4, 4]:
            got, want = pf.batch(step), ds.batch(step)
            assert set(got) == set(want)
            for k in want:
                np.testing.assert_array_equal(got[k], want[k])


# ---------------------------------------------------------------------------
# KeyboardInterrupt dumps the flight recorder (satellite regression)


def test_keyboard_interrupt_dumps_flight(tmp_path):
    cfg = ModelConfig("tiny-d", Family.DENSE, n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    plan = ParallelPlan(remat="none", compute_dtype="float32")
    model = build_model(cfg, plan)
    ds = SyntheticDataset(cfg, InputShape("t", 16, 4, "train"))
    get_batch = lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()}
    step_fn = jax.jit(make_train_step(model, plan, Hyper(total_steps=30)))
    state = init_train_state(model, jax.random.PRNGKey(0))
    flight = FlightRecorder(maxlen=64, path=str(tmp_path / "flight.json"))

    def injector(step, st):
        if step == 3:
            raise KeyboardInterrupt
        return st

    ckpt = CheckpointManager(str(tmp_path / "ck"), async_persist=False)
    with pytest.raises(KeyboardInterrupt) as ei:
        run_with_recovery(state, step_fn, get_batch, 8, ckpt,
                          Monitor(), ckpt_every=4, fault_injector=injector,
                          flight=flight)
    fp = getattr(ei.value, "flight_path", None)
    assert fp is not None and (tmp_path / "flight.json").exists()
    import json
    payload = json.loads((tmp_path / "flight.json").read_text())
    assert payload["reason"] == "KeyboardInterrupt"
    assert any(e["kind"] == "step" for e in payload["events"])


# ---------------------------------------------------------------------------
# checkpoint: a pp_layout change is a layout change -> elastic reshard


def test_check_plan_routes_pp_layout_change_as_reshard(tmp_path):
    cfg = ModelConfig("tiny-d", Family.DENSE, n_layers=4, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    plan = ParallelPlan(remat="none", compute_dtype="float32")
    model = build_model(cfg, plan)
    state = init_train_state(model, jax.random.PRNGKey(0))
    even = ParallelPlan(pp=2, microbatches=2, pp_layout=(2, 2))
    ckpt = CheckpointManager(str(tmp_path), async_persist=False)
    ckpt.save(0, state, blocking=True, plan=even)
    same = ParallelPlan(pp=2, microbatches=2, pp_layout=(2, 2))
    assert ckpt.check_plan(same, step=0) == "replay"
    skew = ParallelPlan(pp=2, microbatches=2, pp_layout=(3, 1))
    assert ckpt.check_plan(skew, step=0, elastic=True) == "reshard"
    with pytest.raises(ValueError, match="pp_layout"):
        ckpt.check_plan(skew, step=0, elastic=False)
    # None (implicit even) vs an explicit layout is also a relayout
    none_lay = ParallelPlan(pp=2, microbatches=2)
    assert ckpt.check_plan(none_lay, step=0, elastic=True) == "reshard"


# ---------------------------------------------------------------------------
# multidevice acceptance


def test_uneven_pp_layout_matches_even_and_single(multidevice):
    """(3,1) == (1,3) == (2,2) == non-pipelined, both schedules, fwd+grad."""
    multidevice("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.models import build_model
from repro.train import Hyper, make_loss_fn
from repro.train.pipeline import pipelined_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=4, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128)
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}

model = build_model(cfg, ParallelPlan(remat="none", compute_dtype="float32"))
params = model.init(jax.random.PRNGKey(0))
ref_loss, _ = make_loss_fn(model, Hyper(z_loss=0.0))(params, batch)
ref_g = jax.grad(lambda p, b: make_loss_fn(model, Hyper(z_loss=0.0))(p, b)[0]
                 )(params, batch)

mesh = make_mesh((2, 2), ("pod", "data"))
base = ParallelPlan(remat="none", compute_dtype="float32", pp=2,
                    microbatches=4)
for layout in [(2, 2), (3, 1), (1, 3)]:
    for sched in ["1f1b", "gpipe"]:
        pl = dataclasses.replace(base, pp_layout=layout, pp_schedule=sched)
        lf = pipelined_loss_fn(cfg, pl, mesh, ("data",))
        loss, _ = jax.jit(lf)(params, batch)
        assert abs(float(loss) - float(ref_loss)) < 1e-6, (
            layout, sched, float(loss), float(ref_loss))
        g = jax.grad(lambda p, b: lf(p, b)[0])(params, batch)
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-5)
        print(layout, sched, "OK", float(loss))
print("uneven pp_layout equivalence OK")
""", n_devices=4)


def test_straggler_rebalance_end_to_end(multidevice):
    """The whole ladder: seeded slow fault on stage 1 -> detected within the
    confirm window, attributed (rank=1, compute) -> policy rebalances
    pp_layout via a checkpoint reshard restore -> run completes."""
    multidevice("""
import dataclasses, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.checkpoint import CheckpointManager
from repro.core import (Family, InputShape, ModelConfig, ParallelPlan,
                        RecoveryPolicy)
from repro.data import SyntheticDataset
from repro.ft import (Monitor, RemeshSpec, StragglerDetector, StragglerTimer,
                      run_with_recovery)
from repro.ft.inject import FaultSpec, armed
from repro.models import build_model
from repro.train.pipeline import pipelined_loss_fn
from repro.launch.mesh import make_mesh

cfg = ModelConfig("tiny", Family.DENSE, n_layers=4, d_model=64, n_heads=4,
                  n_kv_heads=2, d_ff=128, vocab=128)
mesh = make_mesh((2, 2), ("pod", "data"))
plan = ParallelPlan(remat="none", compute_dtype="float32", pp=2,
                    microbatches=4)
ds = SyntheticDataset(cfg, InputShape("t", 16, 8, "train"))
get_batch = lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()}

model = build_model(cfg, ParallelPlan(remat="none", compute_dtype="float32"))
params0 = model.init(jax.random.PRNGKey(0))

def make_step(pl):
    lf = pipelined_loss_fn(cfg, pl, mesh, ("data",))
    def step(state, batch):
        loss, grads = jax.value_and_grad(
            lambda p, b: lf(p, b)[0])(state["params"], batch)
        params = jax.tree.map(lambda p, g: p - 1e-3 * g,
                              state["params"], grads)
        gn = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                          for g in jax.tree.leaves(grads)))
        return {"params": params}, {"loss": loss, "grad_norm": gn}
    return jax.jit(step)

state0 = {"params": params0}
N = 16
detector = StragglerDetector(window=8, factor=2.0, confirm=3,
                             min_seconds=1e-3)
timer = StragglerTimer(cfg=cfg, plan=plan, detector=detector)
policy = RecoveryPolicy(straggler="rebalance", max_restores=4,
                        straggler_confirm=3)
monitor = Monitor(hang_min_seconds=60.0)   # the straggler ladder owns this

applied = []
def rebalance(layout):
    applied.append(tuple(layout))
    pl2 = dataclasses.replace(plan, pp_layout=tuple(layout))
    return RemeshSpec(train_step=make_step(pl2), state_template=state0,
                      plan=pl2, mesh=mesh)

ckpt = CheckpointManager(tempfile.mkdtemp(), keep=4, async_persist=False)
# stage 1 degrades from step 6 on: 50ms of extra host time per layer held
with armed([FaultSpec("pp.stage.tick", "slow", step=6, span=999, rank=1,
                      sleep_s=0.05)]):
    final, report = run_with_recovery(
        state0, make_step(plan), get_batch, N, ckpt, monitor,
        ckpt_every=3, plan=plan, mesh=mesh, policy=policy,
        straggler=timer, rebalance=rebalance)

assert report.steps_done == N, report
assert report.rebalances == 1, report
assert applied and applied[0] == (3, 1), applied     # stage 1 shed a layer
strag = [a for a in report.anomalies if a.kind == "straggler"]
assert strag, report.anomalies
# detected within the confirm window of the fault landing
assert strag[0].step <= 6 + 3, strag[0]
assert "rank=1" in strag[0].detail and "class=compute" in strag[0].detail
assert any(k == "straggler" and act == "rebalance"
           for _, k, act in report.actions), report.actions
# the reshard restore rode the elastic checkpoint path (old layout on disk)
assert report.restores >= 1, report
# a re-attribution of the already-rebalanced rank must not loop the ladder
assert report.rebalances == 1
assert all(np.isfinite(l) for l in report.losses[-3:])
print("straggler rebalance e2e OK:", applied[0], "losses fine")
""", n_devices=4)
