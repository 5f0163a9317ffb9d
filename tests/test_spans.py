"""The trainer's spans: every section of the recovery loop is a
``train.<section>`` span on the profiler's clock, whose seconds land in one
flight event per step; the checkpoint tiers' parts are ``ckpt.*``/``mem.*``
spans; and the train step's HLO carries the name scopes a device trace is
split by."""

import glob
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import CheckpointManager, MemoryCheckpointTier
from repro.core import Family, ModelConfig, ParallelPlan
from repro.core.config import SSMConfig
from repro.ft import FlightRecorder, Monitor, StragglerTimer, run_with_recovery
from repro.models import build_model
from repro.train import Hyper, init_train_state, make_train_step

N = 3
# the spans of one step, in the order the loop runs them (every section
# present: an injector, a straggler timer, a disk save and a RAM snapshot
# after every step)
LOOP_ORDER = ["train.inject", "train.fetch", "train.step", "train.readback",
              "train.monitor", "train.straggler", "train.ckpt",
              "train.mem_ckpt"]


def _toy_step(state, batch):
    new = {"w": state["w"] - 0.01 * jnp.mean(batch["x"])}
    return new, {"loss": jnp.float32(1.0), "grad_norm": jnp.sum(new["w"])}


def _run_loop(tmp_path, flight):
    step_fn = jax.jit(_toy_step)
    get_batch = lambda s: {"x": jnp.full((4, 4), float(s))}
    state = {"w": jnp.ones((8, 8))}
    jax.block_until_ready(step_fn(state, get_batch(0)))     # compile first
    ckpt = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    t0 = time.perf_counter()
    _, report = run_with_recovery(
        state, step_fn, get_batch, N, ckpt,
        Monitor(hang_min_seconds=60.0), ckpt_every=1,
        fault_injector=lambda s, st: st, straggler=StragglerTimer(),
        mem_ckpt=MemoryCheckpointTier(keep=1), flight=flight)
    return report, time.perf_counter() - t0


def test_one_loop_event_per_step_with_its_section_seconds(tmp_path):
    flight = FlightRecorder()
    report, wall = _run_loop(tmp_path, flight)
    setup = [e for e in flight.events if e["kind"] == "setup"]
    loop = [e for e in flight.events if e["kind"] == "loop"]
    assert [e["step"] for e in setup] == [0]
    assert sorted(setup[0]["seconds"]) == ["train.ckpt", "train.mem_ckpt"]
    assert [e["step"] for e in loop] == list(range(N))
    for e in loop:
        assert sorted(e["seconds"]) == sorted(LOOP_ORDER)
    # the train.step spans are the report's step times
    assert [e["seconds"]["train.step"] for e in loop] == report.step_seconds
    # each step's sections fit between its event and the one before it, and
    # all of them inside the loop's wall time
    prev = setup[0]["t"]
    for e in loop:
        assert 0.0 < sum(e["seconds"].values()) <= e["t"] - prev
        prev = e["t"]
    assert sum(sum(e["seconds"].values()) for e in setup + loop) <= wall
    # the tiers report the parts of each save
    disk = [e for e in flight.events
            if e["kind"] == "ckpt.persist" and e["tier"] == "disk"]
    ram = [e for e in flight.events
           if e["kind"] == "ckpt.persist" and e["tier"] == "memory"]
    assert len(disk) == len(ram) == N + 1
    for e in disk:
        assert 0.0 <= e["checksum_seconds"] <= e["seconds"]
    for e in ram:
        parts = e["copy_seconds"] + e["checksum_seconds"] + e["mirror_seconds"]
        assert 0.0 <= parts <= e["seconds"]


def test_profiler_trace_holds_the_spans_in_loop_order(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        _run_loop(tmp_path, FlightRecorder())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                        recursive=True)
    events = [(ev.start_ns, ev.name, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.split(".")[0] in ("train", "ckpt", "mem")]
    events.sort(key=lambda e: e[0])
    loop = [(name, st["step"]) for _, name, st in events
            if name.startswith("train.")]
    # the step-0 save, then every section of each step once, in loop order;
    # a step's saves are of the step it leads to
    want = [("train.ckpt", 0), ("train.mem_ckpt", 0)]
    for s in range(N):
        want += [(name, s + 1 if name in ("train.ckpt", "train.mem_ckpt")
                  else s) for name in LOOP_ORDER]
    assert loop == want
    tiers = [name for _, name, _ in events if not name.startswith("train.")]
    for name in ("ckpt.snapshot", "ckpt.checksum", "ckpt.write", "mem.copy",
                 "mem.checksum", "mem.mirror"):
        assert tiers.count(name) == N + 1, name


def test_ram_restore_reports_its_parts():
    flight = FlightRecorder()
    tier = MemoryCheckpointTier(keep=1, flight=flight)
    state = {"a": jnp.arange(6.0), "b": jnp.ones((3, 4))}
    tier.save(5, state)
    step, got = tier.restore(state)
    assert step == 5
    assert jnp.array_equal(got["b"], state["b"])
    (ev,) = [e for e in flight.events if e["kind"] == "mem.restore"]
    assert 0.0 < ev["fetch_seconds"] + ev["put_seconds"] <= ev["seconds"]


@pytest.fixture(scope="module")
def step_text():
    """The lowered train step of a tiny Mamba-2 (SSD as the Pallas kernel,
    full remat), as text with its locations."""
    cfg = ModelConfig("tiny-ssm", Family.SSM, n_layers=2, d_model=32,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab=64,
                      ssm=SSMConfig(d_state=8, head_dim=16, chunk=16))
    plan = ParallelPlan(remat="full", compute_dtype="float32",
                        ssm_impl="pallas")
    model = build_model(cfg, plan)
    state = init_train_state(model, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    step = jax.jit(make_train_step(model, plan, Hyper(total_steps=10)))
    return step.lower(state, {"tokens": tokens, "labels": tokens}).as_text(
        debug_info=True)


@pytest.mark.parametrize("scope", ["loss", "clip", "optimizer", "embed",
                                   "norm", "mixer", "head", "kernel_layout"])
def test_train_step_hlo_carries_the_name_scopes(step_text, scope):
    # a scope is one part of an op's name stack: "loss/...", or wrapped by a
    # transformation, as in "jvp(embed)/..." and "transpose(jvp(head))/..."
    assert re.search(rf"[\"/(]{scope}[/)]", step_text), scope
