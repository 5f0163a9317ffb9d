"""The readers of the trainer's own spans (the flight recorder's "setup" and
"loop" events) give known numbers on a run written out by hand, on the
events of a run recorded on the chip, and on a tiny run of the trainer on
the CPU; each returns None where it finds nothing to read."""

import json
from pathlib import Path

import pytest

from bench import harness
from bench.tests.tiny import run, tiny_cell, use_test_cache

CELL = "mamba2-370m.pretrain-2k"


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def loop_event(s, **ms):
    return {"kind": "loop", "step": s,
            "seconds": {f"train.{k}": v * 1e-3 for k, v in ms.items()}}


def make_run():
    run_ = harness.RunData(tiny_cell(CELL), t_start=10.0, t_end=13.0,
                           first_step=3, end_step=6)
    run_.flight = [
        {"kind": "ckpt.persist", "tier": "disk", "step": 0, "seconds": 18.0,
         "snapshot_seconds": 6.0, "checksum_seconds": 7.0},
        {"kind": "setup", "step": 0, "seconds": {"train.ckpt": 24.5}},
        loop_event(2, fetch=9.0, step=900.0, readback=9.0),
        # the window's steps 3, 4, 5: 2.5, 2.0 (a save included) and 3.0 ms
        # of the loop's own sections
        loop_event(3, fetch=1.0, step=900.0, readback=1.0, monitor=0.5),
        loop_event(4, fetch=0.5, step=901.0, readback=0.5, ckpt=1.0),
        loop_event(5, fetch=1.0, step=899.0, readback=1.5, straggler=0.5),
        # the step that closed the window, cut short at its fetch
        loop_event(6, fetch=9.0)]
    return run_


def test_loop_host_and_setup_ckpt():
    run_ = make_run()
    assert reader("loop_host_ms")(run_) == pytest.approx(2.5)
    assert reader("setup_ckpt_s")(run_) == pytest.approx(24.5)
    # a program that logs no sections (as before the spans): nothing to read
    run_.flight = run_.flight[:1]
    assert reader("loop_host_ms")(run_) is None
    assert reader("setup_ckpt_s")(run_) is None


def test_readers_on_events_recorded_on_the_chip():
    """The flight events of one untraced run of mamba2-370m.pretrain-2k on a
    TPU v5e: the step-0 save and the loop's sections of each step."""
    small = json.loads((Path(__file__).parent / "flight_small.json")
                       .read_text())
    run_ = harness.RunData(tiny_cell(CELL), first_step=small["first_step"],
                           end_step=small["end_step"])
    run_.flight = small["flight"]
    # 33 window steps; their sections but train.step, in ms, sorted: the
    # median is the 17th
    per_step = sorted(
        1e3 * sum(v for k, v in ev["seconds"].items() if k != "train.step")
        for ev in small["flight"]
        if ev["kind"] == "loop" and 3 <= ev["step"] < 36)
    assert len(per_step) == 33
    assert reader("loop_host_ms")(run_) == pytest.approx(per_step[16])
    assert reader("loop_host_ms")(run_) == pytest.approx(1.8597289998751876)
    assert reader("setup_ckpt_s")(run_) == pytest.approx(24.022047561000022)
    # the disk tier's own event splits the save the loop waited for
    persist = small["flight"][0]
    assert persist["kind"] == "ckpt.persist" and persist["step"] == 0
    assert (persist["checksum_seconds"] < persist["seconds"]
            < reader("setup_ckpt_s")(run_))


def test_tiny_trainer_run_logs_what_the_readers_read(monkeypatch,
                                                     tmp_path_factory):
    """A whole tiny run on the CPU (untraced): the trainer's own flight
    events hold the sections the readers sum."""
    use_test_cache(monkeypatch, str(tmp_path_factory.getbasetemp() / "jax"))
    seen = []

    class Kept(harness.RunData):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.append(self)

    monkeypatch.setattr(harness, "RunData", Kept)
    # the harness's ring holds the chip cell's few dozen steps; a tiny step
    # on the CPU runs a hundred or more in the 1-s window
    import repro.ft

    class Long(repro.ft.FlightRecorder):
        def __init__(self, maxlen=256, path=None):
            super().__init__(maxlen=100_000, path=path)

    monkeypatch.setattr(repro.ft, "FlightRecorder", Long)
    try:
        out = run(CELL)
    finally:
        use_test_cache(None, None)
    assert out["correct"], out["checks"]
    (run_,) = seen
    loop_ms = reader("loop_host_ms")(run_)
    gap_ms = reader("host_gap_ms")(run_)
    assert 0.0 < loop_ms
    # the loop's sections lie between one fetch and the next, outside the
    # step: within a span's entry of the harness's host gap
    assert loop_ms <= gap_ms + 1.0
    persist = [e for e in run_.flight if e["kind"] == "ckpt.persist"]
    assert persist and persist[0]["step"] == 0
    assert 0.0 < persist[0]["checksum_seconds"] < persist[0]["seconds"] \
        <= reader("setup_ckpt_s")(run_)
