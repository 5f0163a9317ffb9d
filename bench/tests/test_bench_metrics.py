"""The per-layer readers give known numbers on a run whose spans, flight
events and trace are written out by hand; each returns None where it finds
nothing to read."""

import pytest

from bench import flops, harness
from bench.tests.tiny import full_cell, tiny_cell

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def make_run(cell="mamba2-370m.pretrain-2k", full=False):
    run = harness.RunData(full_cell(cell) if full else tiny_cell(cell),
                          t_start=10.0,
                          t_end=13.0, first_step=3, end_step=6,
                          peaks=PEAKS)
    # steps 3, 4, 5 in the window; each loop turn 1.0 s, the step 0.9 s,
    # and after step 4 a RAM snapshot of 0.05 s
    run.fetch_calls = [(3, 10.0), (4, 11.0), (5, 12.0), (6, 13.0)]
    run.step_spans = [(2, 9.0, 9.9), (3, 10.0, 10.9), (4, 11.0, 11.9),
                      (5, 12.0, 12.9)]
    run.flight = [{"kind": "ckpt.persist", "tier": "memory", "step": 5,
                   "seconds": 0.05},
                  {"kind": "ckpt.persist", "tier": "memory", "step": 3,
                   "seconds": 9.0},
                  {"kind": "mem.restore", "step": 6, "seconds": 2.5}]
    return run


def test_host_gap_and_tier_timings():
    run = make_run()
    # gaps: step 3 0.1 s, step 4 0.1 - 0.05 s, step 5 0.1 s -> median 0.1 s
    assert reader("host_gap_ms")(run) == pytest.approx(100.0)
    # only the snapshot taken after a window step counts
    assert reader("ram_snapshot_s")(run) == pytest.approx(0.05)
    assert reader("mem_restore_s")(run) == pytest.approx(2.5)
    run.flight = []
    assert reader("ram_snapshot_s")(run) is None
    assert reader("mem_restore_s")(run) is None


def test_step_mfu():
    run = make_run()
    tr = run.cell.traffic
    want = 100 * flops.train_step_flops(run.cell.config, tr["batch"],
                                        tr["seq"]) / 0.9 / 197e12
    assert reader("step_mfu")(run) == pytest.approx(want)


def test_trace_readers():
    run = make_run("zamba2-1.2b.pretrain-4k", full=True)
    assert reader("device_idle")(run) is None
    costs = flops.kernel_costs(run.cell.config, run.cell.traffic["batch"],
                               run.cell.traffic["seq"])
    least = {k: max(f / 197e12, b / 819e9) for k, (f, b) in costs.items()}
    ns = lambda s: int(round(s * 1e9))
    # a 1-s op, five kernel calls 0.1 s apart, each taking ten times its
    # least time at the cell's shapes (under 0.1 s), and a 0.1-s copy
    ops = [["fusion.1", 0, ns(1.0)],
           ["ssd_fwd.2 ssd_fwd", ns(1.5), ns(10 * least["ssd_fwd"])],
           ["ssd_bwd", ns(1.6), ns(10 * least["ssd_bwd"])],
           ["flash_fwd.3", ns(1.7), ns(10 * least["flash_fwd"])],
           ["custom-call.4 flash_dkv", ns(1.8), ns(10 * least["flash_dkv"])],
           ["flash_dq", ns(1.9), ns(10 * least["flash_dq"])],
           ["copy.5", ns(2.0), ns(0.1)]]
    run.trace = {"devices": [{"name": "/device:TPU:0", "ops": ops}],
                 "host": [["bench.step", 0, ns(1.2)]]}
    busy = 1.0 + 0.1 + sum(10 * t for t in least.values())
    assert reader("device_idle")(run) == pytest.approx(100 * (1 - busy / 3),
                                                       rel=1e-6)
    assert reader("ssd_roofline")(run) == pytest.approx(10.0)
    assert reader("attn_roofline")(run) == pytest.approx(10.0)
    run.trace["devices"][0]["ops"] = ops[:1]
    assert reader("ssd_roofline")(run) is None
