"""The trace reduction gives known numbers on a small recorded trace: 48
device ops of zamba2-1.2b.pretrain-4k on a TPU v5e (two slices of one
traced window, around a flash_fwd call and an ssd_bwd call), with the
harness's host spans."""

import json
from pathlib import Path

import pytest

from bench import trace as T

SMALL = json.loads((Path(__file__).parent / "trace_small.json").read_text())


def sweep_busy_ns(ops):
    """Busy time by a sweep over sorted start and end points."""
    points = sorted([(s, 1) for _, s, _ in ops] + [(s + d, -1) for _, s, d in ops],
                    key=lambda p: (p[0], -p[1]))
    busy, depth, since = 0, 0, None
    for t, step in points:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_busy_time_is_the_union_of_ops():
    ops = SMALL["devices"][0]["ops"]
    assert len(ops) == 48
    assert T.busy_seconds(SMALL) == pytest.approx(sweep_busy_ns(ops) * 1e-9,
                                                  abs=1e-12)
    # nested ops: self times add up to the busy time
    assert sum(t for _, t in T.self_times(ops)) * 1e-9 == pytest.approx(
        T.busy_seconds(SMALL), abs=1e-12)


def test_kernel_times_and_breakdown():
    assert [d for _, d in T.kernel_events(SMALL, "flash_fwd")] == [42907323]
    assert [d for _, d in T.kernel_events(SMALL, "ssd_bwd")] == [3959034]
    assert T.kernel_events(SMALL, "ssd_fwd") == []
    top = T.top_ops(SMALL, 2)
    assert [name for name, _ in top] == ["flash_fwd", "ssd_bwd"]
    assert top[0][1] == pytest.approx(0.042907323)


def test_longest_gap_is_between_the_slices_and_named_by_the_host_span():
    ops = SMALL["devices"][0]["ops"]
    early, late = sorted([ops[:16], ops[16:]], key=lambda g: min(o[1] for o in g))
    gap = min(s for _, s, _ in late) - max(s + d for _, s, d in early)
    name, seconds = T.idle_gaps(SMALL, 1)[0]
    assert seconds == pytest.approx(gap * 1e-9)
    assert name == "bench.step"


def test_roofline_share():
    ev = T.kernel_events(SMALL, "flash_fwd")
    assert T.roofline_share([(ev, 0.042907323 / 4)]) == pytest.approx(25.0)
    assert T.roofline_share([([], 1.0)]) is None
