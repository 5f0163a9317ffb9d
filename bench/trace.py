"""From a profiler trace to numbers: device busy time, kernel time and the
longest idle gaps, each labelled by what the host was doing.

:func:`load` reads the ``.xplane.pb`` the JAX profiler writes and keeps a
small plain form, which the reductions below take and which the tests check
on a recorded trace (``bench/tests/trace_small.json``):

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[op, start_ns, duration_ns], ...]}],
     "host": [[annotation name, start_ns, duration_ns], ...]}

An op is the HLO instruction's name as the trace's "XLA Ops" line gives it
(``ssd_fwd.18``, ``fusion.466``, ``while.56``); a Pallas kernel's is the
``name`` of its ``pallas_call``. A loop's op spans the ops of its body, so
ops nest: busy time takes their union, and op totals take self time.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."          # the harness's own TraceAnnotation spans


def load(log_dir: str) -> Dict:
    """The plain form of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData  # noqa: PLC0415 (loaded on use)
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get(OPS_LINE)
            if line is None:
                continue
            ops = [[ev.name.split(" ", 1)[0].lstrip("%"), int(ev.start_ns),
                    int(ev.duration_ns)] for ev in line.events]
            devices.append({"name": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace["devices"]:
        return 0.0
    tot = 0.0
    for dev in trace["devices"]:
        merged = union([(s, s + d) for _, s, d in dev["ops"]])
        tot += sum(e - s for s, e in merged) * 1e-9
    return tot / len(trace["devices"])


def base(op: str) -> str:
    """An op's name without its instance number: ``ssd_fwd.18`` -> ``ssd_fwd``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def kernel_events(trace: Dict, name: str) -> List[Tuple[int, int]]:
    """(start_ns, duration_ns) of every call of kernel ``name``, on every
    device."""
    return [(s, d) for dev in trace["devices"] for op, s, d in dev["ops"]
            if base(op) == name]


def self_times(ops: Sequence[Sequence]) -> List[Tuple[str, int]]:
    """(op, self time in ns) of each op: its duration less that of the ops
    nested directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [int(op[2]) for op in ops]
    stack: List[int] = []
    for i in order:
        s = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [(ops[i][0], own[i]) for i in range(len(ops))]


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    """The n op names with the most self time, seconds per device."""
    tot: Dict[str, float] = {}
    for dev in trace["devices"]:
        for op, t in self_times(dev["ops"]):
            tot[base(op)] = tot.get(base(op), 0.0) + t * 1e-9
    k = max(1, len(trace["devices"]))
    return [[name, sec / k] for name, sec in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Dict, n: int = 10) -> List[List]:
    """The n longest gaps between ops on the first device, each named by the
    harness span that covers its midpoint ("loop" where none does)."""
    if not trace["devices"]:
        return []
    merged = union([(s, s + d) for _, s, d in trace["devices"][0]["ops"]])
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        label = "loop"
        for name, hs, hd in trace["host"]:
            if hs <= mid < hs + hd:
                label = name
        out.append([label, (e - s) * 1e-9])
    return out


def roofline_share(parts: Sequence[Tuple[Sequence[Tuple[int, int]], float]]
                   ) -> Optional[float]:
    """Percent of the measured device time that the least time of the same
    calls takes. ``parts`` pairs each kernel's events with the least time
    of one of its calls. None where there is no call to read."""
    spent = sum(d for events, _ in parts for _, d in events) * 1e-9
    if spent <= 0.0:
        return None
    least = sum(len(events) * t for events, t in parts)
    return 100.0 * least / spent
