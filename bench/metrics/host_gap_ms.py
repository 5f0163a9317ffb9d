"""host_gap_ms (ms): per step of the window, the loop's wall time (from one
batch fetch to the next) less the step callable's span and less the
checkpoint saves the flight recorder logged for that step; the median over
the window. What is left is the trainer loop's own host work: the fetch,
the monitor, the flight recorder, the straggler timer."""

import statistics


def read(run):
    calls = dict(run.fetch_calls)
    spans = {s: t1 - t0 for s, t0, t1 in run.window_steps()}
    saves = {}
    for ev in run.flight:
        if ev["kind"] == "ckpt.persist":
            sec = ev.get("snapshot_seconds", ev.get("seconds", 0.0)) \
                if ev.get("tier") == "disk" else ev.get("seconds", 0.0)
            saves[ev["step"]] = saves.get(ev["step"], 0.0) + sec
    gaps = [calls[s + 1] - calls[s] - spans[s] - saves.get(s + 1, 0.0)
            for s in spans if s + 1 in calls]
    if not gaps:
        return None
    return 1e3 * statistics.median(gaps)
