"""ram_snapshot_s (s): the RAM tier's own timing of each snapshot it took
after a step of the window (flight recorder, ckpt.persist tier=memory),
median over the window."""

import statistics


def read(run):
    secs = [ev["seconds"] for ev in run.flight
            if ev["kind"] == "ckpt.persist" and ev.get("tier") == "memory"
            and run.first_step < ev["step"] <= run.end_step]
    return statistics.median(secs) if secs else None
