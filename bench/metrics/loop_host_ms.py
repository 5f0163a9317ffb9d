"""loop_host_ms (ms): per step of the window, the host seconds of the
trainer loop's own sections, from the step's "loop" flight event
(ft/recovery.py): every span there but ``train.step``, that is the batch
fetch, the metrics' readback, the monitor, the straggler timer, and any save
or fault injection; the median over the window. ``host_gap_ms`` less this
is the part of ``train.step`` outside the harness's own step span and the
loop work that no span covers."""

import statistics


def read(run):
    per_step = [1e3 * (sum(ev["seconds"].values())
                       - ev["seconds"].get("train.step", 0.0))
                for ev in run.flight
                if ev["kind"] == "loop"
                and run.first_step <= ev["step"] < run.end_step]
    return statistics.median(per_step) if per_step else None
