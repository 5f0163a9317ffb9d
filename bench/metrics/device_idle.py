"""device_idle (%): the share of the traced window in which no operation ran
on the device: 1 - (union of the op intervals) / window, from the profiler
trace of the window."""

from bench import trace as T


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    window = run.t_end - run.t_start
    return 100.0 * (1.0 - T.busy_seconds(run.trace) / window)
