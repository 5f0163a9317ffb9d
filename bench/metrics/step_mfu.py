"""step_mfu (%): the configuration's model FLOPs per step (bench/flops.py:
forward plus a backward of twice its FLOPs, no recomputation) over the
median step time in the window, over the chip's bf16 peak. The step time is
the harness's host clock around the step callable the trainer's loop calls,
which ends in block_until_ready: the interval RunReport.step_seconds times."""

import statistics

from bench import flops


def read(run):
    steps = run.window_steps()
    if not steps or run.peaks is None:
        return None
    tr = run.cell.traffic
    f = flops.train_step_flops(run.cell.config, tr["batch"], tr["seq"])
    t = statistics.median(t1 - t0 for _, t0, t1 in steps)
    return 100.0 * f / t / run.peaks["bf16_flops_per_s"]
