"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload mamba2-370m.pretrain-2k --seed 7 \\
        --seconds 40 --trace 0

One process holds the chip: it sets up the cell (weights and batches from
``--seed``, compilation, warm-up), measures ``--seconds``, checks what the
timed loop computed against the plain reference, and prints one JSON object
as the last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and ``checks``, each number compared
beside its limit (also the last lines of standard error). It exits non-zero
and prints no result when JAX finds no TPU, or fewer chips than the cell
asks for.
"""

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else the runtime logs to /tmp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness  # noqa: PLC0415
    cell = harness.resolve(args.workload)
    try:
        devices = harness.require_chip(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, devices=devices)
    for name, c in out["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(harness.result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
