"""attn_roofline (%): the least time of every flash_fwd, flash_dq and
flash_dkv call in the traced window (the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth, bench/flops.py) over their summed
device time."""

from bench import flops
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    tr, pk = run.cell.traffic, run.peaks
    parts = []
    for name, (f, b) in flops.kernel_costs(run.cell.config, tr["batch"],
                                           tr["seq"]).items():
        if name.startswith("flash_"):
            least = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
            parts.append((T.kernel_events(run.trace, name), least))
    return T.roofline_share(parts)
