"""Operations and bytes from shapes: the model's FLOPs per training step and
each kernel's FLOPs and bytes per call.

Counts are of the work the algorithm needs, whatever implements it:

- a matrix product of (m, k) by (k, n) is 2mkn FLOPs;
- causal attention and the SSD's intra-chunk products count the pairs
  (i, j) with j <= i, not the full square an implementation may compute;
- a training step is the forward pass and a backward pass of twice its
  FLOPs; recomputation (remat) is not counted;
- a kernel's bytes are its inputs read once and its outputs written once,
  in the dtypes at its interface (bf16 activations, fp32 statistics and
  scan outputs); scratch and residuals it could avoid are not counted.

So a share of a roofline or of a peak built from these counts cannot pass
100% unless the time measured leaves out part of the work.
"""

from __future__ import annotations

from typing import Dict, Tuple

BF16, F32 = 2, 4


def ssm_dims(c) -> Tuple[int, int, int, int, int]:
    """(d_model, d_inner, heads, groups, state) of a Mamba-2 layer."""
    d = c["hidden_size"]
    di = c["expand"] * d
    return d, di, di // c["head_dim"], c["n_groups"], c["state_size"]


def ssm_layer_matmul_flops(c) -> int:
    """Projection FLOPs per token of one Mamba-2 layer (in and out)."""
    d, di, nh, g, n = ssm_dims(c)
    return 2 * d * (2 * di + 2 * g * n + nh) + 2 * di * d


def ssd_fwd_flops_per_token(c) -> float:
    """SSD scan FLOPs per token of one layer at the chunk it runs with:
    C·Bᵀ over causal pairs (per group), the decay-weighted product with x
    over causal pairs (per head), the chunk states and the carried-in
    state's output."""
    _, _, nh, g, n = ssm_dims(c)
    p, q = c["head_dim"], c["chunk_size"]
    pairs = (q + 1) / 2.0
    return 2 * pairs * n * g + 2 * pairs * p * nh + 2 * 2 * nh * p * n


def attention_fwd_flops_per_token(c, seq: int) -> float:
    """Causal attention FLOPs per token: QKᵀ and PV over (S+1)/2 keys."""
    h, hd = c["num_attention_heads"], c["attention_head_dim"]
    return 2 * 2 * h * hd * (seq + 1) / 2.0


def shared_block_matmul_flops(c) -> int:
    d, h, hd, ff = (c["hidden_size"], c["num_attention_heads"],
                    c["attention_head_dim"], c["intermediate_size"])
    return 2 * d * h * hd * 4 + 2 * d * ff * 3


def forward_flops_per_token(c, seq: int) -> float:
    """Forward FLOPs per token of the whole model: every Mamba-2 layer,
    every application of a shared attention block, and the output head."""
    layers = c["num_hidden_layers"]
    f = layers * (ssm_layer_matmul_flops(c) + ssd_fwd_flops_per_token(c))
    every = c.get("shared_attention_every", 0)
    if every:
        apps = layers // every
        f += apps * (shared_block_matmul_flops(c)
                     + attention_fwd_flops_per_token(c, seq))
    f += 2 * c["hidden_size"] * c["vocab_size"]
    return f


def train_step_flops(c, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward plus a backward of twice
    the forward, no recomputation."""
    return 3.0 * forward_flops_per_token(c, seq) * batch * seq


def kernel_costs(c, batch: int, seq: int) -> Dict[str, Tuple[float, float]]:
    """{kernel name: (FLOPs, bytes) of one call} at this cell's shapes. One
    call is one layer (one application) over the whole batch."""
    t = batch * seq
    out: Dict[str, Tuple[float, float]] = {}
    if "state_size" in c:
        _, di, nh, g, n = ssm_dims(c)
        fwd = ssd_fwd_flops_per_token(c) * t
        x, dt, bc = t * di * BF16, t * nh * F32, 2 * t * g * n * BF16
        y, state = t * di * F32, batch * nh * c["head_dim"] * n * F32
        out["ssd_fwd"] = (fwd, x + dt + bc + nh * F32 + y + state)
        # backward: reads x, dt, B, C and dy; writes dx, ddt, dB, dC, dA
        out["ssd_bwd"] = (2 * fwd, x + dt + bc + y + x + dt + bc + nh * F32)
    if c.get("shared_attention_every"):
        h, hd = c["num_attention_heads"], c["attention_head_dim"]
        pair_flops = 2 * h * hd * batch * seq * (seq + 1) / 2.0
        act = t * h * hd * BF16            # one of q, k, v, o, do, dq, ...
        row = t * h * F32                  # lse or delta
        out["flash_fwd"] = (2 * pair_flops, 4 * act + row)          # q k v -> o lse
        out["flash_dq"] = (3 * pair_flops, 5 * act + 2 * row)       # q k v do -> dq
        out["flash_dkv"] = (4 * pair_flops, 6 * act + 2 * row)      # q k v do -> dk dv
    return out
