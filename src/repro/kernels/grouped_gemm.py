"""Per-expert batched GEMM — differentiable Pallas TPU kernel (survey §4.1.5,
MegaBlocks-style).

MoE expert compute is `(E, C, d) × (E, d, f) -> (E, C, f)`: one GEMM per expert
over its capacity buffer. On GPU MegaBlocks lowers this to block-sparse GEMM
over ragged groups; the TPU adaptation (DESIGN.md §2) keeps the fixed-capacity
layout (which the GShard dispatch already produces) and tiles each expert's
GEMM on the MXU:

- grid = (E, C/block_c, f/block_f, d/block_d) with the contraction dim minor,
  accumulating into a VMEM scratch tile across d-steps;
- block shapes 128-aligned; weights stream through VMEM one (block_d, block_f)
  tile at a time so arbitrarily large experts never exceed the VMEM budget.

``group_sizes`` (an ``(E,)`` int32 array) marks how many leading rows of each
expert's capacity buffer hold real tokens. Row tiles whose start index is past
the expert's load are skipped entirely (``pl.when`` on the whole tile) and the
straddling tile is masked at the output write — the dropless-MoE FLOP saving,
adapted to fixed capacity. ``group_sizes=None`` keeps every row.

Backward (the FlashAttention-2 analogue for GEMMs): ``jax.custom_vjp`` runs two
more grouped GEMMs through the same tiled kernel —

- ``dx = dy · wᵀ``   row-masked by ``group_sizes`` (padding rows get zero grad);
- ``dw = xᵀ · dy``   with ``group_sizes`` masking the *contraction* dim instead
  (padding rows must not contribute to weight gradients), via the kernel's
  ``mask="contract"`` mode that zeroes weight-tile rows past the group size and
  skips fully-padded contraction tiles.

``interpret=None`` auto-detects the backend like flash_attention: compiled on
TPU, interpreter everywhere else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import resolve_interpret

MASK_MODES = ("rows", "contract")


def _kernel(gs_ref, x_ref, w_ref, o_ref, acc_ref, *, n_dsteps: int,
            block_r: int, block_k: int, mask: str):
    ei = pl.program_id(0)
    ri = pl.program_id(1)
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gs = gs_ref[ei]   # scalar-prefetched group sizes live in SMEM
    # whole-tile skip: row tiles past the expert's load ("rows") or contraction
    # tiles made of padding rows ("contract") contribute nothing
    relevant = (ri * block_r < gs) if mask == "rows" else (di * block_k < gs)

    @pl.when(relevant)
    def _compute():
        x = x_ref[0].astype(jnp.float32)       # (br, bk)
        w = w_ref[0].astype(jnp.float32)       # (bk, bf)
        if mask == "contract":
            # zero the padding rows of the weight tile (global contraction
            # index >= group size); zeroing either operand's slice suffices
            kidx = di * block_k + jax.lax.broadcasted_iota(
                jnp.int32, w.shape, 0)
            w = jnp.where(kidx < gs, w, 0.0)
        acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(di == n_dsteps - 1)
    def _finish():
        acc = acc_ref[...]
        if mask == "rows":
            ridx = ri * block_r + jax.lax.broadcasted_iota(
                jnp.int32, acc.shape, 0)
            acc = jnp.where(ridx < gs, acc, 0.0)
        o_ref[0] = acc.astype(o_ref.dtype)


def _grouped_gemm(x, w, gs, *, mask: str, block_r: int, block_co: int,
                  block_k: int, interpret: bool):
    """(E, R, K) × (E, K, F) -> (E, R, F), masked by per-expert ``gs``."""
    assert mask in MASK_MODES, mask
    e, r, k = x.shape
    f = w.shape[-1]
    assert w.shape == (e, k, f), (x.shape, w.shape)

    block_r = min(block_r, r)
    block_co = min(block_co, f)
    block_k = min(block_k, k)

    def pad_to(a, dim, blk):
        rem = (-a.shape[dim]) % blk
        if rem == 0:
            return a
        pads = [(0, 0)] * a.ndim
        pads[dim] = (0, rem)
        return jnp.pad(a, pads)

    xp = pad_to(pad_to(x, 1, block_r), 2, block_k)
    wp = pad_to(pad_to(w, 1, block_k), 2, block_co)
    rp, kp, fp = xp.shape[1], xp.shape[2], wp.shape[2]
    grid = (e, rp // block_r, fp // block_co, kp // block_k)

    # group_sizes ride scalar prefetch: the whole (E,) vector sits in SMEM
    # and every grid step reads its expert's entry (a (1,) VMEM block per
    # expert is not a tiling the TPU accepts)
    out = pl.pallas_call(
        functools.partial(_kernel, n_dsteps=grid[3], block_r=block_r,
                          block_k=block_k, mask=mask),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_r, block_k),
                             lambda ei, ri, fi, di, gs_ref: (ei, ri, di)),
                pl.BlockSpec((1, block_k, block_co),
                             lambda ei, ri, fi, di, gs_ref: (ei, di, fi)),
            ],
            out_specs=pl.BlockSpec((1, block_r, block_co),
                                   lambda ei, ri, fi, di, gs_ref: (ei, ri, fi)),
            scratch_shapes=[pltpu.VMEM((block_r, block_co), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((e, rp, fp), x.dtype),
        interpret=interpret,
        name=f"grouped_gemm_{mask}",
    )(gs, xp, wp)
    return out[:, :r, :f]


# ---------------------------------------------------------------------------
# custom_vjp plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gemm(x, w, gs, block_c, block_f, block_d, interpret):
    return _grouped_gemm(x, w, gs, mask="rows", block_r=block_c,
                         block_co=block_f, block_k=block_d,
                         interpret=interpret)


def _gemm_fwd(x, w, gs, block_c, block_f, block_d, interpret):
    out = _gemm(x, w, gs, block_c, block_f, block_d, interpret)
    # named for selective remat (models.families.REMAT_SAVE_NAMES)
    out = checkpoint_name(out, "expert_gemm_out")
    return out, (x, w, gs)


def _gemm_bwd(block_c, block_f, block_d, interpret, res, g):
    x, w, gs = res
    # dx = dy · wᵀ — row-masked: padding rows never reached the output, so
    # their cotangent is zero (also skips their tiles entirely)
    dx = _grouped_gemm(g, w.transpose(0, 2, 1), gs, mask="rows",
                       block_r=block_c, block_co=block_d, block_k=block_f,
                       interpret=interpret)
    # dw = xᵀ · dy — contraction-masked: only real rows contribute to the
    # weight gradient
    dw = _grouped_gemm(x.transpose(0, 2, 1), g, gs, mask="contract",
                       block_r=block_d, block_co=block_f, block_k=block_c,
                       interpret=interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_gemm.defvjp(_gemm_fwd, _gemm_bwd)


def expert_gemm(
    x: jax.Array,                 # (E, C, d)
    w: jax.Array,                 # (E, d, f)
    group_sizes: Optional[jax.Array] = None,   # (E,) int32 real rows per expert
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 256,
    interpret: Optional[bool] = None,   # None -> compiled on TPU, interpreted elsewhere
) -> jax.Array:
    """Fused differentiable per-expert GEMM; see module docstring."""
    e, c, _ = x.shape
    if group_sizes is None:
        gs = jnp.full((e,), c, jnp.int32)
    else:
        gs = jax.lax.stop_gradient(group_sizes).astype(jnp.int32)
    return _gemm(x, w, gs, int(block_c), int(block_f), int(block_d),
                 resolve_interpret(interpret))
