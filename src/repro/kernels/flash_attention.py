"""FlashAttention — differentiable Pallas TPU kernel (survey §5.1.1).

The CUDA FlashAttention organizes around SMs, warps and shared memory; the TPU
version (DESIGN.md §2) organizes around the grid + BlockSpec machinery:

- forward grid = (batch, q_heads, S/block_q, T/block_k); the KV-block dim is
  minor, so for a fixed query tile the kernel sweeps KV tiles sequentially
  while online-softmax state (m, l, acc) lives in VMEM scratch across grid
  steps — the TPU equivalent of the CUDA inner loop over KV tiles in shared
  memory.
- BlockSpec index_maps implement GQA natively: query head h reads KV head
  h // group, so repeated KV never materializes in HBM.
- block shapes default to 128 (MXU-aligned); the last dim (head_dim) is kept
  whole inside VMEM (128, 224 or 256 for the assigned archs: a block that
  spans the whole dim lowers at 224 too, with no padding).
- causal + sliding-window + logit-softcap masks are computed from global tile
  offsets with iota (``q_offset`` shifts query positions for chunked prefill),
  and fully-masked tiles exit early via ``pl.when``.

Backward follows FlashAttention-2's one-write/two-reads split (PAPERS.md
"FlashAttention2"): the forward additionally emits the per-row logsumexp
``lse = m + log l`` (one extra S-sized vector per head instead of the O(S·T)
probability matrix), and two kernels recompute tiled scores from it:

- ``_dq_kernel``  — grid (..., S/bq, T/bk), KV minor: accumulates dq for a
  fixed query tile across KV tiles in VMEM scratch (one write per q row).
- ``_dkv_kernel`` — grid (..., T/bk, S/bq), Q minor: accumulates dk and dv for
  a fixed KV tile across query tiles (one write per k row).

Each recomputes p = exp(s - lse) and ds = p * (dO·Vᵀ - Δ) with
Δ = rowsum(dO ∘ O) (computed once in XLA before the kernels — cheap,
elementwise). GQA gradients are emitted per query head and group-summed
outside the kernel. ``jax.custom_vjp`` ties the three kernels together, so
``jax.grad`` through :func:`flash_attention` never materializes score
matrices in HBM.

VMEM working set per step ≈ q(128·hd) + k,v(128·hd) + scores(128·128) + acc —
well under the ~16 MB budget for hd ≤ 256.

``interpret=None`` auto-detects the backend: compiled on TPU, interpreter
everywhere else (CPU containers validate correctness through the same code
path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

NEG_INF = -1e30


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> backend detection: compiled on TPU, interpreter elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _tile_relevant(q_start, k_start, *, causal: bool, window: int,
                   q_offset: int, block_q: int, block_k: int):
    """Whole-tile skip: causal / sliding-window can rule out (q, k) tile pairs."""
    relevant = jnp.bool_(True)
    if causal:
        relevant = k_start <= q_offset + q_start + block_q - 1
    if window > 0:
        # oldest key in tile must be within reach of at least one query in it
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_offset + q_start - window)
    return relevant


def _tile_mask(q_start, k_start, *, causal: bool, window: int, q_offset: int,
               block_q: int, block_k: int, seq_q: int, seq_k: int):
    """(block_q, block_k) boolean mask from global tile offsets."""
    rows_l = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    rows_g = q_offset + rows_l
    mask = (rows_l < seq_q) & (cols < seq_k)
    if causal:
        mask &= cols <= rows_g
    if window > 0:
        mask &= (rows_g - cols) < window
    return mask


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, window: int, softcap: float,
                q_offset: int, block_q: int, block_k: int,
                seq_q: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_relevant(q_start, k_start, causal=causal, window=window,
                            q_offset=q_offset, block_q=block_q,
                            block_k=block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)

        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          q_offset=q_offset, block_q=block_q, block_k=block_k,
                          seq_q=seq_q, seq_k=seq_k)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None]) * mask
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_ref[...] + jnp.log(l)


def _fwd_scratch(block_q: int, hd: int):
    from jax.experimental.pallas import tpu as pltpu
    return [
        pltpu.VMEM((block_q,), jnp.float32),          # m
        pltpu.VMEM((block_q,), jnp.float32),          # l
        pltpu.VMEM((block_q, hd), jnp.float32),       # acc
    ]


def _pad_seq(x, axis: int, target: int):
    if x.shape[axis] == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, pads)


def _flash_forward(q, k, v, causal, window, softcap, scale, q_offset,
                   block_q, block_k, interpret):
    """Returns (o (B,Hq,S,hd), lse (B,Hq,S) fp32)."""
    b, hq, s, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    assert hq % hkv == 0
    group = hq // hkv

    block_q = min(block_q, s)
    block_k = min(block_k, t)
    s_pad = -(-s // block_q) * block_q
    t_pad = -(-t // block_k) * block_k
    q = _pad_seq(q, 2, s_pad)
    k = _pad_seq(k, 2, t_pad)
    v = _pad_seq(v, 2, t_pad)

    grid = (b, hq, s_pad // block_q, t_pad // block_k)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, window=window,
            softcap=softcap, q_offset=q_offset, block_q=block_q,
            block_k=block_k, seq_q=s, seq_k=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, qi, ki, g=group: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, qi, ki, g=group: (bi, h // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, h, qi, ki: (bi, h, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, s_pad, hd), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, s_pad), jnp.float32),
        ],
        scratch_shapes=_fwd_scratch(block_q, hd),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out[:, :, :s, :], lse[:, :, 0, :s]


# ---------------------------------------------------------------------------
# backward


def _recompute_ds(q, k, v, do, lse, delta, mask, *, scale: float,
                  softcap: float):
    """Shared tile math of both backward kernels.

    Returns (p, ds_raw), both (block_q, block_k) fp32, where p is the
    normalized probability tile and ds_raw = dL/d(q·kᵀ·scale) before the
    scale factor is re-applied to dq/dk.
    """
    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap:
        th = jnp.tanh(s_raw / softcap)
        s_c = softcap * th
    else:
        s_c = s_raw
    # where() before exp: lse of fully-masked rows is a huge negative number,
    # exp(s - lse) would overflow before the mask could zero it
    p = jnp.exp(jnp.where(mask, s_c - lse[:, None], NEG_INF))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    if softcap:
        ds = ds * (1.0 - th * th)
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc_ref,
               *, scale: float, causal: bool, window: int, softcap: float,
               q_offset: int, block_q: int, block_k: int,
               seq_q: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_relevant(q_start, k_start, causal=causal, window=window,
                            q_offset=q_offset, block_q=block_q,
                            block_k=block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          q_offset=q_offset, block_q=block_q, block_k=block_k,
                          seq_q=seq_q, seq_k=seq_k)
        _, ds = _recompute_ds(q, k, v, do, lse_ref[0, 0, 0], dl_ref[0, 0, 0],
                              mask, scale=scale, softcap=softcap)
        acc_ref[...] += jax.lax.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale: float, causal: bool, window: int, softcap: float,
                q_offset: int, block_q: int, block_k: int,
                seq_q: int, seq_k: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(_tile_relevant(q_start, k_start, causal=causal, window=window,
                            q_offset=q_offset, block_q=block_q,
                            block_k=block_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        mask = _tile_mask(q_start, k_start, causal=causal, window=window,
                          q_offset=q_offset, block_q=block_q, block_k=block_k,
                          seq_q=seq_q, seq_k=seq_k)
        p, ds = _recompute_ds(q, k, v, do, lse_ref[0, 0, 0], dl_ref[0, 0, 0],
                              mask, scale=scale, softcap=softcap)
        # contract the query dim: pᵀ·do and dsᵀ·q without explicit transposes
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(causal, window, softcap, scale, q_offset, block_q,
                    block_k, interpret, res, g):
    q, k, v, o, lse = res
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)      # (B, Hq, S)
    return flash_attention_bwd(
        q, k, v, do, lse, delta, causal=causal, window=window,
        softcap=softcap, scale=scale, q_offset=q_offset, block_q=block_q,
        block_k=block_k, interpret=interpret)


def flash_attention_bwd(q, k, v, do, lse, delta, *, causal, window, softcap,
                        scale, q_offset, block_q, block_k, interpret):
    """Backward kernels against an externally supplied softmax statistic.

    This is the lse-merging chunk entry of the backward: ``lse``/``delta`` may
    come from a *larger* softmax than (k, v) — ring context parallelism passes
    the globally merged logsumexp and Δ = rowsum(dO ∘ O_global) while (k, v)
    is one ring chunk, and the emitted (dq, dk, dv) are exactly that chunk's
    contribution to the global attention gradient. ``_flash_backward`` (the
    single-device custom-VJP rule) is the degenerate one-chunk case.

    Layouts are head-major: q/do (B, Hq, S, hd); k/v (B, Hkv, T, hd);
    lse/delta (B, Hq, S) fp32. Returns (dq, dk, dv) with dk/dv group-summed
    back onto the shared KV heads.
    """
    b, hq, s, hd = q.shape
    hkv, t = k.shape[1], k.shape[2]
    group = hq // hkv

    block_q = min(block_q, s)
    block_k = min(block_k, t)
    s_pad = -(-s // block_q) * block_q
    t_pad = -(-t // block_k) * block_k
    qp = _pad_seq(q, 2, s_pad)
    dop = _pad_seq(do, 2, s_pad)
    # per-row statistics travel as (B, Hq, 1, S): a (1, block_q) block is a
    # lane-major row the TPU tiling accepts, where (1, 1, block_q) over
    # (B, Hq, S) puts a 1 in the sublane dim
    lsep = _pad_seq(lse, 2, s_pad)[:, :, None, :]
    deltap = _pad_seq(delta, 2, s_pad)[:, :, None, :]
    kp = _pad_seq(k, 2, t_pad)
    vp = _pad_seq(v, 2, t_pad)

    kwargs = dict(scale=scale, causal=causal, window=window, softcap=softcap,
                  q_offset=q_offset, block_q=block_q, block_k=block_k,
                  seq_q=s, seq_k=t)
    from jax.experimental.pallas import tpu as pltpu

    q_spec = pl.BlockSpec((1, 1, block_q, hd),
                          lambda bi, h, i, j: (bi, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda bi, h, i, j, g=group: (bi, h // g, j, 0))
    row_spec = pl.BlockSpec((1, 1, 1, block_q),
                            lambda bi, h, i, j: (bi, h, 0, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kwargs),
        grid=(b, hq, s_pad // block_q, t_pad // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, h, i, j: (bi, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s_pad, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(qp, kp, vp, dop, lsep, deltap)

    # dk/dv grids put the query-tile dim minor so the accumulators carry; the
    # q-side specs therefore index with the *minor* grid coordinate
    q_spec_t = pl.BlockSpec((1, 1, block_q, hd),
                            lambda bi, h, i, j: (bi, h, j, 0))
    kv_spec_t = pl.BlockSpec((1, 1, block_k, hd),
                             lambda bi, h, i, j, g=group: (bi, h // g, i, 0))
    row_spec_t = pl.BlockSpec((1, 1, 1, block_q),
                              lambda bi, h, i, j: (bi, h, 0, j))
    dkv_out = pl.BlockSpec((1, 1, block_k, hd),
                           lambda bi, h, i, j: (bi, h, i, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kwargs),
        grid=(b, hq, t_pad // block_k, s_pad // block_q),
        in_specs=[q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[dkv_out, dkv_out],
        out_shape=[jax.ShapeDtypeStruct((b, hq, t_pad, hd), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, t_pad, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(qp, kp, vp, dop, lsep, deltap)

    # GQA: gradients were emitted per query head; sum each group back onto
    # its shared KV head
    dk = dk[:, :, :t].reshape(b, hkv, group, t, hd).sum(axis=2)
    dv = dv[:, :, :t].reshape(b, hkv, group, t, hd).sum(axis=2)
    return (dq[:, :, :s].astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


# ---------------------------------------------------------------------------
# custom_vjp plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, window, softcap, scale, q_offset, block_q,
           block_k, interpret):
    o, _ = _flash_forward(q, k, v, causal, window, softcap, scale, q_offset,
                          block_q, block_k, interpret)
    return o


def _flash_fwd(q, k, v, causal, window, softcap, scale, q_offset, block_q,
               block_k, interpret):
    o, lse = _flash_forward(q, k, v, causal, window, softcap, scale, q_offset,
                            block_q, block_k, interpret)
    # named for selective remat (models.families.REMAT_SAVE_NAMES): saving
    # (out, lse) lets jax.checkpoint keep exactly the backward's residuals
    # instead of re-running the forward kernel
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _flash_backward)


def flash_attention(
    q: jax.Array,                 # (B, Hq, S, hd)
    k: jax.Array,                 # (B, Hkv, T, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,            # global position of q[.., 0, ..] (chunked prefill)
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,   # None -> compiled on TPU, interpreted elsewhere
) -> jax.Array:
    """Fused differentiable attention. Mask parameters must be static."""
    hd = q.shape[-1]
    scale = float(scale) if scale is not None else hd ** -0.5
    return _flash(q, k, v, bool(causal), int(window), float(softcap), scale,
                  int(q_offset), int(block_q), int(block_k),
                  resolve_interpret(interpret))


def flash_attention_lse(
    q: jax.Array,                 # (B, Hq, S, hd)
    k: jax.Array,                 # (B, Hkv, T, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """Forward kernel that also returns the per-row logsumexp.

    The lse-merging entry for chunked softmax (ring context parallelism,
    survey §4.1.4): partial attention over one KV chunk returns
    ``(o_c, lse_c)`` and chunks merge exactly via
    ``lse = log Σ_c exp(lse_c)``, ``o = Σ_c exp(lse_c - lse) · o_c``.
    Fully-masked rows report ``lse ≈ NEG_INF`` (finite), so they drop out of
    the merge without producing NaNs. Not differentiable — ring attention owns
    the custom VJP and calls :func:`flash_attention_bwd` per chunk with the
    *merged* statistics. Returns (o (B, Hq, S, hd), lse (B, Hq, S) fp32).
    """
    hd = q.shape[-1]
    scale = float(scale) if scale is not None else hd ** -0.5
    return _flash_forward(q, k, v, bool(causal), int(window), float(softcap),
                          scale, int(q_offset), int(block_q), int(block_k),
                          resolve_interpret(interpret))
