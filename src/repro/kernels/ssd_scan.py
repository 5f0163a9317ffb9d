"""Mamba2 SSD chunk scan — fused, differentiable Pallas TPU kernel.

§Perf pair B localized mamba2/zamba2's residual memory term to the SSD
intra-chunk intermediates: the pure-JAX ``ssd_scan`` materializes per-chunk
decay matrices ``L = exp(segsum(dA))`` of shape (b, c, h, q, q) plus carried
states to HBM every layer and every pass. This kernel fuses the whole chunk
pipeline — decay computation, intra-chunk "attention" (C·Bᵀ ∘ L)·x, carried-
state contribution, and the inter-chunk state recurrence — so only x/dt/B/C
stream in and y streams out; L and the running state never leave VMEM, in
either pass.

Layout (TPU adaptation — same pattern as flash_attention.py):

- grid = (batch, groups · head_blocks, n_chunks) with the chunk dim minor.
  One grid step holds ``hb`` heads of one group for one chunk: x and y as
  (hb, q, p) blocks, dt as one (hb, q) tile (rows of heads), A as (hb, 1),
  and that group's B and C as one (q, n) block each, fetched once for all
  ``hb`` heads. Heads are group-major, so head block j belongs to group
  j // head_blocks.
- the (hb, p, n) running states live in VMEM scratch across chunk steps (the
  recurrence the GPU implementation does with a separate kernel launch +
  global memory round trip).
- per grid step, once: ``C·Bᵀ`` (q, q); the block's cumulative log-decays
  ``cs = cumsum(dt·A)`` as rows (hb, q), one triangular product at fp32
  precision, and their transposes (q, hb), so each head's column terms are a
  lane slice. Then an
  unrolled loop over the block's heads forms each head's decay matrix L,
  ``scores = CB ∘ L``, its y, carried-state term and state update.
- ``hb`` is the largest divisor of the heads per group (as the kernel sees
  them: the local count under tensor parallelism) whose VMEM working set —
  every block double-buffered, with (8, 128) tile padding, plus scratch and
  the (q, q) temporaries, all counted at fp32 — fits ``VMEM_BUDGET`` (24 MiB)
  under the 32-MiB scoped limit both kernels ask for (:func:`ssd_head_block`,
  :func:`vmem_bytes`). mamba2-370m (32 heads, P 64, N 128, q 128): one block
  of 32 heads in both passes, 128 grid steps a call at 8 x 2048 tokens;
  zamba2-1.2b (64 heads, N 64): blocks of 32; zamba2-7b's tensor-parallel
  share (56 heads, N 64, chunk 256): 28 forward, 14 backward.

Backward follows the FlashAttention-2 recipe (PAPERS.md): the forward
additionally saves only the state *entering* each chunk — an (nc, p, n) strip
per (batch, head), the logsumexp analogue — and a reversed-grid backward
kernel recomputes the decay matrix ``L`` and the intra-chunk scores tile by
tile in VMEM to produce ``dx/ddt/dA/dB/dC``:

- the same grid and head blocks, sweeping chunks *last to first* (the index
  maps flip the chunk coordinate); the (hb, p, n) state cotangents ``dS``
  ride across steps in VMEM scratch, seeded by the final-state cotangent,
  propagated by ``dS_in = exp(cs[-1])·dS_out + (dy ∘ exp(cs))ᵀ·C``.
- per-chunk, all (q, q) quantities (L, scores, dscores) are recomputed from
  the streamed-in x/dt/B/C, never written to HBM. ``C·Bᵀ`` is formed once
  per grid step; ``Σ_h dcb_h`` accumulates in a (q, q) scratch, and
  ``(Σ dcb)·B`` and ``(Σ dcb)ᵀ·C`` run once per grid step. The per-head
  terms of dB and dC accumulate in the (q, n) output blocks themselves.
- dB and dC leave the kernel as (b, groups · head_blocks, l, n): summed over
  each block's heads inside the kernel, and over a group's head blocks
  outside (no sum at all when one block holds the group).
- the kernel emits ``dda`` (cotangent of the per-step log-decay ``dt·A``)
  and ``ddt`` as (hb, q) rows per grid step: each head's cotangent of cs is
  gathered into rows, and one suffix-sum product over all the block's heads
  turns them into ``dda``; outside, ``dA_h = Σ dda·dt``.

Every dot takes the same fp32 operands as the per-head formulation and
accumulates in fp32, at Mosaic's default precision; the cumulative sums,
vector reductions before, are HIGHEST-precision products. Only sums over
heads, and the order of the cumulative sums, are reassociated.

``jax.custom_vjp`` ties the two kernels together, so ``jax.grad`` through
:func:`ssd_chunk_scan` never materializes a (b, c, h, q, q) decay tensor.

``interpret=None`` auto-detects the backend: compiled on TPU, interpreter
everywhere else.
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


# The VMEM working set a head block may take (ssd_head_block), and the scoped
# VMEM limit both kernels ask the compiler for: a v5e core has 128 MiB of
# VMEM, Mosaic's default scoped limit there is 16 MiB. The budget leaves a
# quarter of the limit to the compiler's own scratch.
VMEM_BUDGET = 24 * 2**20
VMEM_LIMIT = 32 * 2**20
_F32 = 4


def _tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of an fp32 (rows, cols) tile padded to the (8, 128) tiling."""
    return -(-rows // 8) * 8 * -(-cols // 128) * 128 * _F32


def vmem_bytes(hb: int, p: int, n: int, chunk: int, backward: bool) -> int:
    """VMEM working set of one grid step over ``hb`` heads, every stream
    counted at fp32: the blocks double-buffered, plus scratch and the (q, q)
    temporaries of one head."""
    head, rows, rates = (_tile_bytes(chunk, p), _tile_bytes(hb, chunk),
                         _tile_bytes(hb, 1))
    state, qn, qq = (_tile_bytes(p, n), _tile_bytes(chunk, n),
                     _tile_bytes(chunk, chunk))
    if backward:
        # x, dy, dx and the entering state, final-state cotangent per head;
        # dt, ddt, dda; A; B, C, dB, dC. Scratch: dS per head, Σ dcb, the
        # cs cotangent's three row sets.
        blocks = hb * (3 * head + 2 * state) + 3 * rows + rates + 4 * qn
        scratch = hb * state + qq + 3 * rows
        temps = 8 * qq
    else:
        # x, y and the entering and final states per head; dt; A; B, C.
        # Scratch: the running states.
        blocks = hb * (2 * head + 2 * state) + rows + rates + 2 * qn
        scratch = hb * state
        temps = 4 * qq
    return 2 * blocks + scratch + temps


def ssd_head_block(heads_per_group: int, p: int, n: int, chunk: int,
                   backward: bool = False) -> int:
    """Heads one grid step holds: the largest divisor of ``heads_per_group``
    whose working set (:func:`vmem_bytes`) fits ``VMEM_BUDGET``; 1 if none
    does."""
    for hb in range(heads_per_group, 0, -1):
        if (heads_per_group % hb == 0
                and vmem_bytes(hb, p, n, chunk, backward) <= VMEM_BUDGET):
            return hb
    return 1


def _lower_tri(q: int, upper: bool = False):
    """(q, q) mask of i >= j (``upper``: i <= j)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return i <= j if upper else i >= j


def _row_scan(v, reverse: bool = False):
    """Inclusive prefix (``reverse``: suffix) sums along the rows of an
    (r, q) tile: one triangular product at fp32 precision. Mosaic's default
    precision rounds an fp32 dot's operands to bf16, which exp(cs) would
    magnify, so this one dot asks for HIGHEST."""
    tri = _lower_tri(v.shape[1], upper=not reverse).astype(jnp.float32)
    return jax.lax.dot(v, tri, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a · bᵀ, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """aᵀ · b, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    return jax.lax.dot(a, b, preferred_element_type=jnp.float32)


class _Decay:
    """The decay terms of a block's heads over one chunk. Rows (hb, q) hold a
    head's (q,) vector along lanes; the transposed columns (q, hb) hold it
    along sublanes, as a head's (q, q) and (q, p) tiles broadcast it.

    cs = cumsum(dt·A) along the chunk, for all heads as one triangular
    product; cs_end = cs[-1] as a sum."""

    def __init__(self, dt, a):
        q = dt.shape[1]
        da = dt * a                                        # (hb, q) log-decays
        self.cs = _row_scan(da)
        cs_end = da.sum(axis=1, keepdims=True)             # (hb, 1)
        self.exp_end = jnp.exp(cs_end)                     # (hb, 1)
        self.dt_t = dt.T                                   # (q, hb)
        self.cs_t = self.cs.T
        self.exp_cs_t = jnp.exp(self.cs).T                 # exp(cs)
        self.decay_t = jnp.exp(cs_end - self.cs).T         # exp(cs[-1] - cs)
        self.tri = _lower_tri(q)

    def L(self, i):
        """Head i's (q, q) decay matrix exp(cs_t - cs_s) over t >= s. Masked
        *before* exp: the upper entries hold positive log-decays that could
        overflow fp32 for long chunks / large dt·|A|."""
        li = self.cs_t[:, i:i + 1] - self.cs[i:i + 1, :]
        return jnp.exp(jnp.where(self.tri, li, -jnp.inf))


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *refs,
                n_chunks: int, hb: int):
    # refs = (enter_ref?, state_out_ref, state_ref): the entering-states
    # residual output only exists when the VJP will need it — forward-only
    # calls (eval/decode) skip that extra HBM write entirely
    enter_ref = refs[0] if len(refs) == 3 else None
    state_out_ref, state_ref = refs[-2], refs[-1]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    bmat = b_ref[0, 0].astype(jnp.float32)       # (q, n), shared by the block
    cmat = c_ref[0, 0].astype(jnp.float32)       # (q, n)
    cb = _dot_nt(cmat, bmat)                      # (q, q), once per group
    d = _Decay(dt_ref[0, 0].astype(jnp.float32), a_ref[0])

    for i in range(hb):
        h = slice(i, i + 1)
        xd = x_ref[0, i].astype(jnp.float32) * d.dt_t[:, h]   # (q, p)
        y = _dot(cb * d.L(i), xd)

        # carried-state contribution: y += exp(cs) * C @ state  (state: (p, n))
        state = state_ref[i]
        if enter_ref is not None:
            enter_ref[0, i, 0] = state.astype(enter_ref.dtype)  # bwd residual
        y = y + d.exp_cs_t[:, h] * _dot_nt(cmat, state)

        # state recurrence: state' = exp(cs[-1])·state + Σ_q exp(cs[-1]-cs)·xdᵀB
        state_ref[i] = (state * d.exp_end[h, :]
                        + _dot_tn(xd * d.decay_t[:, h], bmat))
        y_ref[0, i] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...].astype(state_out_ref.dtype)


def _ssd_forward(x, dt, A, Bm, Cm, chunk, interpret, save_enters: bool,
                 hb: int):
    """Returns (y (B,H,L,P) fp32, entering states (B,H,nc,P,N) fp32 or None,
    final_state (B,H,P,N) fp32). ``save_enters`` is True only under the VJP —
    forward-only calls skip the residual's HBM write. ``hb`` heads of one
    group share each grid step."""
    b, h, l, p = x.shape
    g, n = Bm.shape[1], Bm.shape[3]
    assert l % chunk == 0, (l, chunk)
    assert h % g == 0
    hpg = h // g
    assert hpg % hb == 0, (hpg, hb)
    nhb = hpg // hb                               # head blocks per group
    nc = l // chunk
    grid = (b, g * nhb, nc)

    out_specs = [
        pl.BlockSpec((1, hb, chunk, p), lambda bi, j, ci: (bi, j, ci, 0)),
        pl.BlockSpec((1, hb, p, n), lambda bi, j, ci: (bi, j, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, l, p), jnp.float32),
        jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
    ]
    if save_enters:
        out_specs.insert(1, pl.BlockSpec(
            (1, hb, 1, p, n), lambda bi, j, ci: (bi, j, ci, 0, 0)))
        out_shape.insert(1, jax.ShapeDtypeStruct((b, h, nc, p, n),
                                                 jnp.float32))
    group_spec = pl.BlockSpec((1, 1, chunk, n),
                              lambda bi, j, ci: (bi, j // nhb, ci, 0))
    # dt (B, H, L) travels as (B, H/hb, hb, L) and A as (H/hb, hb, 1): a head
    # block's per-step rows, and its decay rates, each as one 2-D tile

    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, n_chunks=nc, hb=hb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda bi, j, ci: (bi, j, ci, 0)),
            pl.BlockSpec((1, 1, hb, chunk), lambda bi, j, ci: (bi, j, 0, ci)),
            pl.BlockSpec((1, hb, 1), lambda bi, j, ci: (j, 0, 0)),
            group_spec,
            group_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_fwd",
    )(x, dt.reshape(b, h // hb, hb, l),
      A.astype(jnp.float32).reshape(-1, hb, 1), Bm, Cm)
    if save_enters:
        return outs[0], outs[1], outs[2]
    return outs[0], None, outs[1]


# ---------------------------------------------------------------------------
# backward


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, enter_ref, dy_ref,
                dsf_ref, dx_ref, ddt_ref, dda_ref, db_ref, dc_ref,
                dstate_ref, dcb_ref, dcs_ref, *, hb: int):
    ci = pl.program_id(2)   # reversed sweep: index maps flip to chunk nc-1-ci

    @pl.when(ci == 0)
    def _init():
        # seed with the final-state cotangents
        dstate_ref[...] = dsf_ref[0].astype(jnp.float32)

    bmat = b_ref[0, 0].astype(jnp.float32)       # (q, n)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (q, n)
    cb = _dot_nt(cmat, bmat)                      # (q, q), once per group
    a = a_ref[0]                                  # (hb, 1)
    d = _Decay(dt_ref[0, 0].astype(jnp.float32), a)
    q = cb.shape[0]
    # column -> row: Σ_t mask[t, s]·v[t] over sublanes, for the suffix sums
    # (t >= s) and for a plain transpose (t == s)
    suffix = d.tri
    eye = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    # group sums over the block's heads: Σ dcb, and the per-head terms of
    # dB and dC, accumulated in their own output blocks
    dcb_ref[...] = jnp.zeros_like(dcb_ref)
    db_ref[0, 0] = jnp.zeros(db_ref.shape[2:], db_ref.dtype)
    dc_ref[0, 0] = jnp.zeros(dc_ref.shape[2:], dc_ref.dtype)

    for i in range(hb):
        h = slice(i, i + 1)
        x = x_ref[0, i].astype(jnp.float32)      # (q, p)
        sin = enter_ref[0, i, 0].astype(jnp.float32)  # (p, n) entering state
        dy = dy_ref[0, i].astype(jnp.float32)    # (q, p)
        ds_out = dstate_ref[i]                    # (p, n) cotangent of S_out
        dt_col, decay = d.dt_t[:, h], d.decay_t[:, h]       # (q, 1)
        xd = x * dt_col
        L = d.L(i)
        scores = cb * L

        # --- intra-chunk "attention" term: y_diag = scores @ xd
        dscores = _dot_nt(dy, xd)                 # (q, q)
        dxd = _dot_tn(scores, dy)                 # (q, p)
        dcb_ref[...] += dscores * L

        # --- carried-state term: y_off = exp(cs) ∘ (C @ sinᵀ)
        y_off = d.exp_cs_t[:, h] * _dot_nt(cmat, sin)               # (q, p)
        dy_e = dy * d.exp_cs_t[:, h]
        dc_ref[0, 0] += _dot(dy_e, sin)

        # --- state-recurrence term: S_out = exp(cs[-1])·sin + Σ ds_i·xd_i⊗B_i
        xd_ds = _dot(xd, ds_out)                  # (q, n)
        dxd = dxd + decay * _dot_nt(bmat, ds_out)
        db_ref[0, 0] += decay * xd_ds

        # --- cotangent of the cumulative log-decays cs. The terms summed
        # over lanes come out as a column: G's rows, exp(cs) in y_off,
        # exp(cs[-1]-cs) in the state update. G's column sums come out as a
        # row.
        G = dscores * scores                       # dL ∘ L, zero above diagonal
        t_qn = decay * xd_ds * bmat
        dcs_col = (G.sum(axis=1, keepdims=True)
                   + (dy * y_off).sum(axis=1, keepdims=True)
                   - t_qn.sum(axis=1, keepdims=True))           # (q, 1)
        # cs = cumsum(da)  =>  dda_s = Σ_{t>=s} dcs_t. The column part's suffix
        # sums are taken here as a row; the row part is summed for all heads
        # after the loop. The two cs[-1] contributions (Σt from decay, the
        # exp(cs[-1])·sin term) land on every entry, so they join the total.
        last = (t_qn.sum(keepdims=True)
                + d.exp_end[h, :] * (ds_out * sin).sum(keepdims=True))
        dcs_ref[0, h, :] = (jnp.where(suffix, dcs_col, 0.0)
                            .sum(axis=0, keepdims=True) + last)
        dcs_ref[1, h, :] = -G.sum(axis=0, keepdims=True)
        dcs_ref[2, h, :] = jnp.where(eye, (dxd * x).sum(axis=1, keepdims=True),
                                     0.0).sum(axis=0, keepdims=True)

        # propagate the state cotangent to the previous chunk
        dstate_ref[i] = d.exp_end[h, :] * ds_out + _dot_tn(dy_e, cmat)
        dx_ref[0, i] = (dxd * dt_col).astype(dx_ref.dtype)

    # the row part's suffix sums, for all heads as one triangular product
    dda = dcs_ref[0] + _row_scan(dcs_ref[1], reverse=True)
    dda_ref[0, 0] = dda.astype(dda_ref.dtype)
    ddt_ref[0, 0] = (dda * a + dcs_ref[2]).astype(ddt_ref.dtype)

    # the C·Bᵀ products of all the block's heads, once: dC += (Σ dcb)·B,
    # dB += (Σ dcb)ᵀ·C
    dcb = dcb_ref[...]
    dc_ref[0, 0] += _dot(dcb, bmat)
    db_ref[0, 0] += _dot_tn(dcb, cmat)


def _ssd_backward(chunk, interpret, res, g):
    x, dt, A, Bm, Cm, enters = res
    dy, dsf = g
    b, h, l, p = x.shape
    grp, n = Bm.shape[1], Bm.shape[3]
    hpg = h // grp
    hb = ssd_head_block(hpg, p, n, chunk, backward=True)
    return _ssd_backward_blocks(x, dt, A, Bm, Cm, enters, dy, dsf, chunk,
                                interpret, hb)


def _ssd_backward_blocks(x, dt, A, Bm, Cm, enters, dy, dsf, chunk, interpret,
                         hb: int):
    b, h, l, p = x.shape
    grp, n = Bm.shape[1], Bm.shape[3]
    hpg = h // grp
    assert hpg % hb == 0, (hpg, hb)
    nhb = hpg // hb
    nc = l // chunk
    grid = (b, grp * nhb, nc)
    rev = nc - 1   # index maps sweep chunks last -> first

    def heads(*block):
        # one block of the block's heads at the chunk, per-head arrays
        zeros = (0,) * (len(block) - 1)
        return pl.BlockSpec((1, hb) + block,
                            lambda bi, j, ci: (bi, j, rev - ci) + zeros)

    rows_spec = pl.BlockSpec((1, 1, hb, chunk),
                             lambda bi, j, ci: (bi, j, 0, rev - ci))
    group_spec = pl.BlockSpec((1, 1, chunk, n),
                              lambda bi, j, ci: (bi, j // nhb, rev - ci, 0))
    # dB/dC blocks: one per head block (axis 1 of size grp · nhb)
    block_spec = pl.BlockSpec((1, 1, chunk, n),
                              lambda bi, j, ci: (bi, j, rev - ci, 0))
    rows_shape = jax.ShapeDtypeStruct((b, grp * nhb, hb, l), jnp.float32)

    dx, ddt, dda, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb),
        grid=grid,
        in_specs=[
            heads(chunk, p),
            rows_spec,
            pl.BlockSpec((1, hb, 1), lambda bi, j, ci: (j, 0, 0)),
            group_spec,
            group_spec,
            heads(1, p, n),
            heads(chunk, p),
            pl.BlockSpec((1, hb, p, n), lambda bi, j, ci: (bi, j, 0, 0)),
        ],
        out_specs=[heads(chunk, p), rows_spec, rows_spec, block_spec,
                   block_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), jnp.float32),
            rows_shape,
            rows_shape,
            jax.ShapeDtypeStruct((b, grp * nhb, l, n), jnp.float32),
            jax.ShapeDtypeStruct((b, grp * nhb, l, n), jnp.float32),
        ],
        # dS per head; Σ dcb; rows per head of the cs cotangent: the column
        # part's suffix sums, the row part, and Σ_p dxd ∘ x for ddt
        scratch_shapes=[pltpu.VMEM((hb, p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((3, hb, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_bwd",
    )(x, dt.reshape(b, h // hb, hb, l),
      A.astype(jnp.float32).reshape(-1, hb, 1), Bm, Cm, enters,
      dy.astype(jnp.float32), dsf.astype(jnp.float32))
    ddt, dda = ddt.reshape(b, h, l), dda.reshape(b, h, l)

    # block sums of B/C gradients -> sum a group's head blocks onto the shared
    # projection (GQA trick); a no-op reshape when one block holds the group
    dB = db.reshape(b, grp, nhb, l, n).sum(axis=2).astype(Bm.dtype)
    dC = dc.reshape(b, grp, nhb, l, n).sum(axis=2).astype(Cm.dtype)
    # da = dt·A  =>  dA_h = Σ_{b,l} dda·dt (cheap elementwise reduction in XLA)
    dA = jnp.einsum("bhl,bhl->h", dda, dt.astype(jnp.float32)).astype(A.dtype)
    return dx.astype(x.dtype), ddt.astype(dt.dtype), dA, dB, dC


# ---------------------------------------------------------------------------
# custom_vjp plumbing


def _forward(x, dt, A, Bm, Cm, chunk, interpret, save_enters: bool):
    hpg = x.shape[1] // Bm.shape[1]
    hb = ssd_head_block(hpg, x.shape[3], Bm.shape[3], chunk)
    return _ssd_forward(x, dt, A, Bm, Cm, chunk, interpret, save_enters, hb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, Bm, Cm, chunk, interpret):
    y, _, state = _forward(x, dt, A, Bm, Cm, chunk, interpret,
                           save_enters=False)
    return y, state


def _ssd_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    y, enters, state = _forward(x, dt, A, Bm, Cm, chunk, interpret,
                                save_enters=True)
    # named for selective remat (models.families.REMAT_SAVE_NAMES): the
    # per-chunk entering states are the only activation-sized residual the
    # fused backward consumes
    y = checkpoint_name(y, "ssd_out")
    enters = checkpoint_name(enters, "ssd_state")
    return (y, state), (x, dt, A, Bm, Cm, enters)


_ssd.defvjp(_ssd_fwd, _ssd_backward)


def ssd_chunk_scan(
    x: jax.Array,        # (B, H, L, P)
    dt: jax.Array,       # (B, H, L)
    A: jax.Array,        # (H,) negative decay rates
    Bm: jax.Array,       # (B, G, L, N)
    Cm: jax.Array,       # (B, G, L, N)
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,   # None -> compiled on TPU, interpreted elsewhere
):
    """Fused differentiable SSD. Returns (y (B, H, L, P) fp32,
    final_state (B, H, P, N) fp32)."""
    return _ssd(x, dt, A, Bm, Cm, int(chunk), resolve_interpret(interpret))
