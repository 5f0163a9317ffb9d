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

- x and y (and in the backward dy and dx) stay in the model's layout,
  viewed as (B, L, H·P), a free reshape of (B, L, H, P): nothing is
  transposed into or out of the kernels. grid = (batch, groups ·
  head_blocks, n_chunks) with the chunk dim minor. One grid step holds
  ``hb`` heads of one group for one chunk: x and y as one (q, hb·p) block
  whose lanes [i·p, (i+1)·p) are head i (heads are group-major, so head
  block j is a contiguous lane range and belongs to group j //
  head_blocks); dt as one (hb, q) tile (rows of heads); A as (hb, 1); and
  that group's B and C as one (q, n) block each, fetched once for all
  ``hb`` heads. The caller lays out only these: dt travels as (B, H/hb, hb,
  L), since a lane-legal (q, hb) block of (B, L, H) would need hb = H or a
  multiple of 128 (2 MB fp32 a layer in mamba2-370m); B and C as (B, G, L,
  N), a bitcast at one group.
- the block's heads go a lane tile at a time: as many P-wide heads side by
  side as fill 128 lanes (two at P 64, one at P 128; :func:`_tiles`). Per
  head, ``scores @ xd`` runs against the whole tile and a lane mask keeps
  the head's own lanes, at the MXU cost of a (q, p) product; the
  carried-state term ``C·stateᵀ`` and the state update ``(xd ∘ decay)ᵀ·B``
  run once per tile on its heads' stacked (k·p, n) states.
- the (hb·p, n) running states live in VMEM scratch across chunk steps (the
  recurrence the GPU implementation does with a separate kernel launch +
  global memory round trip).
- per grid step, once: ``C·Bᵀ`` (q, q); the block's cumulative log-decays
  ``cs = cumsum(dt·A)`` as rows (hb, q), one triangular product at fp32
  precision, and their transposes (q, hb), so each head's column terms are a
  lane slice. Then an unrolled loop over the block's heads forms each
  head's decay matrix L, ``scores = CB ∘ L``, its y, carried-state term and
  state update.
- ``hb`` is the largest divisor of the heads per group (as the kernel sees
  them: the local count under tensor parallelism) that makes a lane-legal
  block (hb·p a multiple of 128, or every head: hb = H) and whose VMEM
  working set — every block double-buffered, the (q, hb·p) activation
  blocks unpadded and the rest with (8, 128) tile padding, plus scratch and
  the (q, q) temporaries, all counted at fp32 — fits ``VMEM_BUDGET`` (24
  MiB) under the 32-MiB scoped limit both kernels ask for
  (:func:`ssd_head_block`, :func:`vmem_bytes`). mamba2-370m (32 heads, P
  64, N 128, q 128): one block of 32 heads in both passes, 128 grid steps a
  call at 8 x 2048 tokens; zamba2-1.2b (64 heads, N 64): one block of 64;
  zamba2-7b's tensor-parallel share (56 heads, N 64, chunk 256): 28 in
  both passes.

Backward follows the FlashAttention-2 recipe (PAPERS.md): the forward
additionally saves only the state *entering* each chunk — (B, nc, H·P, N),
an (nc, p, n) strip per (batch, head), the logsumexp analogue — and a
reversed-grid backward kernel recomputes the decay matrix ``L`` and the
intra-chunk scores tile by tile in VMEM to produce ``dx/ddt/dA/dB/dC``:

- the same grid and head blocks, sweeping chunks *last to first* (the index
  maps flip the chunk coordinate); the (hb·p, n) state cotangents ``dS``
  ride across steps in VMEM scratch, seeded by the final-state cotangent,
  propagated by ``dS_in = exp(cs[-1])·dS_out + (dy ∘ exp(cs))ᵀ·C``.
- per-chunk, all (q, q) quantities (L, scores, dscores) are recomputed from
  the streamed-in x/dt/B/C, never written to HBM. ``C·Bᵀ`` is formed once
  per grid step; ``Σ_h dcb_h`` accumulates in a (q, q) scratch, and
  ``(Σ dcb)·B`` and ``(Σ dcb)ᵀ·C`` run once per grid step. The per-head
  terms of dB, and dC's per lane tile, accumulate in the (q, n) output
  blocks themselves.
- dB and dC leave the kernel as (b, groups · head_blocks, l, n): summed over
  each block's heads inside the kernel, and over a group's head blocks
  outside (no sum at all when one block holds the group).
- the kernel emits ``dda`` (cotangent of the per-step log-decay ``dt·A``)
  and ``ddt`` as (hb, q) rows per grid step: each head's cotangent of cs is
  gathered into rows, and one suffix-sum product over all the block's heads
  turns them into ``dda``; outside, ``dA_h = Σ dda·dt``.

Every output element comes from the same dot products, over the same fp32
operands, as the per-head formulation (a lane mask adds exact zeros), and
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
import math
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
    temporaries of one head. A head's share of the (q, hb·p) activation
    blocks is unpadded: its p lanes sit beside its neighbours'."""
    head, rows, rates = (chunk * p * _F32, _tile_bytes(hb, chunk),
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


def _lane_legal(hb: int, p: int, heads: int) -> bool:
    """A (q, hb·p) block of the (B, L, H·P) view is a legal TPU block: a
    multiple of the 128 lanes, or all of them."""
    return (hb * p) % 128 == 0 or hb == heads


def has_lane_block(heads: int, groups: int, p: int) -> bool:
    """Some head block of one group is lane-legal (:func:`ssd_head_block`).
    If a whole group is not, no divisor of it is."""
    return _lane_legal(heads // groups, p, heads)


def ssd_head_block(heads_per_group: int, p: int, n: int, chunk: int,
                   backward: bool = False, groups: int = 1) -> int:
    """Heads one grid step holds: the largest lane-legal divisor of
    ``heads_per_group`` whose working set (:func:`vmem_bytes`) fits
    ``VMEM_BUDGET``; the smallest lane-legal one if none does."""
    legal = [hb for hb in range(heads_per_group, 0, -1)
             if heads_per_group % hb == 0
             and _lane_legal(hb, p, heads_per_group * groups)]
    if not legal:
        raise ValueError(
            f"no head block of {heads_per_group} heads of P {p} in "
            f"{groups} groups spans a multiple of 128 lanes")
    for hb in legal:
        if vmem_bytes(hb, p, n, chunk, backward) <= VMEM_BUDGET:
            return hb
    return legal[-1]


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


class _Tile:
    """Heads side by side in one lane tile of a block: lanes [start, start +
    k·p) of the (q, hb·p) activation blocks, and the same rows of the (hb·p,
    n) blocks of states. Head ``heads[j]`` owns the tile's j-th p-wide
    range."""

    def __init__(self, heads: range, p: int):
        self.heads, self.p = heads, p
        self.span = slice(heads[0] * p, (heads[-1] + 1) * p)
        width = len(heads) * p
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        self.row = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)

    def _spread(self, pick, pos):
        # head heads[j]'s value over positions [j·p, (j+1)·p) of ``pos``
        out = pick(self.heads[-1])
        for j in range(len(self.heads) - 2, -1, -1):
            out = jnp.where(pos < (j + 1) * self.p, pick(self.heads[j]), out)
        return out

    def lanes(self, cols):
        """(q, hb) columns, one a head -> each head's over its lanes."""
        return self._spread(lambda i: cols[:, i:i + 1], self.lane)

    def rows(self, vals):
        """(hb, 1) values, one a head -> each head's over its state rows."""
        return self._spread(lambda i: vals[i:i + 1, :], self.row)

    def select(self, j, v, other):
        """Head ``heads[j]``'s lanes from v, the others' from ``other``."""
        if len(self.heads) == 1:
            return v
        own = (self.lane >= j * self.p) & (self.lane < (j + 1) * self.p)
        return jnp.where(own, v, other)


def _tiles(hb: int, p: int):
    """A block's heads by lane tile: as many P-wide heads as fill 128 lanes
    (two at P 64), one where P does not divide 128."""
    k = math.gcd(hb, 128 // p) if 128 % p == 0 else 1
    return [_Tile(range(t, t + k), p) for t in range(0, hb, k)]


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *refs,
                n_chunks: int, hb: int, p: int):
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

    for tile in _tiles(hb, p):
        xd = x_ref[0, :, tile.span].astype(jnp.float32) * tile.lanes(d.dt_t)
        y = None
        for j, i in enumerate(tile.heads):
            # head i's lanes of (CB ∘ L_i) @ xd are its y
            yi = _dot(cb * d.L(i), xd)
            y = yi if y is None else tile.select(j, yi, y)

        # carried-state contribution: y += exp(cs) * C @ stateᵀ, the tile's
        # (k·p, n) states at once
        state = state_ref[tile.span]
        if enter_ref is not None:
            enter_ref[0, 0, tile.span] = state.astype(enter_ref.dtype)
        y = y + tile.lanes(d.exp_cs_t) * _dot_nt(cmat, state)

        # state recurrence: state' = exp(cs[-1])·state + Σ_q exp(cs[-1]-cs)·xdᵀB
        state_ref[tile.span] = (state * tile.rows(d.exp_end)
                                + _dot_tn(xd * tile.lanes(d.decay_t), bmat))
        y_ref[0, :, tile.span] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...].astype(state_out_ref.dtype)


def _ssd_forward(x, dt, A, Bm, Cm, chunk, interpret, save_enters: bool,
                 hb: int):
    """Returns (y (B,L,H,P) fp32, entering states (B,nc,H·P,N) fp32 or None,
    final_state (B,H,P,N) fp32). ``save_enters`` is True only under the VJP —
    forward-only calls skip the residual's HBM write. ``hb`` heads of one
    group share each grid step."""
    b, l, h, p = x.shape
    g, n = Bm.shape[1], Bm.shape[3]
    assert l % chunk == 0, (l, chunk)
    assert h % g == 0
    hpg = h // g
    assert hpg % hb == 0, (hpg, hb)
    nhb = hpg // hb                               # head blocks per group
    nc = l // chunk
    grid = (b, g * nhb, nc)

    # x and y as (B, L, H·P): head block j is lanes [j·hb·p, (j+1)·hb·p)
    act_spec = pl.BlockSpec((1, chunk, hb * p), lambda bi, j, ci: (bi, ci, j))
    out_specs = [
        act_spec,
        pl.BlockSpec((1, hb * p, n), lambda bi, j, ci: (bi, j, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, l, h * p), jnp.float32),
        jax.ShapeDtypeStruct((b, h * p, n), jnp.float32),
    ]
    if save_enters:
        out_specs.insert(1, pl.BlockSpec(
            (1, 1, hb * p, n), lambda bi, j, ci: (bi, ci, j, 0)))
        out_shape.insert(1, jax.ShapeDtypeStruct((b, nc, h * p, n),
                                                 jnp.float32))
    group_spec = pl.BlockSpec((1, 1, chunk, n),
                              lambda bi, j, ci: (bi, j // nhb, ci, 0))
    # dt (B, H, L) travels as (B, H/hb, hb, L) and A as (H/hb, hb, 1): a head
    # block's per-step rows, and its decay rates, each as one 2-D tile

    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, n_chunks=nc, hb=hb, p=p),
        grid=grid,
        in_specs=[
            act_spec,
            pl.BlockSpec((1, 1, hb, chunk), lambda bi, j, ci: (bi, j, 0, ci)),
            pl.BlockSpec((1, hb, 1), lambda bi, j, ci: (j, 0, 0)),
            group_spec,
            group_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb * p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_fwd",
    )(x.reshape(b, l, h * p), dt.reshape(b, h // hb, hb, l),
      A.astype(jnp.float32).reshape(-1, hb, 1), Bm, Cm)
    y, state = outs[0].reshape(b, l, h, p), outs[-1].reshape(b, h, p, n)
    return y, (outs[1] if save_enters else None), state


# ---------------------------------------------------------------------------
# backward


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, enter_ref, dy_ref,
                dsf_ref, dx_ref, ddt_ref, dda_ref, db_ref, dc_ref,
                dstate_ref, dcb_ref, dcs_ref, *, hb: int, p: int):
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

    for tile in _tiles(hb, p):
        x = x_ref[0, :, tile.span].astype(jnp.float32)      # (q, k·p)
        dy = dy_ref[0, :, tile.span].astype(jnp.float32)    # (q, k·p)
        sin = enter_ref[0, 0, tile.span].astype(jnp.float32)  # (k·p, n)
        ds_out = dstate_ref[tile.span]            # (k·p, n) cotangent of S_out
        dt_w, decay_w = tile.lanes(d.dt_t), tile.lanes(d.decay_t)
        exp_cs_w = tile.lanes(d.exp_cs_t)
        xd = x * dt_w

        # --- carried-state term: y_off = exp(cs) ∘ (C @ sinᵀ), the tile's
        # heads at once
        y_off = exp_cs_w * _dot_nt(cmat, sin)                     # (q, k·p)
        dy_e = dy * exp_cs_w
        dc_ref[0, 0] += _dot(dy_e, sin)
        dy_off = dy * y_off

        # --- state-recurrence term: S_out = exp(cs[-1])·sin + Σ ds_i·xd_i⊗B_i
        dxd_state = decay_w * _dot_nt(bmat, ds_out)               # (q, k·p)
        ds_sin = ds_out * sin

        dxd = dxd_state
        for j, i in enumerate(tile.heads):
            h = slice(i, i + 1)
            decay = d.decay_t[:, h]                               # (q, 1)
            L = d.L(i)
            scores = cb * L

            # --- intra-chunk "attention" term: y_diag = scores @ xd; a lane
            # mask on dy keeps head i's products, and head i's lanes of
            # scoresᵀ·dy are its dxd
            dscores = _dot_nt(tile.select(j, dy, 0.0), xd)        # (q, q)
            dxd_i = _dot_tn(scores, dy) + dxd_state
            dcb_ref[...] += dscores * L

            xd_ds = _dot(tile.select(j, xd, 0.0), ds_out)        # (q, n)
            db_ref[0, 0] += decay * xd_ds

            # --- cotangent of the cumulative log-decays cs. The terms summed
            # over lanes come out as a column: G's rows, exp(cs) in y_off,
            # exp(cs[-1]-cs) in the state update. G's column sums come out as
            # a row.
            G = dscores * scores                   # dL ∘ L, zero above diagonal
            t_qn = decay * xd_ds * bmat
            dcs_col = (G.sum(axis=1, keepdims=True)
                       + tile.select(j, dy_off, 0.0).sum(axis=1, keepdims=True)
                       - t_qn.sum(axis=1, keepdims=True))       # (q, 1)
            # cs = cumsum(da)  =>  dda_s = Σ_{t>=s} dcs_t. The column part's
            # suffix sums are taken here as a row; the row part is summed for
            # all heads after the loop. The two cs[-1] contributions (Σt from
            # decay, the exp(cs[-1])·sin term) land on every entry, so they
            # join the total.
            last = (t_qn.sum(keepdims=True)
                    + d.exp_end[h, :]
                    * ds_sin[j * p:(j + 1) * p].sum(keepdims=True))
            dcs_ref[0, h, :] = (jnp.where(suffix, dcs_col, 0.0)
                                .sum(axis=0, keepdims=True) + last)
            dcs_ref[1, h, :] = -G.sum(axis=0, keepdims=True)
            dxd_x = tile.select(j, dxd_i * x, 0.0).sum(axis=1, keepdims=True)
            dcs_ref[2, h, :] = jnp.where(eye, dxd_x, 0.0).sum(axis=0,
                                                              keepdims=True)
            dxd = tile.select(j, dxd_i, dxd)

        # propagate the state cotangents to the previous chunk
        dstate_ref[tile.span] = (tile.rows(d.exp_end) * ds_out
                                 + _dot_tn(dy_e, cmat))
        dx_ref[0, :, tile.span] = (dxd * dt_w).astype(dx_ref.dtype)

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
    b, l, h, p = x.shape
    grp, n = Bm.shape[1], Bm.shape[3]
    hb = ssd_head_block(h // grp, p, n, chunk, backward=True, groups=grp)
    return _ssd_backward_blocks(x, dt, A, Bm, Cm, enters, dy, dsf, chunk,
                                interpret, hb)


def _ssd_backward_blocks(x, dt, A, Bm, Cm, enters, dy, dsf, chunk, interpret,
                         hb: int):
    b, l, h, p = x.shape
    grp, n = Bm.shape[1], Bm.shape[3]
    hpg = h // grp
    assert hpg % hb == 0, (hpg, hb)
    nhb = hpg // hb
    nc = l // chunk
    grid = (b, grp * nhb, nc)
    rev = nc - 1   # index maps sweep chunks last -> first

    # x, dy and dx as (B, L, H·P), the entering states as (B, nc, H·P, N)
    act_spec = pl.BlockSpec((1, chunk, hb * p),
                            lambda bi, j, ci: (bi, rev - ci, j))
    enter_spec = pl.BlockSpec((1, 1, hb * p, n),
                              lambda bi, j, ci: (bi, rev - ci, j, 0))
    rows_spec = pl.BlockSpec((1, 1, hb, chunk),
                             lambda bi, j, ci: (bi, j, 0, rev - ci))
    group_spec = pl.BlockSpec((1, 1, chunk, n),
                              lambda bi, j, ci: (bi, j // nhb, rev - ci, 0))
    # dB/dC blocks: one per head block (axis 1 of size grp · nhb)
    block_spec = pl.BlockSpec((1, 1, chunk, n),
                              lambda bi, j, ci: (bi, j, rev - ci, 0))
    rows_shape = jax.ShapeDtypeStruct((b, grp * nhb, hb, l), jnp.float32)

    dx, ddt, dda, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p),
        grid=grid,
        in_specs=[
            act_spec,
            rows_spec,
            pl.BlockSpec((1, hb, 1), lambda bi, j, ci: (j, 0, 0)),
            group_spec,
            group_spec,
            enter_spec,
            act_spec,
            pl.BlockSpec((1, hb * p, n), lambda bi, j, ci: (bi, j, 0)),
        ],
        out_specs=[act_spec, rows_spec, rows_spec, block_spec, block_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, h * p), jnp.float32),
            rows_shape,
            rows_shape,
            jax.ShapeDtypeStruct((b, grp * nhb, l, n), jnp.float32),
            jax.ShapeDtypeStruct((b, grp * nhb, l, n), jnp.float32),
        ],
        # dS per head; Σ dcb; rows per head of the cs cotangent: the column
        # part's suffix sums, the row part, and Σ_p dxd ∘ x for ddt
        scratch_shapes=[pltpu.VMEM((hb * p, n), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32),
                        pltpu.VMEM((3, hb, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="ssd_bwd",
    )(x.reshape(b, l, h * p), dt.reshape(b, h // hb, hb, l),
      A.astype(jnp.float32).reshape(-1, hb, 1), Bm, Cm, enters,
      dy.astype(jnp.float32).reshape(b, l, h * p),
      dsf.astype(jnp.float32).reshape(b, h * p, n))
    ddt, dda = ddt.reshape(b, h, l), dda.reshape(b, h, l)

    # block sums of B/C gradients -> sum a group's head blocks onto the shared
    # projection (GQA trick); a no-op reshape when one block holds the group
    dB = db.reshape(b, grp, nhb, l, n).sum(axis=2).astype(Bm.dtype)
    dC = dc.reshape(b, grp, nhb, l, n).sum(axis=2).astype(Cm.dtype)
    # da = dt·A  =>  dA_h = Σ_{b,l} dda·dt (cheap elementwise reduction in XLA)
    dA = jnp.einsum("bhl,bhl->h", dda, dt.astype(jnp.float32)).astype(A.dtype)
    return (dx.reshape(b, l, h, p).astype(x.dtype), ddt.astype(dt.dtype), dA,
            dB, dC)


# ---------------------------------------------------------------------------
# custom_vjp plumbing


def _forward(x, dt, A, Bm, Cm, chunk, interpret, save_enters: bool):
    grp = Bm.shape[1]
    hb = ssd_head_block(x.shape[2] // grp, x.shape[3], Bm.shape[3], chunk,
                        groups=grp)
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
    x: jax.Array,        # (B, L, H, P), the model's layout
    dt: jax.Array,       # (B, H, L)
    A: jax.Array,        # (H,) negative decay rates
    Bm: jax.Array,       # (B, G, L, N)
    Cm: jax.Array,       # (B, G, L, N)
    *,
    chunk: int = 128,
    interpret: Optional[bool] = None,   # None -> compiled on TPU, interpreted elsewhere
):
    """Fused differentiable SSD. Returns (y (B, L, H, P) fp32,
    final_state (B, H, P, N) fp32)."""
    return _ssd(x, dt, A, Bm, Cm, int(chunk), resolve_interpret(interpret))
