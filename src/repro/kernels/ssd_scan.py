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

- grid = (batch, heads, n_chunks) with the chunk dim minor: the (p, n) running
  state lives in VMEM scratch across chunk steps (the recurrence the GPU
  implementation does with a separate kernel launch + global memory round
  trip).
- B/C are per-group; the index_map maps head -> group (h // heads_per_group),
  so grouped state projections are never repeated in HBM.
- VMEM working set per step ≈ x(q·p) + B,C(q·n) + L(q·q) + state(p·n)
  ≈ 128·(64+128+128+128)·4 ≈ 230 KB — far under budget, with q=chunk=128
  MXU-aligned.

Backward follows the FlashAttention-2 recipe (PAPERS.md): the forward
additionally saves only the state *entering* each chunk — an (nc, p, n) strip
per (batch, head), the logsumexp analogue — and a reversed-grid backward
kernel recomputes the decay matrix ``L`` and the intra-chunk scores tile by
tile in VMEM to produce ``dx/ddt/dA/dB/dC``:

- grid = (batch, heads, n_chunks) sweeping chunks *last to first* (the index
  maps flip the chunk coordinate); the state cotangent ``dS`` rides across
  steps in VMEM scratch, seeded by the final-state cotangent, propagated by
  ``dS_in = exp(cs[-1])·dS_out + (dy ∘ exp(cs))ᵀ·C``.
- per-chunk, all (q, q) quantities (L, scores, dscores) are recomputed from
  the streamed-in x/dt/B/C, never written to HBM.
- the kernel emits ``dda`` (cotangent of the per-step log-decay ``dt·A``)
  alongside ``ddt``; outside, ``dA_h = Σ dda·dt`` and the per-head dB/dC are
  group-summed (the GQA trick from the attention backward).

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

# Per-step vectors (dt, ddt, dda) travel as (B, H, 1, L): a (1, chunk) block
# is a lane-major row the TPU tiling accepts. The per-head decay rate A is a
# scalar per grid step, so the whole (H,) vector sits in SMEM.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _lower_tri(q: int, upper: bool = False):
    """(q, q) mask of i >= j (``upper``: i <= j)."""
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return i <= j if upper else i >= j


def _cumsum(v, reverse: bool = False):
    """Inclusive prefix (``reverse``: suffix) sum of a (q,) vector as a masked
    row reduction: the TPU kernel lowering has no cumsum primitive."""
    tri = _lower_tri(v.shape[0], upper=reverse)
    return jnp.where(tri, v[None, :], 0.0).sum(axis=1)


def _chunk_decay(dt, a):
    """Shared per-chunk decay math: (cs, cs_end, L) with L strictly in
    registers/VMEM. ``cs_end`` is cs[-1], taken as a sum because the kernel
    lowering has no dynamic slice."""
    da = dt * a                                   # (q,) log-decays
    cs = _cumsum(da)                              # (q,)
    q = cs.shape[0]
    li = cs[:, None] - cs[None, :]
    tri = _lower_tri(q)
    # mask *before* exp: the masked (upper) entries hold positive log-decays
    # that could overflow fp32 for long chunks / large dt·|A|
    L = jnp.exp(jnp.where(tri, li, -jnp.inf))
    return cs, da.sum(), L


# ---------------------------------------------------------------------------
# forward


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *refs,
                n_chunks: int):
    # refs = (enter_ref?, state_out_ref, state_ref): the entering-states
    # residual output only exists when the VJP will need it — forward-only
    # calls (eval/decode) skip that extra HBM write entirely
    enter_ref = refs[0] if len(refs) == 3 else None
    state_out_ref, state_ref = refs[-2], refs[-1]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (q, p)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (q,)
    a = a_ref[pl.program_id(1)]                   # scalar A (negative), SMEM
    bmat = b_ref[0, 0].astype(jnp.float32)       # (q, n)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (q, n)

    xd = x * dt[:, None]
    cs, cs_end, L = _chunk_decay(dt, a)

    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot(scores, xd, preferred_element_type=jnp.float32)

    # carried-state contribution: y += exp(cs) * C @ state  (state: (p, n))
    state = state_ref[...]
    if enter_ref is not None:
        enter_ref[0, 0, 0] = state.astype(enter_ref.dtype)  # backward residual
    y = y + jnp.exp(cs)[:, None] * jax.lax.dot_general(
        cmat, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state recurrence: state' = exp(cs[-1])·state + Σ_q exp(cs[-1]-cs)·xdᵀB
    decay_states = jnp.exp(cs_end - cs)           # (q,)
    state_new = (state * jnp.exp(cs_end)
                 + jax.lax.dot_general(xd * decay_states[:, None], bmat,
                                       (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32))
    state_ref[...] = state_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0, 0] = state_new.astype(state_out_ref.dtype)


def _ssd_forward(x, dt, A, Bm, Cm, chunk, interpret, save_enters: bool):
    """Returns (y (B,H,L,P) fp32, entering states (B,H,nc,P,N) fp32 or None,
    final_state (B,H,P,N) fp32). ``save_enters`` is True only under the VJP —
    forward-only calls skip the residual's HBM write."""
    b, h, l, p = x.shape
    g, n = Bm.shape[1], Bm.shape[3]
    assert l % chunk == 0, (l, chunk)
    assert h % g == 0
    hpg = h // g
    nc = l // chunk
    grid = (b, h, nc)

    out_specs = [
        pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
        pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, l, p), jnp.float32),
        jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
    ]
    if save_enters:
        out_specs.insert(1, pl.BlockSpec(
            (1, 1, 1, p, n), lambda bi, hi, ci: (bi, hi, ci, 0, 0)))
        out_shape.insert(1, jax.ShapeDtypeStruct((b, h, nc, p, n),
                                                 jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bi, hi, ci: (bi, hi, 0, ci)),
            _SMEM,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, g_=hpg: (bi, hi // g_, ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, g_=hpg: (bi, hi // g_, ci, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_fwd",
    )(x, dt[:, :, None, :], A.astype(jnp.float32), Bm, Cm)
    if save_enters:
        return outs[0], outs[1], outs[2]
    return outs[0], None, outs[1]


# ---------------------------------------------------------------------------
# backward


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, enter_ref, dy_ref,
                dsf_ref, dx_ref, ddt_ref, dda_ref, db_ref, dc_ref,
                dstate_ref):
    ci = pl.program_id(2)   # reversed sweep: index maps flip to chunk nc-1-ci

    @pl.when(ci == 0)
    def _init():
        # seed with the final-state cotangent
        dstate_ref[...] = dsf_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (q, p)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)     # (q,)
    a = a_ref[pl.program_id(1)]
    bmat = b_ref[0, 0].astype(jnp.float32)       # (q, n)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (q, n)
    sin = enter_ref[0, 0, 0].astype(jnp.float32)  # (p, n) state entering chunk
    dy = dy_ref[0, 0].astype(jnp.float32)        # (q, p)
    ds_out = dstate_ref[...]                      # (p, n) cotangent of S_out

    xd = x * dt[:, None]
    cs, cs_end, L = _chunk_decay(dt, a)
    exp_cs = jnp.exp(cs)
    decay_states = jnp.exp(cs_end - cs)           # (q,)

    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (q, q)
    scores = cb * L

    # --- intra-chunk "attention" term: y_diag = scores @ xd
    dscores = jax.lax.dot_general(dy, xd, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (q, q)
    dxd = jax.lax.dot_general(scores, dy, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)      # (q, p)
    dcb = dscores * L

    # --- carried-state term: y_off = exp(cs) ∘ (C @ sinᵀ)
    y_off = exp_cs[:, None] * jax.lax.dot_general(
        cmat, sin, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                            # (q, p)
    dy_e = dy * exp_cs[:, None]
    dc = (jax.lax.dot(dy_e, sin, preferred_element_type=jnp.float32)
          + jax.lax.dot(dcb, bmat, preferred_element_type=jnp.float32))

    # --- state-recurrence term: S_out = exp(cs[-1])·sin + Σ ds_i·xd_i⊗B_i
    xd_ds = jax.lax.dot(xd, ds_out, preferred_element_type=jnp.float32)  # (q, n)
    dxd = dxd + decay_states[:, None] * jax.lax.dot_general(
        bmat, ds_out, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    db = (decay_states[:, None] * xd_ds
          + jax.lax.dot_general(dcb, cmat, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32))

    # --- cotangent of the cumulative log-decays cs
    G = dscores * scores                           # dL ∘ L, zero above diagonal
    dcs = G.sum(axis=1) - G.sum(axis=0)
    dcs = dcs + (dy * y_off).sum(axis=-1)          # exp(cs) factor in y_off
    t_qn = decay_states[:, None] * xd_ds * bmat    # exp(cs[-1]-cs) factor
    dcs = dcs - t_qn.sum(axis=-1)
    # the two cs[-1] contributions (Σt from decay_states, exp(cs[-1])·sin term)
    # land on every entry of the reverse cumsum below, so fold them into the
    # total instead of scattering into index q-1. Both totals reduce 2-D
    # tiles: the kernel lowering cannot broadcast the scalar sum of a
    # row-reduced vector back over a vector.
    last = t_qn.sum() + jnp.exp(cs_end) * (ds_out * sin).sum()

    # cs = cumsum(da)  =>  dda_i = Σ_{j>=i} dcs_j  (+ last, which sits at j=q-1)
    dda = _cumsum(dcs, reverse=True) + last

    ddt = dda * a + (dxd * x).sum(axis=-1)
    dx = dxd * dt[:, None]

    # propagate the state cotangent to the previous chunk
    dstate_ref[...] = (jnp.exp(cs_end) * ds_out
                       + jax.lax.dot_general(dy_e, cmat, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32))

    dx_ref[0, 0] = dx.astype(dx_ref.dtype)
    ddt_ref[0, 0, 0] = ddt.astype(ddt_ref.dtype)
    dda_ref[0, 0, 0] = dda.astype(dda_ref.dtype)
    db_ref[0, 0] = db.astype(db_ref.dtype)
    dc_ref[0, 0] = dc.astype(dc_ref.dtype)


def _ssd_backward(chunk, interpret, res, g):
    x, dt, A, Bm, Cm, enters = res
    dy, dsf = g
    b, h, l, p = x.shape
    grp, n = Bm.shape[1], Bm.shape[3]
    hpg = h // grp
    nc = l // chunk
    grid = (b, h, nc)
    rev = nc - 1   # index maps sweep chunks last -> first
    row_spec = pl.BlockSpec((1, 1, 1, chunk),
                            lambda bi, hi, ci, r=rev: (bi, hi, 0, r - ci))

    dx, ddt, dda, db, dc = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci, r=rev: (bi, hi, r - ci, 0)),
            row_spec,
            _SMEM,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, g_=hpg, r=rev: (bi, hi // g_, r - ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, g_=hpg, r=rev: (bi, hi // g_, r - ci, 0)),
            pl.BlockSpec((1, 1, 1, p, n),
                         lambda bi, hi, ci, r=rev: (bi, hi, r - ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci, r=rev: (bi, hi, r - ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p),
                         lambda bi, hi, ci, r=rev: (bi, hi, r - ci, 0)),
            row_spec,
            row_spec,
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=rev: (bi, hi, r - ci, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci, r=rev: (bi, hi, r - ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, l), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, l), jnp.float32),
            jax.ShapeDtypeStruct((b, h, l, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, l, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_bwd",
    )(x, dt[:, :, None, :], A.astype(jnp.float32), Bm, Cm, enters,
      dy.astype(jnp.float32), dsf.astype(jnp.float32))
    ddt, dda = ddt[:, :, 0], dda[:, :, 0]

    # per-head B/C gradients -> group-sum onto the shared projection (GQA trick)
    dB = db.reshape(b, grp, hpg, l, n).sum(axis=2).astype(Bm.dtype)
    dC = dc.reshape(b, grp, hpg, l, n).sum(axis=2).astype(Cm.dtype)
    # da = dt·A  =>  dA_h = Σ_{b,l} dda·dt (cheap elementwise reduction in XLA)
    dA = jnp.einsum("bhl,bhl->h", dda, dt.astype(jnp.float32)).astype(A.dtype)
    return dx.astype(x.dtype), ddt.astype(dt.dtype), dA, dB, dC


# ---------------------------------------------------------------------------
# custom_vjp plumbing


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, A, Bm, Cm, chunk, interpret):
    y, _, state = _ssd_forward(x, dt, A, Bm, Cm, chunk, interpret,
                               save_enters=False)
    return y, state


def _ssd_fwd(x, dt, A, Bm, Cm, chunk, interpret):
    y, enters, state = _ssd_forward(x, dt, A, Bm, Cm, chunk, interpret,
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
