"""Unified parallel block executor + context-parallel (cp) sequence axis.

Before this module, every family's forward wiring existed three times: the
GSPMD/dense bodies (``models/families.py``), the overlap-TP twins
(``attn_sublayer_tp`` / ``mlp_sublayer_tp`` / ``moe_block_tp`` /
``ssm_block_tp``) and the pipeline ``stage_fn`` plumbing — O(families × paths)
surface for every new parallel axis. The executor collapses them: each family
defines its math **once** (``attn_block`` / ``mlp_block_ex`` /
``moe_block_ex`` / ``ssm_block_ex``), parameterized by a
:class:`ParallelContext` that decides gather/ring/shard placement:

- ``ctx.tp`` (model-axis ring, PR 4 conventions): column GEMMs fuse the
  sequence all-gather into ``all_gather_matmul`` ring ticks, row GEMMs
  ring-reduce-scatter, activations stay ``(B, S/tp, d)`` between blocks.
  ``ctx.tp is None`` is the local/GSPMD mode — identity collectives, the
  same ops the annotation-sharded baseline runs.
- ``ctx.cp`` (context-parallel ring, survey §4.1.4): the *sequence* itself is
  sharded over a dedicated ``cp`` mesh axis end to end, so no device ever
  holds the full context — the long-context regime where attention
  activation memory, not weights, dominates. Attention under ``cp`` runs

  * ``cp_impl="gather"`` — all-gather K/V over the cp axis (contiguous
    chunks, exact, O(S) KV per device), or
  * ``cp_impl="ring"`` — ring attention: K/V chunks ``ppermute`` around the
    cp ring while the existing flash kernel runs as the inner tile
    (``dispatch_attention_lse``); per-chunk ``(out, lse)`` partials merge
    exactly via the chunked-softmax identity
    ``lse = log Σ exp(lse_c)``, ``o = Σ exp(lse_c − lse) o_c``. Ownership is
    **zigzag** load-balanced (rank ``i`` holds sub-chunks ``i`` and
    ``2·cp−1−i`` of ``2·cp``), so the causal triangle spreads evenly; each
    (q-sub, k-sub) pair is statically one of {fully-masked, diagonal-causal,
    full-attend}, selected by a collective-free ``lax.switch`` (the
    ``ppermute``s stay outside, uniform across ranks — the PR 4 rule). The
    backward is a ``jax.custom_vjp`` **reversed** ring: dk/dv accumulators
    ride around with their KV chunk and arrive home after a final
    ``ppermute``; each chunk's gradients are computed against the globally
    merged ``(lse, Δ)`` (``dispatch_attention_chunk_bwd``).

  The Mamba2 SSD scan composes by passing **per-chunk entering states**
  around the cp ring: every rank scans its local chunk from a zero state
  through the usual dispatcher (the fused kernel stays eligible), the
  (state, total-decay) pair chains across ranks in ``cp−1`` masked
  ``ppermute`` steps, and the carried-in state's contribution is a closed-
  form rank-local einsum (the recurrence is linear in its initial state).
  Causal convs exchange a (d_conv−1)-token halo with the left neighbour.
  MoE routes on **local** sequence shards with batch-global aux statistics
  (the density/proxy sums ``psum`` over data × cp before the mean).

:func:`make_executor_loss_fn` assembles the whole training-path loss for any
tp × cp combination (``train.tensor_parallel.make_tp_loss_fn`` is now a thin
alias); ``train/pipeline.py`` reuses the same layer bodies inside its 1F1B
ticks, so CP × TP × PP composes. Numerical contract, tested in
tests/test_context_parallel.py: ring == gather == single-device loss/grads to
≤ 1e-6 for dense, MoE (no-drop capacity) and Mamba2.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import sharding as shardlib
from repro.core.compat import shard_map
from repro.core.config import Family, ModelConfig, ParallelPlan
from repro.ft.inject import taint
from repro.kernels.dispatch import (dispatch_attention,
                                    dispatch_attention_chunk_bwd,
                                    dispatch_attention_lse, dispatch_ep_a2a,
                                    dispatch_ssd_scan, select_cp_impl,
                                    select_ep_impl)
from repro.models.layers import NEG_INF, qkv_proj, rms_norm, rope
from repro.train.tensor_parallel import (RingCtx, all_gather_matmul,
                                         matmul_reduce_scatter,
                                         ring_all_gather, ring_reduce_scatter,
                                         tp_embed, tp_head_nll)


def _identity(x):
    return x


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """How a family block executes on the mesh.

    ``tp``/``cp`` are the model-axis and context-axis rings (``None`` = that
    axis is off); ``cp_impl`` is the *resolved* attention mode
    ("gather" | "ring"). ``ep`` is the folded expert ring of MoE parallel
    folding: the same cp × model devices re-read as one flat expert axis
    (``ep.axis`` is an axis *tuple* when both are engaged; in the ep-only
    placement it is "model" and ``cp`` is the attention ring over that same
    axis), with ``ep_impl`` the resolved a2a mode ("blocking" | "overlap").
    ``cx``/``cq``/``ckv`` are the GSPMD activation constrainers of the local
    mode (identity elsewhere); ``mesh``/``batch_axes``/``n_dp`` feed the
    batch-global MoE aux reductions.
    """
    tp: Optional[RingCtx] = None
    cp: Optional[RingCtx] = None
    cp_impl: str = "ring"
    ep: Optional[RingCtx] = None
    ep_impl: str = "overlap"
    batch_axes: Tuple[str, ...] = ()
    n_dp: int = 1
    mesh: Optional[Mesh] = None
    cx: Callable = _identity
    cq: Callable = _identity
    ckv: Callable = _identity

    @property
    def n_tp(self) -> int:
        return self.tp.size if self.tp is not None else 1

    @property
    def n_cp(self) -> int:
        return self.cp.size if self.cp is not None else 1

    @property
    def n_ep(self) -> int:
        return self.ep.size if self.ep is not None else 1

    @property
    def aux_axes(self) -> Tuple[str, ...]:
        """Axes the MoE aux statistics reduce over (batch-global aux).

        Under EP the router runs shard-local on every fold rank's own
        sequence chunk, so the statistics reduce over the whole fold (which
        subsumes the cp axis when engaged); without EP, routing is
        model-replicated (the tp path re-gathers the sequence) and only the
        data × cp token sharding needs completing."""
        axes = tuple(self.batch_axes)
        if self.ep is not None:
            fold = self.ep.axis if isinstance(self.ep.axis, tuple) \
                else (self.ep.axis,)
            return axes + fold
        if self.cp is not None:
            axes = axes + (self.cp.axis,)
        return axes

    @property
    def n_rep(self) -> int:
        """Token-count multiplier completing local counts to global ones."""
        if self.ep is not None:
            return self.n_dp * self.ep.size
        return self.n_dp * self.n_cp


def local_context(mesh=None, batch_axes: Tuple[str, ...] = (),
                  cx=_identity, cq=_identity, ckv=_identity) -> ParallelContext:
    """The GSPMD/single-device mode: identity collectives, XLA owns layout.

    The plan is *not* part of the context — it threads separately into the
    layer builders (``decoder_layer(ctx, cfg, plan, ...)``)."""
    return ParallelContext(tp=None, cp=None, batch_axes=tuple(batch_axes or ()),
                           mesh=mesh, cx=cx, cq=cq, ckv=ckv)


def _tp_index(ctx: ParallelContext):
    return jax.lax.axis_index(ctx.tp.axis) if ctx.tp is not None else 0


def _cp_index(ctx: ParallelContext):
    return jax.lax.axis_index(ctx.cp.axis) if ctx.cp is not None else 0


def _slice_tp(ctx: ParallelContext, p, n_loc: int, axis: int = 0):
    """This rank's chunk of a model-replicated leaf (identity without tp)."""
    if ctx.tp is None:
        return p
    return jax.lax.dynamic_slice_in_dim(p, _tp_index(ctx) * n_loc, n_loc, axis)


def _proj_cols(ctx: ParallelContext, x, ws):
    """Column GEMMs: the executor's gather decision.

    tp: ring all-gather fused into the GEMM ticks — ``x`` (B, S/tp, d) in,
    ``outs[i]`` (B, S, f_loc) out (plus the gathered ``x``, a free ring
    by-product). local: plain matmuls, ``x`` already whole.
    """
    if ctx.tp is not None:
        return all_gather_matmul(ctx.tp, x, ws)
    return tuple(x @ w for w in ws), x


def _proj_rows(ctx: ParallelContext, h, w):
    """Row GEMM: ring reduce-scatter under tp, plain matmul locally."""
    if ctx.tp is not None:
        return matmul_reduce_scatter(ctx.tp, h, w)
    return h @ w


# ---------------------------------------------------------------------------
# context-parallel sequence layout (zigzag)


def zigzag_permutation(seq: int, cp: int) -> np.ndarray:
    """Global-position permutation for the zigzag ring layout.

    The sequence splits into ``2·cp`` contiguous sub-chunks; rank ``r`` owns
    sub-chunks ``r`` and ``2·cp−1−r``, so every rank's causal-attention work
    (the number of attended (q, k) pairs) is identical — the load-balancing
    trick ring attention needs because the causal triangle makes contiguous
    chunks wildly uneven. ``tokens[:, perm]`` reorders a batch so that a
    plain contiguous ``P(..., "cp")`` shard_map spec hands each rank its
    zigzag pair; everything position-wise (embedding, rope with explicit
    positions, per-token loss) is permutation-invariant.
    """
    assert seq % (2 * cp) == 0, (seq, cp)
    lc = seq // (2 * cp)
    parts = []
    for r in range(cp):
        parts.append(np.arange(r * lc, (r + 1) * lc))
        parts.append(np.arange((2 * cp - 1 - r) * lc, (2 * cp - r) * lc))
    return np.concatenate(parts)


def zigzag_pair_counts(seq: int, cp: int) -> np.ndarray:
    """Attended causal (q, k) pairs per rank under the zigzag layout (static
    accounting used by the load-balance unit tests)."""
    perm = zigzag_permutation(seq, cp)
    s_loc = seq // cp
    counts = np.zeros((cp,), np.int64)
    for r in range(cp):
        q_pos = perm[r * s_loc:(r + 1) * s_loc]
        counts[r] = int(np.sum(q_pos + 1))    # each query attends pos+1 keys
    return counts


def cp_local_positions(ctx: ParallelContext, s_loc: int):
    """Global positions of this rank's (cp-local) sequence chunk.

    Contiguous layout (gather / SSM): ``[idx·s_loc, (idx+1)·s_loc)``.
    Zigzag (ring attention): the concatenation of the rank's two sub-chunk
    ranges. Without cp: ``arange(s_loc)``.
    """
    if ctx.cp is None:
        return jnp.arange(s_loc)
    idx = _cp_index(ctx)
    if ctx.cp_impl != "ring":
        return idx * s_loc + jnp.arange(s_loc)
    lc = s_loc // 2
    cp = ctx.cp.size
    return jnp.concatenate([idx * lc + jnp.arange(lc),
                            (2 * cp - 1 - idx) * lc + jnp.arange(lc)])


# ---------------------------------------------------------------------------
# ring attention (zigzag, lse-merging, custom-VJP reversed ring)


@dataclasses.dataclass(frozen=True)
class RingAttnParams:
    """Static ring-attention parameters (hashable: rides nondiff_argnums)."""
    ctx: RingCtx
    softcap: float = 0.0
    scale: Optional[float] = None
    impl: str = "auto"
    block_size: int = 1024
    block_q: int = 128
    block_k: int = 128


def _merge_lse(o, lse, o_c, lse_c):
    """Exact chunked-softmax merge of normalized partials (fp32)."""
    m = jnp.maximum(lse, lse_c)
    w1 = jnp.exp(lse - m)
    w2 = jnp.exp(lse_c - m)
    tot = w1 + w2
    o_new = (o * w1[..., None] + o_c.astype(jnp.float32) * w2[..., None]) / \
        tot[..., None]
    return o_new, m + jnp.log(tot)


def _pair_attention(rp: RingAttnParams, q, k, v, rel):
    """One (q-sub, k-sub) tile of the ring forward.

    ``rel`` (traced) is the q-sub-chunk id minus the k-sub-chunk id; zigzag
    alignment makes the mask statically one of three cases, so the flash
    kernel (compile-time masks) stays eligible inside a collective-free
    ``lax.switch``: rel < 0 → fully masked, rel == 0 → diagonal causal,
    rel > 0 → full attend.
    """
    b, lc, hq, hd = q.shape

    def masked(_q, _k, _v):
        return (jnp.zeros((b, lc, hq, hd), _q.dtype),
                jnp.full((b, lc, hq), NEG_INF, jnp.float32))

    def diag(q_, k_, v_):
        return dispatch_attention_lse(
            q_, k_, v_, impl=rp.impl, causal=True, softcap=rp.softcap,
            scale=rp.scale, block_size=rp.block_size, block_q=rp.block_q,
            block_k=rp.block_k)

    def full(q_, k_, v_):
        return dispatch_attention_lse(
            q_, k_, v_, impl=rp.impl, causal=False, softcap=rp.softcap,
            scale=rp.scale, block_size=rp.block_size, block_q=rp.block_q,
            block_k=rp.block_k)

    case = (jnp.clip(jnp.sign(rel), -1, 1) + 1).astype(jnp.int32)
    return jax.lax.switch(case, [masked, diag, full], q, k, v)


def _pair_grads(rp: RingAttnParams, q, k, v, do, lse, delta, rel):
    """One (q-sub, k-sub) tile of the ring backward, against the merged
    (lse, Δ) — same three static mask cases as the forward."""
    hkv = k.shape[2]

    def masked(*_):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(k.shape[:2] + (hkv, q.shape[-1]), jnp.float32),
                jnp.zeros(v.shape[:2] + (hkv, v.shape[-1]), jnp.float32))

    def chunk(causal):
        def f(q_, k_, v_, do_, lse_, delta_):
            dq, dk, dv = dispatch_attention_chunk_bwd(
                q_, k_, v_, do_, lse_, delta_, impl=rp.impl, causal=causal,
                softcap=rp.softcap, scale=rp.scale, block_q=rp.block_q,
                block_k=rp.block_k)
            return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                    dv.astype(jnp.float32))
        return f

    case = (jnp.clip(jnp.sign(rel), -1, 1) + 1).astype(jnp.int32)
    return jax.lax.switch(case, [masked, chunk(True), chunk(False)],
                          q, k, v, do, lse, delta)


def _sub_ids(rp: RingAttnParams, owner):
    cp = rp.ctx.size
    return (owner, 2 * cp - 1 - owner)


def _ring_attn_fwd_impl(rp: RingAttnParams, q, k, v):
    """cp-step ring: per step each rank attends its 2 q-subs against the
    visiting KV chunk's 2 k-subs (4 static-mask tiles), merging (o, lse)
    online; the KV pair ppermutes forward between steps (uniform, outside
    the switches)."""
    cp = rp.ctx.size
    idx = jax.lax.axis_index(rp.ctx.axis)
    b, s_loc, hq, hd = q.shape
    assert s_loc % 2 == 0, \
        f"ring cp needs an even per-rank chunk (2 zigzag sub-chunks), got {s_loc}"
    lc = s_loc // 2
    q_subs = (q[:, :lc], q[:, lc:])
    q_ids = _sub_ids(rp, idx)
    o = [jnp.zeros((b, lc, hq, hd), jnp.float32) for _ in range(2)]
    lse = [jnp.full((b, lc, hq), NEG_INF, jnp.float32) for _ in range(2)]
    k_cur, v_cur = k, v
    for step in range(cp):
        src = (idx - step) % cp
        k_ids = _sub_ids(rp, src)
        for qi in range(2):
            for ki in range(2):
                o_c, lse_c = _pair_attention(
                    rp, q_subs[qi], k_cur[:, ki * lc:(ki + 1) * lc],
                    v_cur[:, ki * lc:(ki + 1) * lc], q_ids[qi] - k_ids[ki])
                o[qi], lse[qi] = _merge_lse(o[qi], lse[qi], o_c, lse_c)
        if step < cp - 1:
            # fault seam: the visiting KV pair as it lands from the ring
            # hop — a corrupted link payload lands here (ft/inject)
            k_cur = taint("cp.ring.kv", jax.lax.ppermute(
                k_cur, rp.ctx.axis, rp.ctx.perm_fwd))
            v_cur = jax.lax.ppermute(v_cur, rp.ctx.axis, rp.ctx.perm_fwd)
    out = jnp.concatenate(o, axis=1).astype(q.dtype)
    return out, jnp.concatenate(lse, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ring_attention(rp: RingAttnParams, q, k, v):
    """Zigzag ring attention over the cp axis.

    ``q``/``k``/``v``: (B, S/cp, H, hd) — this rank's zigzag pair of
    sub-chunks, rope already applied with true global positions. Exact
    causal attention over the full sequence; no device ever materializes
    (B, S, ·) K/V or scores. The VJP runs the mirrored **reversed** ring:
    dk/dv accumulators ride with their KV chunk and a final ppermute brings
    them home, each chunk's gradients computed against the globally merged
    (lse, Δ) — so the per-chunk flash backward kernels compose unchanged.
    """
    o, _ = _ring_attn_fwd_impl(rp, q, k, v)
    return o


def _ring_attn_fwd(rp, q, k, v):
    o, lse = _ring_attn_fwd_impl(rp, q, k, v)
    return o, (q, k, v, o, lse)


def _ring_attn_bwd(rp, res, g):
    q, k, v, o, lse = res
    cp = rp.ctx.size
    idx = jax.lax.axis_index(rp.ctx.axis)
    b, s_loc, hq, hd = q.shape
    hkv = k.shape[2]
    lc = s_loc // 2
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)      # (B, S/cp, Hq)
    q_ids = _sub_ids(rp, idx)
    dq = jnp.zeros((b, s_loc, hq, hd), jnp.float32)
    k_cur, v_cur = k, v
    dk_acc = jnp.zeros((b, s_loc, hkv, hd), jnp.float32)
    dv_acc = jnp.zeros((b, s_loc, hkv, hd), jnp.float32)
    for step in range(cp):
        src = (idx + step) % cp           # reversed ring direction
        k_ids = _sub_ids(rp, src)
        for qi in range(2):
            qs = slice(qi * lc, (qi + 1) * lc)
            for ki in range(2):
                ks = slice(ki * lc, (ki + 1) * lc)
                dq_c, dk_c, dv_c = _pair_grads(
                    rp, q[:, qs], k_cur[:, ks], v_cur[:, ks], do[:, qs],
                    lse[:, qs], delta[:, qs], q_ids[qi] - k_ids[ki])
                dq = dq.at[:, qs].add(dq_c)
                dk_acc = dk_acc.at[:, ks].add(dk_c)
                dv_acc = dv_acc.at[:, ks].add(dv_c)
        # the KV chunk and its gradient accumulators ride the reversed ring
        # together; on the last step only the accumulators hop — that final
        # permute brings the summed dk/dv home to the chunk's owner while
        # the (dead) KV buffers stay put
        if step < cp - 1:
            k_cur = jax.lax.ppermute(k_cur, rp.ctx.axis, rp.ctx.perm_bwd)
            v_cur = jax.lax.ppermute(v_cur, rp.ctx.axis, rp.ctx.perm_bwd)
        dk_acc = jax.lax.ppermute(dk_acc, rp.ctx.axis, rp.ctx.perm_bwd)
        dv_acc = jax.lax.ppermute(dv_acc, rp.ctx.axis, rp.ctx.perm_bwd)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


ring_attention.defvjp(_ring_attn_fwd, _ring_attn_bwd)


def gather_attention(ctx: ParallelContext, q, k, v, *, window, softcap,
                     impl, block_size: int = 1024):
    """cp_impl="gather": all-gather K/V over the cp axis (contiguous layout)
    and attend local queries against the full context. The traced per-rank
    ``q_offset`` keeps the XLA twins exact (blockwise masks are built from
    jnp position arrays); O(S) KV per device instead of ring's O(S/cp)."""
    s_loc = q.shape[1]
    kf = jax.lax.all_gather(k, ctx.cp.axis, axis=1, tiled=True)
    vf = jax.lax.all_gather(v, ctx.cp.axis, axis=1, tiled=True)
    return dispatch_attention(q, kf, vf, impl=impl, causal=True,
                              window=window, softcap=softcap,
                              q_offset=_cp_index(ctx) * s_loc,
                              block_size=block_size)


# ---------------------------------------------------------------------------
# context-parallel SSD helpers (conv halo + entering-state chain)


def cp_halo_left(ctx: ParallelContext, x, width: int):
    """The left-neighbour halo for a causal op: the previous cp rank's last
    ``width`` positions (zeros on rank 0). One forward ppermute, uniform."""
    tail = x[:, -width:]
    recv = jax.lax.ppermute(tail, ctx.cp.axis, ctx.cp.perm_fwd)
    return jnp.where(_cp_index(ctx) == 0, jnp.zeros_like(recv), recv)


def cp_chain_state(ctx: ParallelContext, state, decay):
    """Entering state per rank of a linear inter-chunk recurrence.

    ``state`` (B, H, P, N): this rank's accumulated state from a **zero**
    initial state; ``decay`` (B, H): the total decay across the rank's
    chunk. Returns E_r = Σ_{j<r} (Π_{j<k<r} A_k) S_j via cp−1 masked
    forward-ppermute steps — rank ``k`` finalizes at step ``k`` from its
    left neighbour's already-final message (collectives uniform, masking by
    ``where``). Plain autodiff differentiates through the ppermutes
    (linear), so the chain composes with the fused local scan's custom VJP.
    """
    cp = ctx.cp.size
    idx = _cp_index(ctx)
    e = jnp.zeros_like(state)
    for k in range(1, cp):
        msg = state + decay[..., None, None] * e
        # fault seam: the chain message as it lands on the next rank
        recv = taint("cp.ring.state", jax.lax.ppermute(
            msg, ctx.cp.axis, ctx.cp.perm_fwd))
        e = jnp.where(idx == k, recv, e)
    return e


# ---------------------------------------------------------------------------
# family blocks (the math, defined once)


def attn_block(ctx: ParallelContext, lp, x, cfg: ModelConfig, *, positions,
               window=0, dtype=jnp.bfloat16, impl="auto", collect_kv=False):
    """Attention sub-block for any placement.

    local: plain qkv projection, dispatcher attention, plain output GEMM
    (plus the GSPMD seq-shard constrainers). tp: the sequence all-gather is
    fused into the QKV GEMM ring ticks, heads are model-sharded, the output
    projection ring-reduce-scatters. cp: attention runs ring/gathered over
    the cp axis (``positions`` carry the true global ids for rope).
    """
    b, s_in = x.shape[:2]
    hd = cfg.head_dim
    if ctx.tp is None:
        q, k, v = qkv_proj(lp, x, cfg, dtype)
    else:
        ws = (lp["wq"].astype(dtype), lp["wk"].astype(dtype),
              lp["wv"].astype(dtype))
        (q, k, v), _ = all_gather_matmul(ctx.tp, x, ws)
        if cfg.qkv_bias:
            q = q + _slice_tp(ctx, lp["bq"].astype(dtype), q.shape[-1])
            k = k + _slice_tp(ctx, lp["bk"].astype(dtype), k.shape[-1])
            v = v + _slice_tp(ctx, lp["bv"].astype(dtype), v.shape[-1])
        s = s_in * ctx.n_tp
        q = q.reshape(b, s, q.shape[-1] // hd, hd)
        k = k.reshape(b, s, k.shape[-1] // hd, hd)
        v = v.reshape(b, s, v.shape[-1] // hd, hd)
    if cfg.pos_emb == "rope":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q, k, v = ctx.cq(q), ctx.ckv(k), ctx.ckv(v)
    if ctx.cp is None:
        a = dispatch_attention(q, k, v, impl=impl, causal=True, window=window,
                               softcap=cfg.attn_logit_softcap)
    elif ctx.cp_impl == "ring":
        rp = RingAttnParams(ctx.cp, softcap=float(cfg.attn_logit_softcap),
                            impl=impl)
        a = ring_attention(rp, q, k, v)
    else:
        a = gather_attention(ctx, q, k, v, window=window,
                             softcap=cfg.attn_logit_softcap, impl=impl)
    a = ctx.cq(a)
    a = a.reshape(a.shape[0], a.shape[1], -1)
    out = _proj_rows(ctx, a, lp["wo"].astype(dtype))
    if collect_kv:
        return out, (k, v)
    return out


def mlp_block_ex(ctx: ParallelContext, p, x, dtype=jnp.bfloat16):
    """SwiGLU for any placement: one gather decision fused into both the
    gate and up GEMMs, one scatter decision after down."""
    (g, u), _ = _proj_cols(ctx, x, (p["gate"].astype(dtype),
                                    p["up"].astype(dtype)))
    return _proj_rows(ctx, jax.nn.silu(g) * u, p["down"].astype(dtype))


def moe_block_ex(ctx: ParallelContext, p, x, cfg: ModelConfig, dtype,
                 plan: Optional[ParallelPlan] = None):
    """MoE block for any placement. x: (B, S_loc, d) -> (out, aux).

    local: delegates to the dense dispatcher (``moe_lib.moe_block``).
    Sharded: the router sees this (data × cp) shard's token set — under tp a
    ring all-gather re-materializes it once (the GShard cumsum dropping
    policy is order-sensitive, so the model-axis replicas must agree); under
    cp routing is deliberately **local** to the sequence shard (the
    documented shard-local-routing divergence, exact when capacity drops
    nothing) while the aux loss stays batch-global: its density/proxy sums
    psum over data × cp before the mean. The expert SwiGLU is tensor-
    parallel inside each expert when tp is on (d_expert sharded, partials
    psum-completed), full-width otherwise; all three GEMMs keep routing
    through ``dispatch_expert_gemm`` with group_sizes masking.

    ep (``ctx.ep``, MoE parallel folding): the sublayer re-reads the cp ×
    model devices as one flat expert ring — routing is shard-local on this
    fold rank's own sequence chunk (**no** tp re-gather; aux statistics psum
    over the whole fold), each rank owns E/ep complete full-width experts,
    and the dispatch/combine all-to-alls run through ``dispatch_ep_a2a``
    (blocking, or ppermute ticks interleaved with per-peer chunk GEMMs —
    ``ctx.ep_impl``). Post-a2a rows arrive blocked per source peer, so no
    prefix ``group_sizes`` masking applies — padding rows are zero and drop
    out of the GEMMs numerically. Shared experts replicate full-width over
    the fold: every rank routes different tokens, so there is no
    width-partial psum to complete them.
    """
    from repro.models import moe as moe_lib  # noqa: PLC0415 (import cycle)
    if ctx.ep is None and ctx.tp is None and ctx.cp is None:
        return moe_lib.moe_block(p, x, cfg, dtype, ctx.mesh, plan,
                                 ctx.batch_axes)
    e = cfg.moe
    mode = plan.moe_dispatch if plan is not None else "einsum"
    gemm_impl = plan.moe_gemm_impl if plan is not None else "auto"
    b, s_in, d = x.shape
    if ctx.ep is not None:
        n = b * s_in
        xf = x.reshape(n, d)
        capacity = max(int(n * e.top_k / e.num_experts * e.capacity_factor), 1)
        probs, aux = moe_lib.router_probs(p, xf, cfg, dtype, ctx.aux_axes,
                                          ctx.n_rep)
        if mode == "scatter":
            slot, wts = moe_lib.topk_scatter_dispatch(probs, cfg, capacity)
            h = moe_lib._scatter_to_buffers(xf, slot, cfg, capacity)
        else:
            dispatch, combine = moe_lib.topk_dispatch(probs, cfg, capacity)
            h = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype), xf)
        fn = functools.partial(moe_lib.ep_chunk_ffn, dtype=dtype,
                               impl=gemm_impl)
        y = dispatch_ep_a2a(fn, p["experts"], h, axis=ctx.ep.axis,
                            size=ctx.ep.size, impl=ctx.ep_impl)
        if mode == "scatter":
            out = moe_lib._gather_from_buffers(y, slot, wts, dtype)
        else:
            out = jnp.einsum("nec,ecd->nd", combine.astype(dtype), y)
        if e.num_shared_experts:
            sh = jax.nn.silu(xf @ p["shared"]["gate"].astype(dtype)) * (
                xf @ p["shared"]["up"].astype(dtype))
            out = out + sh @ p["shared"]["down"].astype(dtype)
        return out.reshape(b, s_in, d), aux
    if ctx.tp is not None:
        xg = ring_all_gather(ctx.tp, x)            # (B, S_loc·tp, d)
    else:
        xg = x
    s_full = xg.shape[1]
    n = b * s_full
    xf = xg.reshape(n, d)
    capacity = max(int(n * e.top_k / e.num_experts * e.capacity_factor), 1)

    probs, aux = moe_lib.router_probs(p, xf, cfg, dtype, ctx.aux_axes,
                                      ctx.n_rep)

    if mode == "scatter":
        slot, wts = moe_lib.topk_scatter_dispatch(probs, cfg, capacity)
        gs = moe_lib._group_sizes_from_slots(slot, e.num_experts, capacity)
        h = moe_lib._scatter_to_buffers(xf, slot, cfg, capacity)
    else:
        dispatch, combine = moe_lib.topk_dispatch(probs, cfg, capacity)
        gs = moe_lib._group_sizes_from_dispatch(dispatch)
        h = jnp.einsum("nec,nd->ecd", dispatch.astype(dtype), xf)

    part = moe_lib._expert_ffn(p["experts"], h, dtype, gemm_impl, gs)
    if ctx.tp is not None:
        part = jax.lax.psum(part, ctx.tp.axis)   # complete d_expert partials
        # combine only this rank's sequence chunk (token rows independent)
        idx = _tp_index(ctx)

        def chunk_rows(a):
            a = a.reshape((b, s_full) + a.shape[1:])
            a = jax.lax.dynamic_slice_in_dim(a, idx * s_in, s_in, 1)
            return a.reshape((b * s_in,) + a.shape[2:])
    else:
        def chunk_rows(a):
            return a

    if mode == "scatter":
        out = moe_lib._gather_from_buffers(part, chunk_rows(slot),
                                           chunk_rows(wts), dtype)
    else:
        out = jnp.einsum("nec,ecd->nd", chunk_rows(combine).astype(dtype),
                         part)
    if e.num_shared_experts:
        sh = jax.nn.silu(xf @ p["shared"]["gate"].astype(dtype)) * (
            xf @ p["shared"]["up"].astype(dtype))
        sh_part = sh @ p["shared"]["down"].astype(dtype)
        if ctx.tp is not None:
            # shared-expert width is rank-sharded: every rank computes its
            # partial for every token; ring reduce-scatter sums into chunks
            out = out + ring_reduce_scatter(
                ctx.tp, sh_part.reshape(b, s_full, d)).reshape(b * s_in, d)
        else:
            out = out + sh_part
    return out.reshape(b, s_in, d), aux


def ssm_block_ex(ctx: ParallelContext, p, x, cfg: ModelConfig, dtype,
                 plan: Optional[ParallelPlan] = None):
    """Mamba2 block for any placement. x: (B, L_loc, d) -> same shape.

    local: delegates to ``ssm_lib.ssm_block`` (also the decode-side oracle).
    tp: heads carry the model dim (PR 4 layout — in_proj ring-fused, B/C on
    the gathered copy, psum'd gated RMSNorm). cp: contiguous sequence
    chunks; causal convs take a (d_conv−1)-token halo from the left
    neighbour, the local chunk scans from a zero state through the usual
    dispatcher (fused kernel stays eligible), and the inter-rank recurrence
    closes in two rank-local einsums around :func:`cp_chain_state` — the
    carried-in state's contribution is linear, so it never re-runs the scan.
    """
    from repro.models import ssm as ssm_lib  # noqa: PLC0415 (import cycle)
    if ctx.tp is None and ctx.cp is None:
        return ssm_lib.ssm_block(p, x, cfg, dtype, plan=plan)

    s = cfg.ssm
    di, nh, g, n = ssm_lib.ssm_dims(cfg)
    tp = ctx.n_tp
    if tp > 1:
        assert g == 1 and nh % tp == 0 and di % tp == 0, (g, nh, di, tp)
    nh_l, di_l = nh // tp, di // tp
    b = x.shape[0]

    if ctx.tp is not None:
        (z, xin, dtp), xg = all_gather_matmul(
            ctx.tp, x, (p["wz"].astype(dtype), p["wx"].astype(dtype),
                        p["wdt"].astype(dtype)))
        Bv = xg @ p["wB"].astype(dtype)
        Cv = xg @ p["wC"].astype(dtype)
    else:
        z = x @ p["wz"].astype(dtype)
        xin = x @ p["wx"].astype(dtype)
        dtp = x @ p["wdt"].astype(dtype)
        Bv = x @ p["wB"].astype(dtype)
        Cv = x @ p["wC"].astype(dtype)
    l = xin.shape[1]                      # cp-local length (tp re-gathered)
    dt_bias = _slice_tp(ctx, p["dt_bias"], nh_l)
    dt = jax.nn.softplus(dtp.astype(jnp.float32) + dt_bias)   # (b, l, nh_l)

    conv_x = _slice_tp(ctx, p["conv_x"], di_l)
    if ctx.cp is not None and s.d_conv > 1:
        # causal convs need the previous rank's last K−1 positions: one halo
        # exchange for all three streams (concatenated channels). d_conv==1
        # needs no left context (and x[:, -0:] would ship the whole chunk).
        width = s.d_conv - 1
        halo = cp_halo_left(ctx, jnp.concatenate([xin, Bv, Cv], axis=-1),
                            width)
        lx, lB, lC = jnp.split(halo, [xin.shape[-1],
                                      xin.shape[-1] + Bv.shape[-1]], axis=-1)
    else:
        lx = lB = lC = None
    bias_x = p.get("conv_bias_x")
    if bias_x is not None:
        bias_x = _slice_tp(ctx, bias_x, di_l)
    xin = jax.nn.silu(ssm_lib._causal_conv(xin, conv_x, dtype, left=lx,
                                           bias=bias_x))
    Bv = jax.nn.silu(ssm_lib._causal_conv(Bv, p["conv_B"], dtype, left=lB,
                                          bias=p.get("conv_bias_B")))
    Cv = jax.nn.silu(ssm_lib._causal_conv(Cv, p["conv_C"], dtype, left=lC,
                                          bias=p.get("conv_bias_C")))

    A = -jnp.exp(_slice_tp(ctx, p["A_log"], nh_l))
    xh = xin.reshape(b, l, nh_l, s.head_dim)
    Bm = Bv.reshape(b, l, g, n)
    Cm = Cv.reshape(b, l, g, n)
    y, _ = dispatch_ssd_scan(
        xh, dt, A, Bm, Cm, chunk=s.chunk,
        impl=plan.ssm_impl if plan is not None else "auto")

    if ctx.cp is not None:
        # inter-rank recurrence: local accumulated state + total decay chain
        # around the cp ring; the entering state's contribution to y is the
        # closed form C_t · exp(cumΣdA_t) · E (linear in E)
        hpg = nh_l // g
        dA = (dt * A).astype(jnp.float32)                    # (b, l, h)
        cum = jnp.cumsum(dA, axis=1)
        xd = (xh * dt[..., None]).astype(jnp.float32)
        Bf = Bm.astype(jnp.float32)
        Cf = Cm.astype(jnp.float32)
        tail = jnp.exp(cum[:, -1:, :] - cum)                 # Π_{k>t} decay
        s_loc_state = jnp.einsum(
            "btgn,btgh,btghp->bghpn", Bf,
            tail.reshape(b, l, g, hpg),
            xd.reshape(b, l, g, hpg, s.head_dim)).reshape(
                b, nh_l, s.head_dim, n)
        a_total = jnp.exp(cum[:, -1, :])                     # (b, h)
        e_in = cp_chain_state(ctx, s_loc_state, a_total)
        y = y + jnp.einsum(
            "btgn,bghpn,btgh->btghp", Cf,
            e_in.reshape(b, g, hpg, s.head_dim, n),
            jnp.exp(cum).reshape(b, l, g, hpg)).reshape(
                b, l, nh_l, s.head_dim)

    D = _slice_tp(ctx, p["D"], nh_l)
    y = y + xh.astype(jnp.float32) * D[None, None, :, None]
    y = y.reshape(b, l, di_l).astype(dtype)

    scale = _slice_tp(ctx, p["scale"], di_l)
    if ctx.tp is not None:
        # gated RMSNorm over the full (model-sharded) d_inner: per-rank sum
        # of squares + psum reproduces rms_norm's full-width mean
        yz = (y * jax.nn.silu(z)).astype(jnp.float32)
        ssq = jax.lax.psum(jnp.sum(jnp.square(yz), axis=-1, keepdims=True),
                           ctx.tp.axis)
        yn = ((yz * jax.lax.rsqrt(ssq / di + cfg.rms_eps))
              * (1.0 + scale.astype(jnp.float32))).astype(dtype)
    else:
        yn = ssm_lib.gated_rms_norm(y, z, scale, g, cfg.rms_eps)
    return _proj_rows(ctx, yn, p["out_proj"].astype(dtype))


# ---------------------------------------------------------------------------
# layer builders (shared by loss fns, the pipeline stage_fn and families)


def decoder_layer(ctx: ParallelContext, cfg: ModelConfig, plan: ParallelPlan,
                  dtype, collect_kv: bool = False):
    """The one decoder-layer body (dense / MoE) for every placement."""
    alternating = bool(cfg.local_global_alternating and cfg.sliding_window)
    impl = plan.attn_impl if plan is not None else "auto"

    def layer(x, lp, window, positions):
        x = ctx.cx(x)
        with jax.named_scope("norm"):
            h = rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
        with jax.named_scope("attn"):
            a = attn_block(ctx, lp["attn"], h, cfg, positions=positions,
                           window=(window if alternating
                                   else cfg.sliding_window),
                           dtype=dtype, impl=impl, collect_kv=collect_kv)
        if collect_kv:
            a, kv = a
        a = checkpoint_name(a, "attn_out")
        if cfg.post_norm:
            with jax.named_scope("norm"):
                a = rms_norm(a, lp["norm1_post"]["scale"], cfg.rms_eps)
        x = x + a
        with jax.named_scope("norm"):
            h = rms_norm(x, lp["norm2"]["scale"], cfg.rms_eps)
        with jax.named_scope("mlp"):
            if cfg.family == Family.MOE:
                m, aux = moe_block_ex(ctx, lp["moe"], h, cfg, dtype, plan)
            else:
                m, aux = (mlp_block_ex(ctx, lp["mlp"], h, dtype),
                          jnp.float32(0.0))
            if cfg.post_norm:
                m = rms_norm(m, lp["norm2_post"]["scale"], cfg.rms_eps)
        if collect_kv:
            return x + m, aux, kv
        return x + m, aux
    return layer


def ssm_layer(ctx: ParallelContext, cfg: ModelConfig, plan: ParallelPlan,
              dtype):
    """The one Mamba2 layer body for every placement."""
    def layer(x, lp, window, positions):
        del window, positions
        x = ctx.cx(x)
        with jax.named_scope("norm"):
            h = rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
        with jax.named_scope("mixer"):
            y = ssm_block_ex(ctx, lp["ssm"], h, cfg, dtype, plan)
        y = checkpoint_name(y, "block_out")
        return x + y, jnp.float32(0.0)
    return layer


def layer_fn_for(ctx: ParallelContext, cfg: ModelConfig, plan: ParallelPlan,
                 dtype):
    if cfg.family == Family.SSM:
        return ssm_layer(ctx, cfg, plan, dtype)
    return decoder_layer(ctx, cfg, plan, dtype)


# ---------------------------------------------------------------------------
# context construction + whole-model loss


def check_cp_support(cfg: ModelConfig, plan: ParallelPlan, cp: int):
    """Static preconditions of the cp axis. Raises ValueError otherwise.
    (Shared family/pos_emb rules live next to the TP twin —
    ``tensor_parallel.decoder_only_support_errors`` — so the two explicit
    shard_map paths can't drift apart on what they accept.)"""
    from repro.train.tensor_parallel import (  # noqa: PLC0415
        decoder_only_support_errors)
    bad = decoder_only_support_errors(cfg)
    if bad:
        raise ValueError(f"cp={cp} unsupported here: " + "; ".join(bad))


def resolve_context(cfg: ModelConfig, plan: ParallelPlan, mesh: Mesh,
                    batch_axes: Tuple[str, ...]) -> ParallelContext:
    """Build the shard_map-interior ParallelContext for this plan/mesh."""
    from repro.train import tensor_parallel as tplib  # noqa: PLC0415
    tp = mesh.shape.get("model", 1)
    cp = mesh.shape.get("cp", 1) if plan.cp > 1 else 1
    # the tp rings need BOTH a 2-wide model axis and a plan that asked for
    # tensor parallelism (tp > 1, or an explicit tp_impl="overlap" — the old
    # make_tp_loss_fn contract). A cp-only plan on a mesh that happens to
    # carry a model axis must NOT grow unrequested 16-way TP (or trip
    # check_overlap_support's divisibility errors for it).
    use_tp = tp >= 2 and (plan.tp > 1 or plan.tp_impl == "overlap")
    if plan.tp_impl == "overlap" and not use_tp:
        raise ValueError(
            "tp_impl='overlap' was requested explicitly but the mesh has no "
            f"'model' axis of size >= 2 to run the rings on (got {mesh.shape})")
    if plan.cp > 1 and cp < plan.cp:
        raise ValueError(
            f"plan.cp={plan.cp} needs a 'cp' mesh axis of size {plan.cp}, "
            f"mesh has {mesh.shape}")
    use_ep = plan.ep > 1
    cp_axis = "cp"
    ep_ctx = None
    if use_ep:
        # MoE parallel folding: the expert ring re-reads the devices of the
        # resolved cp × model placement, so its size is pinned to theirs.
        if use_tp or cp > 1:
            fold_axes = (("cp",) if cp > 1 else ()) \
                + (("model",) if use_tp else ())
            fold = (cp if cp > 1 else 1) * (tp if use_tp else 1)
            if plan.ep != fold:
                raise ValueError(
                    f"plan.ep={plan.ep} must equal the folded cp×model ring "
                    f"size {fold} (mesh {dict(mesh.shape)}): the expert axis "
                    "re-maps those devices, it does not add any")
        else:
            # ep-only placement: experts ride the model axis and attention
            # runs as a cp ring over that same axis (sequence-sharded)
            if tp != plan.ep:
                raise ValueError(
                    f"plan.ep={plan.ep} needs a 'model' mesh axis of exactly "
                    f"that size to ride (mesh has {dict(mesh.shape)})")
            fold_axes = ("model",)
            cp, cp_axis = plan.ep, "model"
        ep_ctx = RingCtx(fold_axes if len(fold_axes) > 1 else fold_axes[0],
                         plan.ep)
    if use_tp:
        tplib.check_overlap_support(cfg, plan, tp)
    if cp > 1:
        check_cp_support(cfg, plan, cp)
    cp_impl = select_cp_impl(
        plan.cp_impl, family=cfg.family, window=cfg.sliding_window,
        local_global_alternating=bool(cfg.local_global_alternating
                                      and cfg.sliding_window)) \
        if cp > 1 else "ring"
    # the validate()-time twin of this warning only sees *explicit* knobs;
    # here the placement is actually resolved (tp_impl="auto" may have
    # landed on the rings), so re-flag the documented shard-local-routing
    # divergence against the real decision
    if use_tp or cp > 1 or use_ep:
        from repro.core.config import warn_shard_local_routing  # noqa: PLC0415
        warn_shard_local_routing(cfg)
    n_dp = 1
    for a in (batch_axes or ()):
        n_dp *= mesh.shape[a]
    return ParallelContext(
        tp=RingCtx("model", tp) if use_tp else None,
        cp=RingCtx(cp_axis, cp) if cp > 1 else None,
        cp_impl=cp_impl, ep=ep_ctx,
        ep_impl=select_ep_impl(plan.ep_impl),
        batch_axes=tuple(batch_axes or ()), n_dp=n_dp,
        mesh=mesh)


def executor_param_specs(params, cfg: ModelConfig, plan: ParallelPlan,
                         mesh: Mesh, ctx: ParallelContext):
    """shard_map in_specs for the executor loss: overlap column/row/vocab
    shards when the tp rings are on, fully replicated otherwise (cp shards
    the sequence, never the weights). Under EP the MoE leaves override to
    the folded layout (:func:`sharding.ep_spec_for_param` — routed experts
    expert-dim-sharded over the fold, shared experts/router replicated
    full-width); non-MoE leaves keep their tp/replicated classification, so
    attention and MoE genuinely use *different* mappings of the same
    devices."""
    if ctx.tp is not None:
        specs = shardlib.overlap_param_specs(params, cfg, plan, mesh)
    else:
        specs = jax.tree_util.tree_map(lambda _: P(), params)
    if ctx.ep is not None:
        def override(path, leaf, spec):
            ep_spec = shardlib.ep_spec_for_param(
                shardlib._path_names(path), tuple(leaf.shape), plan)
            return spec if ep_spec is None else ep_spec
        specs = jax.tree_util.tree_map_with_path(override, params, specs)
    return specs


def make_executor_loss_fn(cfg: ModelConfig, plan: ParallelPlan, mesh: Mesh,
                          batch_axes: Tuple[str, ...] = ("data",),
                          z_loss: float = 0.0):
    """loss_fn(params, batch) through the unified executor, for any tp × cp.

    The shard_map interior embeds, scans the one layer body per family,
    norms, and reduces the head: vocab-parallel (ring head GEMM +
    ``cross_entropy_vp``) when the tp rings are on, a local full-vocab head
    on sequence shards otherwise — per-position nll sums ``psum`` over
    data × cp and divide by the global token count either way. Ring-cp
    inputs are zigzag-permuted **outside** the shard_map (static
    permutation; every position-wise op is permutation-invariant).
    """
    from repro.models.families import (_embed, _layer_windows,  # noqa: PLC0415
                                       _logits, _remat)
    from repro.train.loss import cross_entropy  # noqa: PLC0415
    ctx = resolve_context(cfg, plan, mesh, batch_axes)
    if ctx.tp is None and ctx.cp is None:
        raise ValueError(
            "executor loss needs a 'model' mesh axis >= 2 (overlap TP) "
            "and/or plan.cp > 1 with a 'cp' mesh axis")
    if plan.dp_shard > 1:
        raise ValueError(
            "the executor loss (overlap TP / cp) expects dp_shard == 1: "
            "params enter the shard_map replicated over data, so FSDP-style "
            "param sharding would silently vanish instead of composing")
    cp, n_tp = ctx.n_cp, ctx.n_tp
    zigzag = ctx.cp is not None and ctx.cp_impl == "ring" \
        and cfg.family != Family.SSM
    dtype = jnp.dtype(plan.compute_dtype)
    windows_all = jnp.asarray(_layer_windows(cfg))
    baxes = batch_axes if batch_axes else None
    n_dp = ctx.n_dp
    layer = layer_fn_for(ctx, cfg, plan, dtype)

    def local_fn(params_l, tokens, labels):
        # tokens/labels: (B_loc, S/cp) — this cp rank's chunk, replicated
        # over model (the vocab-parallel embedding needs every position)
        b, s_loc = tokens.shape
        if n_tp > 1:
            assert s_loc % n_tp == 0, (s_loc, n_tp)
            x = tp_embed(params_l, tokens, cfg, dtype, ctx.tp)
        else:
            x = _embed(params_l, tokens, cfg, dtype)
        positions = cp_local_positions(ctx, s_loc)

        def body(carry, xs):
            xc, aux = carry
            lp, w = xs
            xn, a = layer(xc, lp, w, positions)
            return (xn, aux + a), None

        body = _remat(body, plan.remat)
        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((1,), jnp.float32)),
            (params_l["layers"], windows_all))
        x = rms_norm(x, params_l["final_norm"]["scale"], cfg.rms_eps)
        if n_tp > 1:
            nll = tp_head_nll(params_l, x, labels, cfg, ctx.tp, dtype, z_loss)
        else:
            logits = _logits(params_l, x, cfg, dtype)
            nll = cross_entropy(logits, labels, z_loss=z_loss,
                                reduction="none")
        tot = nll.sum()
        red_axes = tuple(batch_axes or ())
        if ctx.cp is not None:
            red_axes = red_axes + (ctx.cp.axis,)
        if red_axes:
            tot = jax.lax.psum(tot, red_axes)
        loss = tot / (b * n_dp * s_loc * cp)
        return jnp.stack([loss, aux[0]])

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        if zigzag:
            perm = zigzag_permutation(tokens.shape[1], cp)
            tokens, labels = tokens[:, perm], labels[:, perm]
        if ctx.cp is not None:
            assert tokens.shape[1] % (2 * cp if zigzag else cp) == 0, \
                (tokens.shape, cp)
        pspecs = executor_param_specs(params, cfg, plan, mesh, ctx)
        seq_ax = ctx.cp.axis if ctx.cp is not None else None
        v = shard_map(
            local_fn, mesh=mesh,
            in_specs=(pspecs, P(baxes, seq_ax), P(baxes, seq_ax)),
            out_specs=P(),
        )(params, tokens, labels)
        loss, aux = v[0], v[1]
        return loss + aux, {"xent": loss, "moe_aux": aux}

    return loss_fn
