"""Kernel dispatch — pick an implementation per call site, per op.

This is the architecture hook for every fused kernel: model code calls the
per-op dispatcher (:func:`dispatch_attention` via ``repro.models.layers``,
:func:`dispatch_expert_gemm` via ``repro.models.moe._expert_ffn``,
:func:`dispatch_ssd_scan` via ``repro.models.ssm.ssm_block``) with the
matching ``ParallelPlan`` knob (``attn_impl`` / ``moe_gemm_impl`` /
``ssm_impl``), and the dispatcher decides, per call site, whether the fused
Pallas kernel or the XLA twin runs. Shared rules (:func:`_resolve_choice`):

- ``impl="xla"``    — always the pure-XLA twin (also the gradient oracle).
- ``impl="pallas"`` — the fused kernel; a call that breaks its static
  preconditions (attention: compile-time mask params; SSD: no initial state)
  raises ``ValueError`` instead of quietly running XLA.
- ``impl="auto"``   — Pallas iff running on a TPU backend and the
  preconditions hold. Off-TPU the Pallas interpreter validates correctness
  but is orders of magnitude slower, so auto never selects it — tests and
  benchmarks opt in with ``impl="pallas"``. The GSPMD model path on a
  mesh of several devices hands ``"xla"`` in place of ``"auto"``
  (``models.families.gspmd_kernel_plan``): GSPMD cannot partition a
  Mosaic kernel, while the shard_map paths call the kernels per shard.

Every fused kernel here is differentiable (``jax.custom_vjp`` recompute
backwards), so the dispatchers sit on the training path, not just prefill.

Layout contracts: model code uses batch-major layouts ((B, S, H, hd) for
attention, (B, L, H, P) for SSD). The attention kernels use head-major; the
SSD kernels read x and write y in the model's layout and take only dt, B and
C head- or group-major. The dispatchers own the transposes, plus the boundary
padding for unaligned lengths (KV to the block boundary for blockwise
attention, the sequence to the chunk boundary for SSD — never a silent
fall-back to a quadratic whole-sequence path).

Ring context parallelism (``train/executor.py``) gets two extra attention
entries: :func:`dispatch_attention_lse` (per-chunk forward that also returns
the logsumexp — the lse-merging chunked-softmax tile) and
:func:`dispatch_attention_chunk_bwd` (per-chunk backward against the globally
merged (lse, Δ)); :func:`select_cp_impl` resolves ``ParallelPlan.cp_impl``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.ft.inject import taint
from repro.models import layers as _layers
from .flash_attention import (_pad_seq, flash_attention, flash_attention_bwd,
                              flash_attention_lse, resolve_interpret)
from .grouped_gemm import expert_gemm
from .ssd_scan import has_lane_block, ssd_chunk_scan

IMPLS = ("auto", "xla", "pallas")

# the name scope of the layout transposes into and out of each kernel: a
# profile that keeps the ops' metadata finds their copies by it (forward,
# remat recompute and backward)
LAYOUT_SCOPE = "kernel_layout"


def _tainted(point: str):
    """Route a dispatcher's primary output through a named fault point
    (ft/inject): identity unless a FaultSpec is armed at trace time, so the
    production path is untouched while chaos tests can corrupt any fused-op
    output (tuple returns taint their first element)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            if isinstance(out, tuple):
                return (taint(point, out[0]),) + out[1:]
            return taint(point, out)
        return wrapper
    return deco


def _is_static(x) -> bool:
    return isinstance(x, (int, np.integer))


def _resolve_choice(impl: str, *, knob: str, explicit_ok: bool,
                    auto_ok: bool, why: str = "") -> str:
    """Shared auto|xla|pallas resolution. ``explicit_ok`` gates an explicit
    ``"pallas"`` request (hard preconditions; ``why`` names them); an
    unmet one raises rather than running XLA in the kernel's place.
    ``auto_ok`` additionally gates ``"auto"`` (soft preferences like
    lane-friendly shapes)."""
    if impl not in IMPLS:
        raise ValueError(f"{knob} must be one of {IMPLS}, got {impl!r}")
    if impl == "xla":
        return "xla"
    if impl == "pallas":
        if not explicit_ok:
            raise ValueError(f"{knob}='pallas' cannot run here: {why}")
        return "pallas"
    if explicit_ok and auto_ok and jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


def select_impl(impl: str, *, head_dim: int, window, q_offset) -> str:
    """Resolve the attention impl. Traced mask params (gemma2 local/global
    alternation scans the window as layer metadata) force XLA since Pallas
    masks are compile-time."""
    static = _is_static(window) and _is_static(q_offset)
    return _resolve_choice(
        impl, knob="attn_impl", explicit_ok=static,
        auto_ok=head_dim % 8 == 0 and head_dim <= 256,
        why="the kernel's mask needs a static window and q_offset")


TP_IMPLS = ("auto", "gspmd", "overlap")


def select_tp_impl(impl: str) -> str:
    """Resolve ``ParallelPlan.tp_impl`` (survey §4.1.2/§5.2).

    ``"gspmd"`` leaves tensor parallelism to XLA's SPMD partitioner (blocking
    all-reduce after every row GEMM, full-size activations between blocks).
    ``"overlap"`` selects the explicit ``shard_map`` ring path
    (:mod:`repro.train.tensor_parallel`): collective matmuls + sequence-sharded
    activations. ``"auto"`` picks overlap on TPU backends — the ring's
    ``ppermute`` steps compile to async DMAs there, so the per-tick partial
    GEMMs actually hide the transfer — and gspmd elsewhere (on CPU the ring
    is semantically identical but the ticks serialize).
    """
    if impl not in TP_IMPLS:
        raise ValueError(f"tp_impl must be one of {TP_IMPLS}, got {impl!r}")
    if impl == "auto":
        return "overlap" if jax.default_backend() == "tpu" else "gspmd"
    return impl


def dispatch_tp_matmul(x, w, *, impl: str = "auto"):
    """One ring-tick partial GEMM of the collective matmuls.

    ``x``: (..., k) activation tile (one sequence chunk), ``w``: (k, f) weight
    shard. Every partial product of the overlap-TP rings funnels through here
    so the tile GEMM stays a single dispatch point: today it is always the XLA
    dot (bitwise twin of the GSPMD path's local matmul — required by the
    overlap-vs-gspmd equivalence tests); a fused Pallas tile GEMM can slot in
    behind the same signature without touching the ring schedules. The fused
    attention / expert-GEMM / SSD kernels are reached separately — the TP
    layer bodies call :func:`dispatch_attention` / :func:`dispatch_expert_gemm`
    / :func:`dispatch_ssd_scan` on the gathered tiles, so ``tp_impl="overlap"``
    composes with ``attn_impl/moe_gemm_impl/ssm_impl = "pallas"``.
    """
    del impl  # reserved for a fused tile-GEMM kernel
    return jnp.matmul(x, w)


CP_IMPLS = ("auto", "gather", "ring")


def select_cp_impl(impl: str, *, family: str = "dense", window: int = 0,
                   local_global_alternating: bool = False) -> str:
    """Resolve ``ParallelPlan.cp_impl`` (survey §4.1.4, long-context training).

    ``"gather"`` all-gathers K/V over the ``cp`` axis (contiguous sequence
    chunks, Megatron-SP-style): every rank holds the full KV but only its
    query chunk — exact, simple, O(S) KV memory per device. ``"ring"`` keeps
    KV sharded too and ``ppermute``s chunks around the cp ring with zigzag
    causal load balancing — no device ever holds the full context, the
    long-context regime ring attention exists for. ``"auto"`` picks ring
    whenever its static preconditions hold:

    - full causal attention only (sliding windows / gemma2 local-global
      alternation make the ring's static per-pair mask cases traced — gather
      handles them);
    - the SSM family always resolves to ``"ring"``: its cp execution is the
      per-chunk entering-state chain (there is no KV to gather), and the
      zigzag remark doesn't apply (SSD per-position work is uniform, so the
      layout stays contiguous).
    """
    if impl not in CP_IMPLS:
        raise ValueError(f"cp_impl must be one of {CP_IMPLS}, got {impl!r}")
    from repro.core.config import Family  # noqa: PLC0415 (import cycle)
    if family == Family.SSM:
        return "ring"
    ring_ok = not window and not local_global_alternating
    if impl == "ring" and not ring_ok:
        raise ValueError(
            "cp_impl='ring' needs full causal attention (no sliding window / "
            "local-global alternation); use cp_impl='gather'")
    if impl == "auto":
        return "ring" if ring_ok else "gather"
    return impl


def dispatch_attention_lse(q, k, v, *, impl: str = "auto", causal: bool = True,
                           window=0, softcap: float = 0.0, q_offset=0,
                           block_size: int = 1024,
                           scale: Optional[float] = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: Optional[bool] = None):
    """Chunk attention that also returns the merged-softmax statistic.

    Batch-major twin of the plain dispatcher: q (B, S, Hq, hd), k/v
    (B, T, Hkv, hd) -> (o (B, S, Hq, hd), lse (B, S, Hq) fp32). This is the
    inner tile of ring context parallelism — per-chunk (o, lse) pairs merge
    exactly across the cp ring (see ``train/executor.py``).
    """
    choice = select_impl(impl, head_dim=q.shape[-1], window=window,
                         q_offset=q_offset)
    if choice == "pallas":
        with jax.named_scope(LAYOUT_SCOPE):
            q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        o, lse = flash_attention_lse(
            q, k, v, causal=causal, window=int(window),
            softcap=softcap, scale=scale, q_offset=int(q_offset),
            block_q=block_q, block_k=block_k,
            interpret=resolve_interpret(interpret))
        with jax.named_scope(LAYOUT_SCOPE):
            return o.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1)
    t = k.shape[1]
    if t <= 2 * block_size:
        return _layers.attention_direct_lse(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, scale=scale)
    if t % block_size:
        t_pad = -(-t // block_size) * block_size
        return _layers.attention_blockwise(
            q, _pad_seq(k, 1, t_pad), _pad_seq(v, 1, t_pad), causal=causal,
            window=window, softcap=softcap, q_offset=q_offset,
            block_size=block_size, scale=scale, kv_len=t, return_lse=True)
    return _layers.attention_blockwise(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_size=block_size, scale=scale,
        return_lse=True)


def dispatch_attention_chunk_bwd(q, k, v, do, lse, delta, *,
                                 impl: str = "auto", causal: bool = True,
                                 softcap: float = 0.0, q_offset=0,
                                 scale: Optional[float] = None,
                                 block_q: int = 128, block_k: int = 128,
                                 interpret: Optional[bool] = None):
    """One KV chunk's (dq, dk, dv) against the globally merged (lse, delta).

    Batch-major: q/do (B, S, Hq, hd), k/v (B, T, Hkv, hd), lse/delta
    (B, S, Hq). Routes to the FlashAttention-2 backward kernels
    (:func:`repro.kernels.flash_attention.flash_attention_bwd`) or the XLA
    twin (:func:`repro.models.layers.attention_chunk_grads`).
    """
    choice = select_impl(impl, head_dim=q.shape[-1], window=0,
                         q_offset=q_offset)
    if choice == "pallas":
        hd = q.shape[-1]
        with jax.named_scope(LAYOUT_SCOPE):
            q, k, v, do = (a.transpose(0, 2, 1, 3) for a in (q, k, v, do))
            lse, delta = lse.transpose(0, 2, 1), delta.transpose(0, 2, 1)
        dq, dk, dv = flash_attention_bwd(
            q, k, v, do.astype(jnp.float32), lse, delta,
            causal=causal, window=0, softcap=softcap,
            scale=float(scale) if scale is not None else hd ** -0.5,
            q_offset=int(q_offset), block_q=block_q, block_k=block_k,
            interpret=resolve_interpret(interpret))
        with jax.named_scope(LAYOUT_SCOPE):
            return tuple(a.transpose(0, 2, 1, 3) for a in (dq, dk, dv))
    return _layers.attention_chunk_grads(
        q, k, v, do, lse, delta, causal=causal, window=0, softcap=softcap,
        q_offset=q_offset, scale=scale)


def select_gemm_impl(impl: str) -> str:
    """Resolve the expert-GEMM impl (the kernel pads every dim, so an explicit
    "pallas" is always honored)."""
    return _resolve_choice(impl, knob="moe_gemm_impl", explicit_ok=True,
                           auto_ok=True)


def select_ssd_impl(impl: str, *, has_initial_state: bool = False,
                    lanes_ok: bool = True) -> str:
    """Resolve the SSD impl. The fused kernel starts from a zero state, so a
    caller-supplied initial state takes the XLA scan under ``auto``; so do
    heads with no lane-legal block (``ssd_scan.has_lane_block``)."""
    return _resolve_choice(impl, knob="ssm_impl",
                           explicit_ok=not has_initial_state,
                           auto_ok=lanes_ok,
                           why="the kernel starts from a zero state")


# ---------------------------------------------------------------------------
# attention


@_tainted("kernel.attention")
def dispatch_attention(q, k, v, *, impl: str = "auto", causal: bool = True,
                       window=0, softcap: float = 0.0, q_offset=0,
                       block_size: int = 1024,
                       scale: Optional[float] = None,
                       block_q: int = 128, block_k: int = 128,
                       interpret: Optional[bool] = None):
    """q: (B, S, Hq, hd), k/v: (B, T, Hkv, hd) -> (B, S, Hq, hd)."""
    choice = select_impl(impl, head_dim=q.shape[-1], window=window,
                         q_offset=q_offset)
    if choice == "pallas":
        with jax.named_scope(LAYOUT_SCOPE):
            q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
        out = flash_attention(
            q, k, v, causal=causal, window=int(window),
            softcap=softcap, scale=scale, q_offset=int(q_offset),
            block_q=block_q, block_k=block_k,
            interpret=resolve_interpret(interpret))
        with jax.named_scope(LAYOUT_SCOPE):
            return out.transpose(0, 2, 1, 3)

    t = k.shape[1]
    if t <= 2 * block_size:
        return _layers.attention_direct(
            q, k, v, causal=causal, window=window, softcap=softcap,
            q_offset=q_offset, scale=scale)
    if t % block_size:
        # pad KV to the block boundary and mask the tail — never drop to the
        # O(S·T) direct path just because the context length is unaligned
        t_pad = -(-t // block_size) * block_size
        return _layers.attention_blockwise(
            q, _pad_seq(k, 1, t_pad), _pad_seq(v, 1, t_pad), causal=causal,
            window=window, softcap=softcap, q_offset=q_offset,
            block_size=block_size, scale=scale, kv_len=t)
    return _layers.attention_blockwise(
        q, k, v, causal=causal, window=window, softcap=softcap,
        q_offset=q_offset, block_size=block_size, scale=scale)


# ---------------------------------------------------------------------------
# MoE expert GEMM


@_tainted("kernel.expert_gemm")
def dispatch_expert_gemm(x, w, group_sizes=None, *, impl: str = "auto",
                         block_c: int = 128, block_f: int = 128,
                         block_d: int = 256,
                         interpret: Optional[bool] = None):
    """x: (E, C, d) × w: (E, d, f) -> (E, C, f); ``group_sizes`` (E,) marks the
    real rows per expert (padding rows are masked out of outputs and grads)."""
    choice = select_gemm_impl(impl)
    if choice == "pallas":
        return expert_gemm(x, w, group_sizes, block_c=block_c,
                           block_f=block_f, block_d=block_d,
                           interpret=resolve_interpret(interpret))
    if group_sizes is not None:
        rows = jnp.arange(x.shape[1])[None, :, None]
        x = jnp.where(rows < jax.lax.stop_gradient(group_sizes)[:, None, None],
                      x, 0)
    return jnp.einsum("ecd,edf->ecf", x, w)


# ---------------------------------------------------------------------------
# EP dispatch/combine all-to-all (expert parallelism, survey §4.1.5)


EP_IMPLS = ("auto", "blocking", "overlap")


def select_ep_impl(impl: str) -> str:
    """Resolve ``ParallelPlan.ep_impl`` (survey §4.1.5/§5.2).

    ``"blocking"`` runs one ``lax.all_to_all`` before and one after the
    expert GEMM — the whole token exchange is exposed on the critical path.
    ``"overlap"`` decomposes each all-to-all into ``ppermute`` ring ticks
    interleaved with per-peer expert-GEMM chunks: every tick computes the
    chunk it already holds while the next is in flight. ``"auto"`` resolves
    to overlap everywhere — unlike the TP ring (where the gspmd baseline is
    a different layout), the EP ring is semantically identical to the
    blocking a2a on every backend, and its ticks compile to async DMAs on
    TPU.
    """
    if impl not in EP_IMPLS:
        raise ValueError(f"ep_impl must be one of {EP_IMPLS}, got {impl!r}")
    return "overlap" if impl == "auto" else impl


def _ep_a2a_blocking(fn, axis, size, w, h):
    """GShard-style exposed exchange: dispatch a2a → expert fn → combine a2a.

    Plain traced (autodiff goes straight through ``lax.all_to_all``), so it
    doubles as the gradient oracle for the custom-VJP overlap ring.
    """
    e, c, d = h.shape
    e_loc = e // size
    hr = h.reshape(size, e_loc, c, d)
    hx = taint("ep.a2a.tick", jax.lax.all_to_all(
        hr, axis, split_axis=0, concat_axis=0, tiled=False))
    # hx[j] = peer j's token chunk for my local experts; block rows per
    # source peer so fn sees one (e_loc, size·C, d) buffer
    hs = hx.transpose(1, 0, 2, 3).reshape(e_loc, size * c, d)
    y = fn(w, hs)
    yr = y.reshape(e_loc, size, c, -1).transpose(1, 0, 2, 3)
    out = jax.lax.all_to_all(yr, axis, split_axis=0, concat_axis=0,
                             tiled=False)
    return out.reshape(e, c, out.shape[-1])


def _ep_overlap_ticks(fn, axis, size, w, h):
    """The shared overlap ring schedule: tick t processes the chunk from
    source peer (r - t) mod N while shipping the next one."""
    n = size
    e, c, d = h.shape
    e_loc = e // n
    r = jax.lax.axis_index(axis)
    hr = h.reshape(n, e_loc, c, d)
    # t = 0: my own chunk, no communication
    chunk0 = jax.lax.dynamic_slice_in_dim(hr, r, 1, axis=0)[0]
    y0 = fn(w, chunk0)
    out = jnp.zeros((n, e_loc, c, y0.shape[-1]), y0.dtype)
    out = jax.lax.dynamic_update_slice_in_dim(out, y0[None], r, axis=0)
    for t in range(1, n):
        perm_t = [(i, (i + t) % n) for i in range(n)]
        perm_back = [(i, (i - t) % n) for i in range(n)]
        # ship the chunk destined for peer (r+t); receive, from peer (r-t),
        # the chunk it dispatched to my experts
        send = jax.lax.dynamic_slice_in_dim(hr, (r + t) % n, 1, axis=0)[0]
        recv = taint("ep.a2a.tick",
                     jax.lax.ppermute(send, axis, perm_t))
        y = fn(w, recv)
        # return the result to its source; symmetrically receive my chunk's
        # result back from peer (r+t)
        yb = jax.lax.ppermute(y, axis, perm_back)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, yb[None], (r + t) % n, axis=0)
    return out.reshape(e, c, out.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ep_a2a_overlap(fn, axis, size, w, h):
    return _ep_overlap_ticks(fn, axis, size, w, h)


def _ep_overlap_fwd(fn, axis, size, w, h):
    out = _ep_overlap_ticks(fn, axis, size, w, h)
    # residuals are the *inputs* only — the backward re-runs the dispatch
    # ring to recover the received chunks (remat over the wire, same policy
    # as the tp/cp rings: keep O(E·C) live, trade a second ring of ticks)
    return out, (w, h)


def _ep_overlap_bwd(fn, axis, size, res, dout):
    w, h = res
    n = size
    e, c, d = h.shape
    e_loc = e // n
    r = jax.lax.axis_index(axis)
    hr = h.reshape(n, e_loc, c, d)
    dr = dout.reshape(n, e_loc, c, dout.shape[-1])

    # t = 0: my own chunk's VJP, no communication
    chunk0 = jax.lax.dynamic_slice_in_dim(hr, r, 1, axis=0)[0]
    dy0 = jax.lax.dynamic_slice_in_dim(dr, r, 1, axis=0)[0]
    _, vjp = jax.vjp(fn, w, chunk0)
    dw, dchunk = vjp(dy0)
    dh = jnp.zeros_like(hr)
    dh = jax.lax.dynamic_update_slice_in_dim(dh, dchunk[None], r, axis=0)
    for t in range(1, n):
        perm_t = [(i, (i + t) % n) for i in range(n)]
        perm_back = [(i, (i - t) % n) for i in range(n)]
        # recompute the chunk my experts saw at forward tick t (dispatch
        # direction), and ship the matching output cotangent the same way:
        # source (r-t)'s dout slot for peer r travels the t-step ring too
        recv = jax.lax.ppermute(
            jax.lax.dynamic_slice_in_dim(hr, (r + t) % n, 1, axis=0)[0],
            axis, perm_t)
        dy = jax.lax.ppermute(
            jax.lax.dynamic_slice_in_dim(dr, (r + t) % n, 1, axis=0)[0],
            axis, perm_t)
        _, vjp = jax.vjp(fn, w, recv)
        dw_t, dchunk = vjp(dy)
        dw = jax.tree_util.tree_map(jnp.add, dw, dw_t)
        # dchunk is d/d(source (r-t)'s dispatch buffer for me): ship it back
        # along the combine direction; receive my own chunk's gradient from
        # peer (r+t)
        dback = jax.lax.ppermute(dchunk, axis, perm_back)
        dh = jax.lax.dynamic_update_slice_in_dim(
            dh, dback[None], (r + t) % n, axis=0)
    return dw, dh.reshape(e, c, d)


_ep_a2a_overlap.defvjp(_ep_overlap_fwd, _ep_overlap_bwd)


def dispatch_ep_a2a(fn, w, h, *, axis, size: int, impl: str = "auto"):
    """The EP dispatch → expert-compute → combine exchange, one seam.

    ``h``: (E, C, d) per-rank dispatch buffers for all E *global* experts
    (E divisible by ``size``; each rank owns the e_loc = E/size experts of
    its ring slot, blocked contiguously). ``fn(w, chunk)`` applies the local
    experts to a ``(e_loc, C', d)`` row block and must be row-wise (per-row
    independent, shape-polymorphic in C') so per-peer chunk application
    equals the concatenated buffer — pass a hashable static callable (e.g. a
    ``functools.partial`` of a module-level function); it is traced inside a
    ``custom_vjp`` on the overlap path. ``axis`` is the mesh axis (or axis
    tuple, for the folded cp×model ring) the exchange runs over. Returns the
    combined (E, C, f) buffer in dispatch order.
    """
    choice = select_ep_impl(impl)
    if size == 1:
        return fn(w, h)
    if h.shape[0] % size:
        raise ValueError(
            f"global expert dim {h.shape[0]} must divide ep ring size {size}")
    if choice == "blocking":
        return _ep_a2a_blocking(fn, axis, size, w, h)
    return _ep_a2a_overlap(fn, axis, size, w, h)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunk scan


@_tainted("kernel.ssd")
def dispatch_ssd_scan(x, dt, A, B, C, *, chunk: int, impl: str = "auto",
                      initial_state=None,
                      interpret: Optional[bool] = None):
    """Model layout: x (B, L, H, P), dt (B, L, H), A (H,), B/C (B, L, G, N).
    Returns (y (B, L, H, P) fp32, final_state (B, H, P, N) fp32).

    Unaligned lengths are padded to the chunk boundary with ``dt = 0`` steps
    (decay exp(0)=1, zero input: the state rides through unchanged), never
    collapsed into one whole-sequence chunk with an O(L²) decay matrix.
    """
    from repro.models.ssm import ssd_scan  # noqa: PLC0415 (import cycle)

    b, l, h, p = x.shape
    chunk = min(int(chunk), l)
    l_pad = -(-l // chunk) * chunk
    if l_pad != l:
        x = _pad_seq(x, 1, l_pad)
        dt = _pad_seq(dt, 1, l_pad)
        B = _pad_seq(B, 1, l_pad)
        C = _pad_seq(C, 1, l_pad)

    choice = select_ssd_impl(impl, has_initial_state=initial_state is not None,
                             lanes_ok=has_lane_block(h, B.shape[2], p))
    if choice == "pallas":
        # x and y stay as they are: the kernel reads the model's layout
        with jax.named_scope(LAYOUT_SCOPE):
            dt = dt.transpose(0, 2, 1)
            B, C = B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3)
        y, state = ssd_chunk_scan(x, dt, A, B, C, chunk=chunk,
                                  interpret=resolve_interpret(interpret))
    else:
        y, state = ssd_scan(x, dt, A, B, C, chunk=chunk,
                            initial_state=initial_state)
    return y[:, :l], state
