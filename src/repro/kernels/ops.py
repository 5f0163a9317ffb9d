"""Jit'd public wrappers around the Pallas kernels.

These are the entry points model layers reach through the dispatch layer
(``repro.kernels.dispatch``, driven by ``ParallelPlan.attn_impl`` /
``moe_gemm_impl`` / ``ssm_impl``). On real TPU hardware they compile; the CPU
container exercises them in interpret mode (``interpret=None`` auto-detects
the backend for every op). All three are differentiable — ``jax.grad``
through them runs the custom-VJP Pallas backward kernels.
"""

from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention as _flash
from .grouped_gemm import expert_gemm as _expert_gemm
from .ssd_scan import ssd_chunk_scan as _ssd


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "q_offset", "block_q", "block_k",
    "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
                    q_offset=0, block_q=128, block_k=128, interpret=None):
    """(B, Hq, S, hd) attention; GQA via kv-head broadcast in the index map.

    Differentiable: ``jax.grad`` through this runs the FlashAttention-2-style
    dq / dkv Pallas kernels (see flash_attention.py).
    """
    return _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                  scale=scale, q_offset=q_offset, block_q=block_q,
                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "block_c", "block_f", "block_d", "interpret"))
def expert_gemm(x, w, group_sizes=None, *, block_c=128, block_f=128,
                block_d=256, interpret=None):
    """(E, C, d) × (E, d, f) -> (E, C, f) per-expert GEMM; ``group_sizes``
    masks each expert's padding rows out of the output and both gradients.

    Differentiable: the backward runs two more grouped GEMMs (dx = dy·wᵀ,
    dw = xᵀ·dy) through the same tiled kernel (see grouped_gemm.py).
    """
    return _expert_gemm(x, w, group_sizes, block_c=block_c, block_f=block_f,
                        block_d=block_d, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, dt, A, Bm, Cm, *, chunk=128, interpret=None):
    """Fused Mamba2 SSD: x (B,L,H,P), dt (B,H,L), B/C (B,G,L,N) -> (y
    (B,L,H,P), final_state); the intra-chunk decay matrices and the running
    state stay in VMEM.

    Differentiable: the forward saves only per-chunk entering states and the
    backward kernel recomputes the decay/score tiles (see ssd_scan.py).
    """
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
