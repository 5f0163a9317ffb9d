"""Pallas TPU kernels for the compute hot spots the survey optimizes — all
three now fully differentiable and plan-selectable:

- flash_attention (survey §5.1.1) — online-softmax tiled attention; the
  forward emits per-row logsumexp and ``jax.custom_vjp`` ties it to
  FlashAttention-2-style dq / dkv recompute kernels.
- grouped_gemm / expert_gemm (survey §4.1.5) — MoE per-expert GEMM with
  ``group_sizes`` padding-row masking (tile skip for imbalanced experts); the
  backward runs two more grouped GEMMs (dx = dy·wᵀ, dw = xᵀ·dy) through the
  same tiled kernel.
- ssd_chunk_scan (Mamba2 SSD, §Perf pair B) — fused chunked state-space scan;
  the forward saves only per-chunk entering states and a reversed-grid
  backward kernel recomputes the decay/score tiles in VMEM, so the
  (b, c, h, q, q) decay tensor never hits HBM in either pass.

Dispatch (``dispatch.py``): model layers reach each kernel through its per-op
dispatcher with the matching :class:`~repro.core.config.ParallelPlan` knob —
``dispatch_attention``/``attn_impl``, ``dispatch_expert_gemm``/
``moe_gemm_impl``, ``dispatch_ssd_scan``/``ssm_impl``. Shared rules:

- ``"xla"``    — the pure-jnp twins (models/layers.py attention,
  masked einsum, models/ssm.py ssd_scan); kept as the gradient oracles.
- ``"pallas"`` — the fused kernel (interpret mode off-TPU); raises when hard
  preconditions fail (traced mask params, SSD initial state).
- ``"auto"``   — pallas only on TPU backends (where its preconditions hold);
  XLA everywhere else.

Each kernel has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py;
tests sweep shapes/dtypes/grads and assert allclose in interpret mode, and
tests/test_tpu_compile.py compiles each one for a described TPU v5e.
"""

from .dispatch import (
    dispatch_attention,
    dispatch_expert_gemm,
    dispatch_ssd_scan,
    select_gemm_impl,
    select_impl,
    select_ssd_impl,
)
from .ops import expert_gemm, flash_attention, ssd_chunk_scan
from . import ref

__all__ = ["dispatch_attention", "dispatch_expert_gemm", "dispatch_ssd_scan",
           "expert_gemm", "flash_attention", "select_gemm_impl",
           "select_impl", "select_ssd_impl", "ssd_chunk_scan", "ref"]
