"""Configuration system.

Two layers of configuration:

- :class:`ModelConfig` — architecture hyperparameters (one instance per assigned
  architecture lives in ``repro/configs/<arch>.py``).
- :class:`ParallelPlan` — how the model is laid out on the mesh, following the
  survey's taxonomy (§4.1): DP sharding factor, tensor parallelism, context
  (sequence) parallelism, expert parallelism, optimizer-state (ZeRO-1)
  sharding, pipeline stages, remat policy.

Everything is a frozen dataclass so configs hash and can key jit caches.

Parallel-composition knobs (survey §4.1) beyond tp/cp/pp at a glance:

====================================  =======================================
knob                                  meaning
====================================  =======================================
``ParallelPlan.ep``                   expert-parallel degree: MoE expert dim
                                      sharded over ``ep`` ranks, folded onto
                                      the cp × model device ring (MoE
                                      parallel folding) — attention keeps its
                                      cp/tp mapping, the MoE sublayer re-reads
                                      the same devices as one flat expert
                                      ring, so ``ep == cp·tp`` when either is
                                      > 1 (ep-only runs over ``model`` with
                                      attention as a cp ring). Executor-only.
``ParallelPlan.ep_impl``              ``auto`` | ``blocking`` | ``overlap``:
                                      how EP dispatch/combine all-to-alls
                                      execute. ``blocking`` = one
                                      ``lax.all_to_all`` each side (exposed);
                                      ``overlap`` = ppermute ring ticks
                                      interleaved with per-peer expert-GEMM
                                      chunks, custom-VJP reversed-ring
                                      backward; ``auto`` = overlap
====================================  =======================================

Robustness knobs (survey §8) at a glance:

====================================  =======================================
knob                                  meaning
====================================  =======================================
``ParallelPlan.integrity``            ``off`` | ``audit``: per-step uint32
                                      param/grad checksum cross-checked
                                      across replicas → ``sdc`` anomaly
``RecoveryPolicy.sdc``                action on checksum divergence
                                      (default ``rollback``)
``RecoveryPolicy.ckpt_io``            action on exhausted persist retries
                                      (default ``ignore``)
``CheckpointManager(keep=K)``         keep-last-K GC; corrupt checkpoints are
                                      skipped on restore, so K > 1 is the
                                      fallback budget
``CheckpointManager(io_retries=N,     persist-write retry loop: N attempts,
  io_backoff=s, io_timeout=T)``       exponential backoff starting at ``s``
                                      seconds, cumulative deadline ``T``
``RecoveryPolicy.ckpt_memory_keep``   hot in-memory checkpoint tier: RAM ring
                                      of the last K snapshots restored
                                      *before* any disk walk (0 disables;
                                      ``--ckpt-memory-keep``)
``RecoveryPolicy.peer_redundancy``    mirror each host-group's RAM shards
                                      onto its ring neighbor so one lost
                                      group rebuilds from surviving peers
                                      (``--no-peer-redundancy`` to disable)
``RecoveryPolicy.preempt_grace``      seconds of grace after SIGTERM/SIGUSR1
                                      for the just-in-time snapshot; tier
                                      picked from measured persist time
                                      (``--preempt-grace``)
``RecoveryPolicy.flight_len``         crash flight recorder: ring capacity
                                      of per-step events dumped to JSON on
                                      preemption/crash/RecoveryExhausted
                                      (``--flight-len``, ``--flight-path``)
``ParallelPlan.pp_layout``            uneven layers-per-stage pipeline
                                      partition (Malleus-style, survey §8.1):
                                      tuple summing to ``n_layers``; ``None``
                                      = even split. A ``pp_layout`` change is
                                      a *reshard*, not a refusal, so the
                                      straggler rebalance restarts through
                                      the elastic checkpoint path
``RecoveryPolicy.straggler``          action on a fail-slow attribution from
                                      ``ft/straggler`` (default ``ignore``;
                                      the ladder is ignore → ``rebalance``
                                      (re-partition ``pp_layout`` from
                                      measured per-stage times) → ``remesh``;
                                      ``--on-straggler``)
``RecoveryPolicy.straggler_factor``   relative slowdown threshold: a rank is
                                      slow when its section time exceeds
                                      ``factor ×`` its peers' median (or its
                                      own trailing median for global
                                      sections) (``--straggler-factor``)
``RecoveryPolicy.straggler_window``   sliding window (observations) of
                                      per-(section, rank) timings kept by
                                      the detector (``--straggler-window``)
``RecoveryPolicy.straggler_confirm``  consecutive slow observations before a
                                      ``straggler`` anomaly is raised — the
                                      detection latency in steps
                                      (``--straggler-confirm``)
``RecoveryPolicy.straggler_min_seconds``  absolute slowdown floor; below it
                                      the relative test never fires
                                      (scheduler jitter guard)
====================================  =======================================
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Tuple


class Family:
    DENSE = "dense"
    MOE = "moe"
    SSM = "ssm"
    HYBRID = "hybrid"
    AUDIO = "audio"   # encoder-decoder with audio-frame frontend stub
    VLM = "vlm"       # decoder with vision-patch frontend stub


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_expert: int                 # per-expert FFN hidden size (fine-grained MoE)
    num_shared_experts: int = 0   # DeepSeek-MoE style always-on experts
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    aux_loss_coef: float = 0.01


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128              # SSD chunk length
    conv_bias: bool = False       # a bias on the depthwise conv's channels


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str
    n_layers: int
    d_model: int
    n_heads: int                  # 0 for attention-free archs
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    qkv_bias: bool = False
    pos_emb: str = "rope"         # "rope" | "sinusoidal" (whisper)
    rope_theta: float = 1e4
    rms_eps: float = 1e-5
    tie_embeddings: bool = False

    # gemma2-style features
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0
    sliding_window: int = 0       # 0 -> full attention
    local_global_alternating: bool = False  # even layers local (sliding), odd global
    long_context: bool = False    # beyond-paper: force all layers sliding-window
    post_norm: bool = False       # gemma2 post-sub-block RMSNorms
    scale_embed: bool = False     # gemma: embeddings scaled by sqrt(d_model)

    # family extras
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None

    # hybrid (zamba2): apply a weight-shared attention block every k ssm layers
    shared_attn_every: int = 0

    # hybrid, the published Zamba2 block (transformers 4.57
    # models/zamba2/modeling_zamba2.py): a non-empty ``hybrid_layer_ids``
    # selects it and places it (``shared_attn_every`` is then not read).
    # Application j, before Mamba-2 layer hybrid_layer_ids[j], runs shared
    # block j % num_mem_blocks, then its own MLP adapter and output linear;
    # ids at or beyond n_layers are left out with their layers.
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 1
    adapter_rank: int = 0         # the per-application MLP adapter's rank

    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_frames: int = 1500        # audio frontend stub: frame-embedding count

    # vlm (pixtral)
    vision_tokens: int = 0        # patch-embedding count supplied by frontend stub

    # citation: source paper / model card for this config
    source: str = ""

    def __post_init__(self):
        if self.n_heads and not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    @property
    def shared_applications(self) -> Tuple[int, ...]:
        """The layers (published Zamba2 block) the shared block runs before."""
        return tuple(i for i in self.hybrid_layer_ids if i < self.n_layers)

    @property
    def shared_blocks(self) -> int:
        """Shared blocks the published Zamba2 block holds: those its
        applications use."""
        return min(self.num_mem_blocks, len(self.shared_applications))

    @property
    def is_enc_dec(self) -> bool:
        return self.enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == Family.SSM

    @property
    def sub_quadratic(self) -> bool:
        """Eligible for long_500k decode (see DESIGN.md §4)."""
        if self.family in (Family.SSM, Family.HYBRID):
            return True
        return bool(self.sliding_window) and self.long_context

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-FLOPs in roofline)."""
        d, L, V = self.d_model, self.n_layers, self.vocab
        hd = self.head_dim
        total = V * d                       # embedding
        if not self.tie_embeddings:
            total += V * d                  # lm head

        def attn_params() -> int:
            q = d * self.n_heads * hd
            kv = 2 * d * self.n_kv_heads * hd
            o = self.n_heads * hd * d
            b = (self.n_heads + 2 * self.n_kv_heads) * hd if self.qkv_bias else 0
            return q + kv + o + b

        def mlp_params(dff: int) -> int:
            return 3 * d * dff              # SwiGLU: gate, up, down

        def ssm_params() -> int:
            di = self.ssm.expand * d
            nh = di // self.ssm.head_dim
            ng, ns = self.ssm.n_groups, self.ssm.d_state
            in_proj = d * (2 * di + 2 * ng * ns + nh)
            conv = (di + 2 * ng * ns) * (self.ssm.d_conv + self.ssm.conv_bias)
            out = di * d
            return in_proj + conv + out + 2 * nh  # + A_log, D

        if self.family == Family.SSM:
            total += L * (ssm_params() + d)
        elif self.family == Family.HYBRID:
            total += L * (ssm_params() + d)
            if self.hybrid_layer_ids:
                hq, hkv = self.n_heads * hd, self.n_kv_heads * hd
                block = (2 * d * (hq + 2 * hkv) + hq * d
                         + mlp_params(self.d_ff) + 3 * d)
                app = d * d + self.adapter_rank * (d + 2 * self.d_ff)
                total += (self.shared_blocks * block
                          + len(self.shared_applications) * app)
            elif self.shared_attn_every:
                total += attn_params() + 2 * d  # one shared block
        elif self.family == Family.MOE:
            per_layer = attn_params() + 2 * d
            e = self.moe
            per_layer += d * e.num_experts                       # router
            per_layer += e.num_experts * 3 * d * e.d_expert      # routed experts
            per_layer += e.num_shared_experts * 3 * d * e.d_expert
            total += L * per_layer
        else:  # dense / vlm decoder / audio
            total += L * (attn_params() + mlp_params(self.d_ff) + 2 * d)
            if self.is_enc_dec:
                # encoder layers + decoder cross-attention
                total += self.enc_layers * (attn_params() + mlp_params(self.d_ff) + 2 * d)
                total += L * (attn_params() + d)
        return total

    def active_param_count(self) -> int:
        """Activated params per token (MoE: only top-k + shared experts)."""
        if self.family != Family.MOE:
            return self.param_count()
        e = self.moe
        d, L = self.d_model, self.n_layers
        dense_like = self.param_count()
        inactive = L * (e.num_experts - e.top_k) * 3 * d * e.d_expert
        return dense_like - inactive


def warn_shard_local_routing(cfg: "ModelConfig") -> None:
    """Warn when shard-local MoE routing can drop tokens differently from
    the global-routing GSPMD baseline (the one documented divergence of the
    overlap-TP / cp paths). No-op for non-MoE or no-drop capacity."""
    if cfg.moe is None:
        return
    if cfg.moe.capacity_factor * cfg.moe.top_k >= cfg.moe.num_experts:
        return
    warnings.warn(
        "token-dropping capacity under shard-local MoE routing "
        f"(capacity_factor={cfg.moe.capacity_factor} < "
        f"E/top_k={cfg.moe.num_experts / cfg.moe.top_k:g}): drop decisions "
        "are per data/context shard and may diverge from the global-routing "
        "GSPMD baseline; use capacity_factor >= E/top_k for exact "
        "equivalence", UserWarning, stacklevel=3)


# the ParallelPlan fields that pick a fused kernel or its XLA twin
KERNEL_KNOBS = ("attn_impl", "moe_gemm_impl", "ssm_impl")


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """Distribution strategy per survey §4.

    Axis semantics (see DESIGN.md §3): ``model`` = TP/EP/sequence, ``data`` = DP,
    ``pod`` = DP (default) or pipeline stages.
    """
    tp: int = 1                    # tensor-parallel degree (model axis)
    tp_impl: str = "auto"          # "auto" | "gspmd" | "overlap": how model-axis
                                   # tensor parallelism executes (survey §4.1.2,
                                   # §5.2). "gspmd" annotates layouts and lets
                                   # XLA insert the (blocking) all-reduce after
                                   # every row GEMM. "overlap" is the explicit
                                   # shard_map path (train/tensor_parallel.py):
                                   # collective matmuls decompose the column
                                   # GEMM's all-gather and the row GEMM's
                                   # reduce-scatter into ppermute ring steps
                                   # interleaved with partial GEMM tiles, and
                                   # activations stay sequence-sharded
                                   # (batch, seq/tp) between blocks (Megatron-
                                   # SP). "auto" resolves per backend in
                                   # repro.kernels.dispatch.select_tp_impl:
                                   # overlap on TPU (where the async ppermutes
                                   # actually hide the transfer), gspmd
                                   # elsewhere.
    cp: int = 1                    # context-parallel degree (survey §4.1.4):
                                   # shard the *sequence* dim over a dedicated
                                   # "cp" mesh axis, end to end — the residual
                                   # stream between blocks is
                                   # (batch, seq/(cp·tp), d) and no device
                                   # ever holds the full context. The block
                                   # executor (train/executor.py) owns the
                                   # wiring: attention runs ring or gathered
                                   # KV (``cp_impl``), the Mamba2 SSD scan
                                   # passes per-chunk entering states around
                                   # the cp ring, MoE routes on local
                                   # sequence shards with batch-global aux.
    cp_impl: str = "auto"          # "auto" | "gather" | "ring": how cp
                                   # attention executes. "gather" all-gathers
                                   # K/V over the cp axis (contiguous chunks,
                                   # O(S) KV per device, exact). "ring" keeps
                                   # KV sharded and ppermutes chunks around
                                   # the ring with zigzag causal load
                                   # balancing — the flash kernel runs as the
                                   # inner tile and per-chunk (out, lse)
                                   # partials merge exactly (chunked
                                   # softmax), so attention activation
                                   # memory scales with S/cp. "auto" =
                                   # ring when statically eligible (full
                                   # causal attention), gather otherwise;
                                   # resolved by
                                   # repro.kernels.dispatch.select_cp_impl.
    dp_shard: int = 1              # param sharding factor F over data axis (§4.1.1)
    zero_stage: int = 1            # 0: replicated opt state, 1: shard over data axis
    ep: int = 1                    # expert-parallel degree (survey §4.1.5):
                                   # shard the *expert* dim of MoE layers over
                                   # ``ep`` ranks and exchange token buffers
                                   # with dispatch/combine all-to-alls. The
                                   # expert axis is *folded* onto the existing
                                   # cp × model device ring (MoE parallel
                                   # folding, Megatron-Core arXiv 2504.14960):
                                   # attention keeps its cp/tp mapping while
                                   # the MoE sublayer re-reads the same
                                   # devices as one flat expert ring, so
                                   # ``ep`` must equal cp·tp when either is
                                   # > 1. With tp == cp == 1, ``ep`` ranks
                                   # run on the ``model`` mesh axis and
                                   # attention runs as a cp ring over it
                                   # (sequence-sharded). Executor-only:
                                   # ep > 1 always selects the block-executor
                                   # loss (train/executor.py).
    ep_impl: str = "auto"          # "auto" | "blocking" | "overlap": how the
                                   # EP dispatch/combine all-to-alls execute
                                   # (survey §4.1.5, §5.2). "blocking" is one
                                   # lax.all_to_all before and after the
                                   # expert GEMM — the whole token exchange
                                   # is exposed. "overlap" decomposes each
                                   # all-to-all into ppermute ring ticks
                                   # interleaved with per-peer expert-GEMM
                                   # chunks (each tick computes the chunk it
                                   # already holds while the next is in
                                   # flight), with a custom-VJP mirrored
                                   # reversed-ring backward; resolved by
                                   # repro.kernels.dispatch.select_ep_impl
                                   # ("auto" = overlap — the ring is
                                   # semantically identical everywhere and
                                   # its ticks compile to async DMAs on TPU).
    pp: int = 1                    # pipeline stages over pod axis (1 = pure DP pods)
    pp_layout: Optional[Tuple[int, ...]] = None
                                   # layers-per-stage partition for uneven
                                   # (Malleus-style) pipelining, survey §8.1:
                                   # a tuple of length pp summing to
                                   # cfg.n_layers, each stage >= 1 layer.
                                   # None = the even n_layers/pp split (and
                                   # then n_layers must divide pp). Uneven
                                   # layouts are the fail-slow mitigation:
                                   # a straggling stage gets fewer layers, so
                                   # a degraded device does less work per
                                   # tick instead of stalling the whole ring.
    pp_schedule: str = "1f1b"      # pipeline schedule (§4.1.3): "gpipe" is
                                   # fill-drain with reverse-AD through the
                                   # forward scan (keeps O(M) microbatches of
                                   # activations live); "1f1b" is a custom-VJP
                                   # one-forward-one-backward schedule whose
                                   # backward scan interleaves the mirrored
                                   # drain with forward recompute ticks —
                                   # same loss/grads, O(P) stages of in-flight
                                   # activations.
    microbatches: int = 1          # grad-accumulation / pipeline microbatches
    remat: str = "full"            # activation recomputation (§6.1), applied
                                   # per decoder layer: "none" saves every
                                   # intermediate, "full" recomputes the whole
                                   # layer in the backward, "selective" saves
                                   # only the fused-kernel outputs (flash-attn
                                   # out+lse, expert-GEMM out, SSD chunk
                                   # states — the residuals the custom VJPs
                                   # consume) and recomputes the cheap glue.
    seq_shard_decode: bool = True  # shard KV cache seq dim over model axis
    seq_shard_attn: bool = True    # Megatron-SP/context-parallel: shard the
                                   # query-sequence dim of attention over
                                   # ``model`` (survey §4.1.4) — needed because
                                   # GQA kv_heads < 16 defeats head sharding
    pad_vocab_to_multiple: int = 0 # pad embedding/LM-head vocab dim so it
                                   # divides the model axis (Megatron-style):
                                   # keeps logits vocab-parallel instead of
                                   # all-reducing a (B,S,V) tensor per step.
                                   # Padded logits are masked to -1e9. Under
                                   # tp_impl="overlap" the vocab-parallel
                                   # cross-entropy (train/loss.py
                                   # cross_entropy_vp) completes this: the
                                   # softmax reduces per shard + scalar psum,
                                   # so the full-vocab logits tensor never
                                   # exists.
    dp_over_model: bool = False    # beyond-paper mesh remap: run the model
                                   # axis as extra data parallelism (256-way
                                   # DP). Right for small models where 1-D TP
                                   # activation all-reduces dominate (the
                                   # survey's small-model guidance).
    moe_dispatch: str = "einsum"   # "einsum": GShard one-hot dispatch/combine
                                   # (paper-faithful). "scatter": MegaBlocks-
                                   # inspired index gather/scatter — same
                                   # routing, ~E·C/k less dispatch traffic.
    attn_impl: str = "auto"        # "auto" | "xla" | "pallas": which attention
                                   # implementation train/prefill use (survey
                                   # §5.1.1). Resolved per call site by
                                   # repro.kernels.dispatch — "auto" picks the
                                   # fused Pallas flash kernel on TPU backends
                                   # and the XLA twins elsewhere.
    moe_gemm_impl: str = "auto"    # same choices, for the MoE expert GEMMs
                                   # (survey §4.1.5): "pallas" routes all three
                                   # SwiGLU GEMMs of _expert_ffn through the
                                   # differentiable grouped kernel with
                                   # group_sizes padding-row masking, on both
                                   # the dense and the EP/shard_map paths.
    ssm_impl: str = "auto"         # same choices, for the Mamba2 SSD chunk
                                   # scan: "pallas" keeps the (q, q) decay
                                   # matrices and the running state in VMEM in
                                   # both passes (forward saves only per-chunk
                                   # entering states for the backward).
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    integrity: str = "off"         # "off" | "audit": silent-data-corruption
                                   # defense (survey §8.2). "audit" makes the
                                   # train step emit an exact uint32 bitcast
                                   # checksum of updated params + grads
                                   # (ft/integrity.tree_checksum) and cross-
                                   # check it across every mesh axis with a
                                   # pmax/pmin pair — metrics gain
                                   # "integrity_checksum" and
                                   # "integrity_div" (0.0 = all replicas
                                   # bit-identical); ft/recovery turns a
                                   # nonzero divergence into an "sdc"
                                   # anomaly (policy default: rollback).
                                   # Cost is one elementwise pass + two
                                   # scalar collectives, measured per family
                                   # by BENCH_integrity.json.

    def __post_init__(self):
        if self.pp_layout is not None:
            # normalize to a tuple of ints so the frozen plan stays hashable
            # and JSON-round-tripped layouts ([3, 1]) compare equal
            object.__setattr__(self, "pp_layout",
                               tuple(int(x) for x in self.pp_layout))

    def validate(self, cfg: ModelConfig) -> None:
        if self.integrity not in ("off", "audit"):
            raise ValueError(
                f"integrity must be off|audit, got {self.integrity!r}")
        for knob in KERNEL_KNOBS:
            if getattr(self, knob) not in ("auto", "xla", "pallas"):
                raise ValueError(
                    f"{knob} must be auto|xla|pallas, got {getattr(self, knob)!r}")
        if self.tp_impl not in ("auto", "gspmd", "overlap"):
            raise ValueError(
                f"tp_impl must be auto|gspmd|overlap, got {self.tp_impl!r}")
        if self.remat not in ("none", "selective", "full"):
            raise ValueError(
                f"remat must be none|selective|full, got {self.remat!r}")
        if self.pp_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pp_schedule must be gpipe|1f1b, got {self.pp_schedule!r}")
        if self.cp_impl not in ("auto", "gather", "ring"):
            raise ValueError(
                f"cp_impl must be auto|gather|ring, got {self.cp_impl!r}")
        if self.ep_impl not in ("auto", "blocking", "overlap"):
            raise ValueError(
                f"ep_impl must be auto|blocking|overlap, got {self.ep_impl!r}")
        if isinstance(self.ep, bool):
            raise ValueError(
                "ParallelPlan.ep is an integer expert-parallel degree now "
                "(the legacy bool selected the pre-executor shard_map path, "
                f"which is gone); got ep={self.ep!r} — use ep=<degree>")
        if self.ep < 1:
            raise ValueError(f"ep must be >= 1, got {self.ep}")
        if self.cp < 1:
            raise ValueError(f"cp must be >= 1, got {self.cp}")
        if self.cp > 1:
            if cfg.family not in (Family.DENSE, Family.MOE, Family.SSM):
                raise ValueError(
                    f"cp > 1 supports dense/moe/ssm decoder-only families "
                    f"(the block-executor wiring), got {cfg.family!r}")
            if self.tp > 1 and self.tp_impl == "gspmd":
                raise ValueError(
                    "cp > 1 composes with tp via the executor's explicit "
                    "shard_map rings; set tp_impl='overlap' (or 'auto')")
            if self.dp_over_model:
                raise ValueError("cp > 1 is incompatible with dp_over_model")
        # Documented divergence (PR 4 / cp): with shard-local routing, GShard
        # token-dropping decisions are made per data/context shard while the
        # GSPMD baseline routes globally — same math only when no tokens
        # drop. Flag it loudly instead of silently differing; equivalence
        # tests force no-drop capacity (capacity_factor >= E / top_k).
        # (validate() only sees *explicit* knobs; the executor re-checks
        # against the resolved placement, catching tp_impl="auto"→overlap.)
        if self.cp > 1 or self.tp_impl == "overlap" or self.ep > 1:
            warn_shard_local_routing(cfg)
        if self.ep > 1:
            if cfg.family != Family.MOE:
                raise ValueError(
                    f"expert parallelism requires a MoE arch, got {cfg.family}")
            if self.dp_over_model:
                raise ValueError(
                    "dp_over_model consumes the model axis; EP needs it")
            if self.tp > 1 and self.tp_impl == "gspmd":
                raise ValueError(
                    "ep > 1 composes with tp via the executor's explicit "
                    "shard_map rings; set tp_impl='overlap' (or 'auto')")
            # MoE parallel folding: the expert ring reuses the cp × model
            # devices, so its size is pinned to their product. The ep-only
            # placement (tp == cp == 1 → experts over the model axis) is
            # checked against the actual mesh in executor.resolve_context.
            fold = (self.cp if self.cp > 1 else 1) * \
                   (self.tp if self.tp > 1 else 1)
            if fold > 1 and self.ep != fold:
                raise ValueError(
                    f"ep={self.ep} must equal cp×tp={fold}: the expert axis "
                    "folds onto the existing cp/model device ring (MoE "
                    "parallel folding) — it is a re-mapping of those "
                    "devices, not extra ones")
            if cfg.moe and cfg.moe.num_experts % self.ep != 0:
                raise ValueError(
                    f"ep={self.ep} must divide num_experts="
                    f"{cfg.moe.num_experts} for expert parallelism")
        if self.pp_layout is not None:
            if self.pp <= 1:
                raise ValueError(
                    f"pp_layout requires pp > 1, got pp={self.pp}")
            if len(self.pp_layout) != self.pp:
                raise ValueError(
                    f"pp_layout length {len(self.pp_layout)} != pp={self.pp}")
            if any(x < 1 for x in self.pp_layout):
                raise ValueError(
                    f"pp_layout stages need >= 1 layer, got {self.pp_layout}")
            if sum(self.pp_layout) != cfg.n_layers:
                raise ValueError(
                    f"pp_layout {self.pp_layout} sums to "
                    f"{sum(self.pp_layout)}, expected n_layers={cfg.n_layers}")
        elif self.pp > 1 and cfg.n_layers % self.pp != 0:
            raise ValueError(
                "n_layers must divide pp (or give an explicit pp_layout)")


# ---------------------------------------------------------------------------
# Recovery policy (survey §8): what ft/recovery.run_with_recovery does per
# anomaly kind reported by ft/anomaly.Monitor.

RECOVERY_ACTIONS = ("rollback", "lr_rescue", "remesh", "rebalance", "ignore")


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Anomaly -> action table for the recovery driver (survey §8.3).

    Actions:

    - ``"rollback"``: restore the latest checkpoint and replay (the
      deterministic pipeline makes the replay bit-faithful);
    - ``"lr_rescue"``: rollback, then damp the optimizer through the bad
      region — via the driver's ``rescue_step`` (LR scaled by
      ``rescue_lr_scale``) when one was built, else by skipping the
      offending batch (recorded as a nan in the loss trace);
    - ``"remesh"``: elastic recovery from host loss (survey §8.3.2) —
      rebuild the mesh at reduced size via the driver's ``remesh`` hook and
      :meth:`CheckpointManager.restore_resharded` the state (params + the
      ZeRO-1 moments, re-scattered over the new data axis), then continue
      on the shrunken cluster;
    - ``"rebalance"``: Malleus-style fail-slow mitigation (survey §8.1) —
      re-partition the pipeline's layers-per-stage (``ParallelPlan.
      pp_layout``) from the straggler detector's measured per-stage times
      via the driver's ``rebalance`` hook, restart through an elastic
      checkpoint reshard-restore, and continue degraded-but-faster; only
      meaningful for ``straggler`` anomalies attributed to a pipeline
      stage — other kinds fall back to ``remesh``/``ignore``;
    - ``"ignore"``: log the anomaly and keep going.
    """
    nan: str = "rollback"            # non-finite loss/grad-norm: numerical
                                     # failure — replay is the only safe move
    spike: str = "rollback"          # first loss spike at a step: assume
                                     # transient (bad host, bit flip), replay
    repeated_spike: str = "lr_rescue"  # the same step spikes again after a
                                     # rollback: replay alone is a loop —
                                     # escalate to LR-rescue / skip-batch
                                     # (PaLM-style spike handling)
    hang: str = "ignore"             # slow/hung step: "remesh" shrinks the
                                     # mesh and reshard-restores (needs the
                                     # driver's remesh hook); default ignore
                                     # keeps the watchdog advisory-only
    sdc: str = "rollback"            # cross-replica integrity-checksum
                                     # divergence under plan.integrity=
                                     # "audit": a device produced different
                                     # bits — the state cannot be trusted,
                                     # roll back to the last checkpoint
    straggler: str = "ignore"        # fail-slow attribution from
                                     # ft/straggler (rank, component,
                                     # compute|comm|host-io): the response
                                     # ladder is "ignore" (advisory, the
                                     # default) -> "rebalance" (uneven
                                     # pp_layout re-partition from measured
                                     # per-stage times, restarted through a
                                     # checkpoint reshard) -> "remesh" (evict
                                     # the slow rank's host entirely); a
                                     # rebalance that can't apply (no
                                     # pipeline, non-stage attribution, or
                                     # the same stage already rebalanced)
                                     # escalates to remesh when that hook
                                     # exists
    ckpt_io: str = "ignore"          # checkpoint persist failed after
                                     # io_retries attempts (ft/inject's
                                     # persist_exc, full disk, ...): the
                                     # *run* is still healthy, so default
                                     # ignore — the anomaly is recorded and
                                     # training continues on the older
                                     # checkpoint cadence; "rollback" forces
                                     # an immediate restore instead
    max_restores: int = 3            # give up after this many restores
    rescue_lr_scale: float = 0.1     # LR multiplier while an lr_rescue step
                                     # replays the offending step
    elastic: bool = True             # allow cross-layout restore routing
                                     # (check_plan returns "reshard" instead
                                     # of refusing on a layout change)
    ckpt_memory_keep: int = 2        # hot in-memory checkpoint tier (survey
                                     # §8.3.1, Gemini/CheckFreq): RAM ring of
                                     # the last K snapshots, restored before
                                     # any disk walk; 0 disables the tier
    peer_redundancy: bool = True     # mirror each host-group's RAM shards
                                     # onto its ring neighbor (host-side
                                     # stand-in for the fleet's ring
                                     # ppermute) so a lost group rebuilds
                                     # from surviving peers without disk
    preempt_grace: float = 30.0      # seconds between the preemption notice
                                     # (SIGTERM/SIGUSR1) and the kill: the
                                     # just-in-time snapshot must fit here;
                                     # ft/preempt.choose_tier picks disk when
                                     # measured persist time fits, RAM
                                     # otherwise
    flight_len: int = 256            # crash flight recorder ring capacity
                                     # (events, not steps); the ring is
                                     # dumped to JSON on preemption, crash,
                                     # or RecoveryExhausted
    straggler_factor: float = 2.0    # relative slowdown threshold: a rank is
                                     # slow when its section time exceeds
                                     # factor x the median of its peers (or
                                     # of its own trailing window for
                                     # global sections)
    straggler_window: int = 16       # sliding window (observations) kept per
                                     # (section, rank) by the detector
    straggler_confirm: int = 3       # consecutive slow observations before
                                     # the anomaly is raised — this IS the
                                     # detection latency in steps
    straggler_min_seconds: float = 5e-3
                                     # absolute slowdown floor (seconds above
                                     # baseline); below it the relative test
                                     # never fires, so scheduler jitter on
                                     # sub-ms sections can't page anyone

    def validate(self) -> None:
        for knob in ("nan", "spike", "repeated_spike", "hang", "sdc",
                     "ckpt_io", "straggler"):
            if getattr(self, knob) not in RECOVERY_ACTIONS:
                raise ValueError(
                    f"{knob} action must be one of {RECOVERY_ACTIONS}, "
                    f"got {getattr(self, knob)!r}")
        if self.max_restores < 0:
            raise ValueError(f"max_restores must be >= 0, got {self.max_restores}")
        if not 0.0 < self.rescue_lr_scale <= 1.0:
            raise ValueError(
                f"rescue_lr_scale must be in (0, 1], got {self.rescue_lr_scale}")
        if self.ckpt_memory_keep < 0:
            raise ValueError(
                f"ckpt_memory_keep must be >= 0, got {self.ckpt_memory_keep}")
        if self.preempt_grace <= 0.0:
            raise ValueError(
                f"preempt_grace must be > 0, got {self.preempt_grace}")
        if self.flight_len < 1:
            raise ValueError(
                f"flight_len must be >= 1, got {self.flight_len}")
        if self.straggler_factor <= 1.0:
            raise ValueError(
                f"straggler_factor must be > 1, got {self.straggler_factor}")
        if self.straggler_window < 4:
            raise ValueError(
                f"straggler_window must be >= 4, got {self.straggler_window}")
        if self.straggler_confirm < 1:
            raise ValueError(
                f"straggler_confirm must be >= 1, got {self.straggler_confirm}")
        if self.straggler_min_seconds < 0.0:
            raise ValueError(
                f"straggler_min_seconds must be >= 0, "
                f"got {self.straggler_min_seconds}")


# ---------------------------------------------------------------------------
# Input shapes assigned to this paper (fixed public pool).

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES: Tuple[InputShape, ...] = (
    InputShape("train_4k", 4_096, 256, "train"),
    InputShape("prefill_32k", 32_768, 32, "prefill"),
    InputShape("decode_32k", 32_768, 128, "decode"),
    InputShape("long_500k", 524_288, 1, "decode"),
)

SHAPES_BY_NAME = {s.name: s for s in INPUT_SHAPES}
