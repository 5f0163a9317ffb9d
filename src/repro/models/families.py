"""Model assembly for all assigned architecture families.

Unified functional API (``build_model`` returns a :class:`Model`):

- ``init(rng) -> params``                       (fp32 master weights)
- ``forward(params, batch) -> (logits, aux)``   (train / prefill)
- ``init_cache(batch, max_seq) -> cache``       (decode state, zeros)
- ``decode_step(params, cache, tokens, pos) -> (logits, cache)``

Layer stacks are built with ``jax.vmap`` over per-layer RNGs and executed with
``jax.lax.scan`` so HLO size is O(1) in depth (compile-time hygiene, DESIGN.md
§5). Per-layer heterogeneity (gemma2 local/global alternation) rides along as a
scanned metadata array rather than unrolled python branches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.config import KERNEL_KNOBS, Family, ModelConfig, ParallelPlan
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import (
    attention,
    dense_init,
    init_attn,
    init_mlp,
    mlp_block,
    qkv_proj,
    rms_norm,
    rope,
    sinusoidal_pos_emb,
    split_tree,
)


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[[jax.Array], Any]
    forward: Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, jax.Array]]
    init_cache: Callable[[int, int], Any]
    decode_step: Callable[[Any, Any, jax.Array, jax.Array], Tuple[jax.Array, Any]]
    extras: Dict[str, Callable] = {}


# ---------------------------------------------------------------------------
# helpers

def _stacked_init(rng, n: int, fn: Callable[[jax.Array], Any]) -> Any:
    """Stack per-layer params along a new leading dim via vmap over rngs."""
    return jax.vmap(fn)(jax.random.split(rng, n))


# Selective-remat save set (survey §6.1): the fused-kernel outputs and the
# residuals their custom VJPs consume — flash-attention out + per-row
# logsumexp, the grouped expert-GEMM output, the SSD per-chunk entering
# states — plus the glue-level block outputs the XLA twins tag. Everything
# else (norms, projections, rotary, SwiGLU glue) is cheap to recompute.
REMAT_SAVE_NAMES: Tuple[str, ...] = (
    "flash_out", "flash_lse",        # kernels/flash_attention.py fwd residuals
    "expert_gemm_out",               # kernels/grouped_gemm.py fwd output
    "ssd_out", "ssd_state",          # kernels/ssd_scan.py output + chunk states
    "attn_out", "block_out",         # glue-level tags (XLA twin paths)
)


def _remat(f, mode: str):
    """Per-decoder-layer activation recomputation (``plan.remat``).

    ``none`` differentiates normally (every intermediate saved), ``full``
    recomputes the whole layer in the backward, ``selective`` saves only
    :data:`REMAT_SAVE_NAMES` and recomputes the cheap glue around the kernels.
    """
    if mode == "none":
        return f
    if mode == "selective":
        pol = jax.checkpoint_policies.save_only_these_names(*REMAT_SAVE_NAMES)
        return jax.checkpoint(f, policy=pol)
    return jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)


def _layer_windows(cfg: ModelConfig) -> np.ndarray:
    """Per-layer sliding-window size (0 = full attention)."""
    if cfg.long_context and cfg.sliding_window:
        return np.full((cfg.n_layers,), cfg.sliding_window, np.int32)
    if cfg.local_global_alternating and cfg.sliding_window:
        w = np.zeros((cfg.n_layers,), np.int32)
        w[0::2] = cfg.sliding_window          # even layers local (gemma2)
        return w
    return np.full((cfg.n_layers,), cfg.sliding_window, np.int32)


def _padded_vocab(cfg: ModelConfig, plan: Optional[ParallelPlan]) -> int:
    m = plan.pad_vocab_to_multiple if plan else 0
    if not m:
        return cfg.vocab
    return -(-cfg.vocab // m) * m


def _logits(params, x, cfg: ModelConfig, dtype, plan: Optional[ParallelPlan] = None):
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(dtype).T
    else:
        w = params["lm_head"]["w"].astype(dtype)
    logits = x @ w
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = c * jnp.tanh(logits / c)
    logits = logits.astype(jnp.float32)
    vp = logits.shape[-1]
    if vp != cfg.vocab:
        # Megatron-style padded vocab: mask the padded tail out of the softmax
        pad_mask = jnp.arange(vp) >= cfg.vocab
        logits = jnp.where(pad_mask, -1e9, logits)
    return logits


def _embed(params, tokens, cfg: ModelConfig, dtype):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(dtype)
        if cfg.scale_embed:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
        return x


def _head(params, x, cfg: ModelConfig, dtype, aux):
    """The final norm and the logits of a training forward, under the
    ``head`` scope (the loss's cross-entropy joins it in ``train/step``)."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        return _logits(params, x, cfg, dtype), aux


def _residual_constrainer(mesh, batch_axes):
    """Anchor the (B, S, d) residual stream's batch sharding. GSPMD propagation
    can silently replicate the batch over mesh axes that only appear in the
    batch spec (e.g. the dp_over_model remap) — one constraint per scan body
    pins it."""
    if mesh is None or not batch_axes:
        return lambda x: x
    baxes = batch_axes

    def cx(x):
        if x.ndim != 3:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(baxes, None, None)))
    return cx


def _seq_constrainers(plan, mesh, batch_axes):
    """Megatron-SP / context-parallel constraints (survey §4.1.4).

    Returns (cq, ckv): ``cq`` shards a (B, S, H, hd) tensor's sequence dim over
    ``model`` (queries + attention output); ``ckv`` pins K/V replicated over
    ``model`` (each query shard attends to full KV — exact attention, the
    all-gather is one (B,T,Hkv,hd) tensor vs. a (B,S,S)-sized score matrix).
    No-ops when disabled or when shapes don't divide.
    """
    if mesh is None or plan is None or not plan.seq_shard_attn \
            or "model" not in mesh.shape or "model" in (batch_axes or ()):
        ident = lambda x: x
        return ident, ident
    tp = mesh.shape["model"]
    baxes = batch_axes if batch_axes else None

    def cq(x):
        if x.ndim != 4 or x.shape[1] % tp or x.shape[1] < tp:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(baxes, "model", None, None)))

    def ckv(x):
        if x.ndim != 4:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(baxes, None, None, None)))

    return cq, ckv


# ---------------------------------------------------------------------------
# decoder-only transformer (dense / moe / vlm backbone)

def _init_decoder_layer(cfg: ModelConfig):
    def one(rng):
        r = split_tree(rng, 2)
        p = {
            "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "norm2": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "attn": init_attn(r[0], cfg),
        }
        if cfg.post_norm:
            p["norm1_post"] = {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}
            p["norm2_post"] = {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}
        if cfg.family == Family.MOE:
            p["moe"] = moe_lib.init_moe(r[1], cfg)
        else:
            p["mlp"] = init_mlp(r[1], cfg.d_model, cfg.d_ff)
        return p
    return one


def _decoder_layer_fwd(cfg: ModelConfig, dtype, mesh, plan, batch_axes,
                       collect_kv: bool = False):
    """The dense/MoE decoder layer body — one wiring for every placement.

    Routes through the unified block executor (``repro.train.executor``)
    with a *local* ParallelContext: identity collectives, the GSPMD
    seq-shard/residual constrainers as placement hooks. The overlap-TP and
    context-parallel paths build the same layer with ring contexts instead
    — the family math is defined once, the executor decides placement.
    """
    from repro.train import executor as exlib  # noqa: PLC0415 (import cycle)
    cq, ckv = _seq_constrainers(plan, mesh, batch_axes)
    cx = _residual_constrainer(mesh, batch_axes)
    ctx = exlib.local_context(mesh=mesh, batch_axes=tuple(batch_axes or ()),
                              cx=cx, cq=cq, ckv=ckv)
    return exlib.decoder_layer(ctx, cfg, plan, dtype, collect_kv=collect_kv)


def build_decoder_only(cfg: ModelConfig, plan: Optional[ParallelPlan] = None,
                       mesh=None, batch_axes=("data",)) -> Model:
    plan = plan or ParallelPlan()
    dtype = jnp.dtype(plan.compute_dtype)
    windows = jnp.asarray(_layer_windows(cfg))

    def init(rng):
        r = split_tree(rng, 3)
        params = {
            "embed": {"tok": dense_init(r[0], (_padded_vocab(cfg, plan), cfg.d_model), in_axis=-1)},
            "layers": _stacked_init(r[1], cfg.n_layers, _init_decoder_layer(cfg)),
            "final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": dense_init(r[2], (cfg.d_model, _padded_vocab(cfg, plan)))}
        return params

    layer_fwd = _decoder_layer_fwd(cfg, dtype, mesh, plan, batch_axes)

    def forward(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = _embed(params, tokens, cfg, dtype)
        if cfg.family == Family.VLM and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(dtype)      # (B, N_img, d)
            vp = batch["vision_pos"]                       # (B, N_img)
            x = x.at[jnp.arange(b)[:, None], vp].set(ve)
        positions = jnp.arange(s)
        if cfg.pos_emb == "sinusoidal":
            x = x + sinusoidal_pos_emb(positions, cfg.d_model).astype(dtype)

        def body(carry, xs):
            xc, aux = carry
            lp, w = xs
            xn, a = layer_fwd(xc, lp, w, positions)
            return (xn, aux + a), None

        body = _remat(body, plan.remat)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                   (params["layers"], windows))
        return _head(params, x, cfg, dtype, aux)

    def init_cache(batch: int, max_seq: int):
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "k": jnp.zeros((cfg.n_layers, batch, max_seq, hkv, hd), dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_seq, hkv, hd), dtype),
        }

    def decode_step(params, cache, tokens, pos):
        from repro.serve.attention import decode_attention  # noqa: PLC0415
        b = tokens.shape[0]
        x = _embed(params, tokens, cfg, dtype)[:, None, :]   # (B, 1, d)
        positions = jnp.asarray(pos)[None]
        if cfg.pos_emb == "sinusoidal":
            x = x + sinusoidal_pos_emb(positions, cfg.d_model).astype(dtype)[None]

        def body(x, xs):
            lp, kc, vc, w = xs
            h = rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
            q, k, v = qkv_proj(lp["attn"], h, cfg, dtype)
            if cfg.pos_emb == "rope":
                q = rope(q, positions, cfg.rope_theta)
                k = rope(k, positions, cfg.rope_theta)
            a, kc, vc = decode_attention(q, kc, vc, k, v, pos, window=w,
                                         softcap=cfg.attn_logit_softcap,
                                         mesh=mesh, batch_axes=batch_axes)
            a = a.reshape(b, 1, -1) @ lp["attn"]["wo"].astype(dtype)
            if cfg.post_norm:
                a = rms_norm(a, lp["norm1_post"]["scale"], cfg.rms_eps)
            x = x + a
            h = rms_norm(x, lp["norm2"]["scale"], cfg.rms_eps)
            if cfg.family == Family.MOE:
                m, _ = moe_lib.moe_block(lp["moe"], h, cfg, dtype, mesh, plan,
                                         batch_axes)
            else:
                m = mlp_block(lp["mlp"], h, dtype)
            if cfg.post_norm:
                m = rms_norm(m, lp["norm2_post"]["scale"], cfg.rms_eps)
            return x + m, (kc, vc)

        # decode sliding window must be static per layer for mask simplicity;
        # pass the per-layer window array as scanned metadata.
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], windows))
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        logits = _logits(params, x[:, 0, :], cfg, dtype)
        return logits, {"k": ks, "v": vs}

    def prefill(params, batch, max_seq: int):
        """Process a prompt in parallel and return (logits, filled cache).

        The production serving flow: prefill once (full forward, KV emitted per
        layer) then call decode_step from position S onward.
        """
        tokens = batch["tokens"]
        b, s = tokens.shape
        assert s <= max_seq
        x = _embed(params, tokens, cfg, dtype)
        if cfg.family == Family.VLM and "vision_embeds" in batch:
            ve = batch["vision_embeds"].astype(dtype)
            vp = batch["vision_pos"]
            x = x.at[jnp.arange(b)[:, None], vp].set(ve)
        positions = jnp.arange(s)
        if cfg.pos_emb == "sinusoidal":
            x = x + sinusoidal_pos_emb(positions, cfg.d_model).astype(dtype)

        layer_kv = _decoder_layer_fwd(cfg, dtype, mesh, plan, batch_axes,
                                      collect_kv=True)

        def body(carry, xs):
            xc, aux = carry
            lp, w = xs
            xn, a, kv = layer_kv(xc, lp, w, positions)
            return (xn, aux + a), kv

        (x, aux), (ks, vs) = jax.lax.scan(
            body, (x, jnp.float32(0.0)), (params["layers"], windows))
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        logits = _logits(params, x, cfg, dtype)

        cache = init_cache(b, max_seq)
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], ks, 0, axis=2),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], vs, 0, axis=2),
        }
        return logits, cache

    return Model(cfg, init, forward, init_cache, decode_step,
                 extras={"prefill": prefill})


# ---------------------------------------------------------------------------
# SSM (mamba2) — attention-free

def build_ssm(cfg: ModelConfig, plan: Optional[ParallelPlan] = None,
              mesh=None, batch_axes=("data",)) -> Model:
    plan = plan or ParallelPlan()
    dtype = jnp.dtype(plan.compute_dtype)
    cx = _residual_constrainer(mesh, batch_axes)

    def init_layer(rng):
        return {
            "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "ssm": ssm_lib.init_ssm(rng, cfg),
        }

    def init(rng):
        r = split_tree(rng, 3)
        params = {
            "embed": {"tok": dense_init(r[0], (_padded_vocab(cfg, plan), cfg.d_model), in_axis=-1)},
            "layers": _stacked_init(r[1], cfg.n_layers, init_layer),
            "final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": dense_init(r[2], (cfg.d_model, _padded_vocab(cfg, plan)))}
        return params

    def forward(params, batch):
        from repro.train import executor as exlib  # noqa: PLC0415
        tokens = batch["tokens"]
        x = _embed(params, tokens, cfg, dtype)
        layer = exlib.ssm_layer(
            exlib.local_context(mesh=mesh,
                                batch_axes=tuple(batch_axes or ()), cx=cx),
            cfg, plan, dtype)

        def body(carry, lp):
            xn, _ = layer(carry, lp, None, None)
            return xn, None

        body = _remat(body, plan.remat)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return _head(params, x, cfg, dtype, jnp.float32(0.0))

    def init_cache(batch: int, max_seq: int):
        one = ssm_lib.init_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)

    def decode_step(params, cache, tokens, pos):
        x = _embed(params, tokens, cfg, dtype)               # (B, d)

        def body(x, xs):
            lp, c = xs
            h = rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
            y, c = ssm_lib.ssm_step(lp["ssm"], h, c, cfg, dtype)
            return x + y, c

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        return _logits(params, x, cfg, dtype), new_cache

    return Model(cfg, init, forward, init_cache, decode_step)


# ---------------------------------------------------------------------------
# hybrid (zamba2): mamba2 backbone + weight-shared attention block

def build_hybrid(cfg: ModelConfig, plan: Optional[ParallelPlan] = None,
                 mesh=None, batch_axes=("data",)) -> Model:
    if cfg.hybrid_layer_ids:
        return build_zamba2(cfg, plan, mesh, batch_axes)
    plan = plan or ParallelPlan()
    dtype = jnp.dtype(plan.compute_dtype)
    every = cfg.shared_attn_every
    n_apps = cfg.n_layers // every
    covered = n_apps * every
    rest = cfg.n_layers - covered

    def init_layer(rng):
        return {
            "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "ssm": ssm_lib.init_ssm(rng, cfg),
        }

    def init(rng):
        r = split_tree(rng, 5)
        params = {
            "embed": {"tok": dense_init(r[0], (_padded_vocab(cfg, plan), cfg.d_model), in_axis=-1)},
            "layers": _stacked_init(r[1], cfg.n_layers, init_layer),
            "shared_attn": {
                "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
                "norm2": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
                "attn": init_attn(r[2], cfg),
                "mlp": init_mlp(r[3], cfg.d_model, cfg.d_ff),
            },
            "final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "lm_head": {"w": dense_init(r[4], (cfg.d_model, _padded_vocab(cfg, plan)))},
        }
        return params

    def _split_groups(layers):
        head = jax.tree.map(lambda a: a[:covered].reshape(
            (n_apps, every) + a.shape[1:]), layers)
        tail = jax.tree.map(lambda a: a[covered:], layers)
        return head, tail

    def _ssm_layers(x, stacked, remat_mode):
        def body(xc, lp):
            xc = cx(xc)
            with jax.named_scope("norm"):
                h = rms_norm(xc, lp["norm1"]["scale"], cfg.rms_eps)
            with jax.named_scope("mixer"):
                y = ssm_lib.ssm_block(lp["ssm"], h, cfg, dtype, plan=plan)
            y = checkpoint_name(y, "block_out")
            return xc + y, None
        x, _ = jax.lax.scan(_remat(body, remat_mode), x, stacked)
        return x

    cq, ckv = _seq_constrainers(plan, mesh, batch_axes)
    cx = _residual_constrainer(mesh, batch_axes)

    def _shared_attn_fwd(sp, x, positions):
        with jax.named_scope("norm"):
            h = rms_norm(x, sp["norm1"]["scale"], cfg.rms_eps)
        with jax.named_scope("attn"):
            q, k, v = qkv_proj(sp["attn"], h, cfg, dtype)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
            q, k, v = cq(q), ckv(k), ckv(v)
            a = cq(attention(q, k, v, causal=True, window=cfg.sliding_window,
                             impl=plan.attn_impl))
            x = x + (a.reshape(x.shape[0], x.shape[1], -1)
                     @ sp["attn"]["wo"].astype(dtype))
        with jax.named_scope("norm"):
            h = rms_norm(x, sp["norm2"]["scale"], cfg.rms_eps)
        with jax.named_scope("mlp"):
            return x + mlp_block(sp["mlp"], h, dtype)

    def forward(params, batch):
        tokens = batch["tokens"]
        x = _embed(params, tokens, cfg, dtype)
        positions = jnp.arange(tokens.shape[1])
        head, tail = _split_groups(params["layers"])
        sp = params["shared_attn"]

        def group(xc, gp):
            xc = _ssm_layers(xc, gp, plan.remat)
            xc = _shared_attn_fwd(sp, xc, positions)
            return xc, None

        x, _ = jax.lax.scan(group, x, head)
        if rest:
            x = _ssm_layers(x, tail, plan.remat)
        return _head(params, x, cfg, dtype, jnp.float32(0.0))

    def init_cache(batch: int, max_seq: int):
        one = ssm_lib.init_ssm_cache(cfg, batch, dtype)
        ssm_cache = jax.tree.map(
            lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "ssm": ssm_cache,
            "attn_k": jnp.zeros((n_apps, batch, max_seq, hkv, hd), dtype),
            "attn_v": jnp.zeros((n_apps, batch, max_seq, hkv, hd), dtype),
        }

    def decode_step(params, cache, tokens, pos):
        from repro.serve.attention import decode_attention  # noqa: PLC0415
        x = _embed(params, tokens, cfg, dtype)               # (B, d)
        positions = jnp.asarray(pos)[None]
        sp = params["shared_attn"]
        head, tail = _split_groups(params["layers"])
        c_head, c_tail = _split_groups(cache["ssm"])

        def ssm_body(x, xs):
            lp, c = xs
            h = rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
            y, c = ssm_lib.ssm_step(lp["ssm"], h, c, cfg, dtype)
            return x + y, c

        def shared_step(x, kc, vc):
            xs = x[:, None, :]
            h = rms_norm(xs, sp["norm1"]["scale"], cfg.rms_eps)
            q, k, v = qkv_proj(sp["attn"], h, cfg, dtype)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
            a, kc, vc = decode_attention(q, kc, vc, k, v, pos,
                                         window=cfg.sliding_window,
                                         mesh=mesh, batch_axes=batch_axes)
            xs = xs + a.reshape(a.shape[0], 1, -1) @ sp["attn"]["wo"].astype(dtype)
            h = rms_norm(xs, sp["norm2"]["scale"], cfg.rms_eps)
            xs = xs + mlp_block(sp["mlp"], h, dtype)
            return xs[:, 0, :], kc, vc

        def group(x, xs):
            gp, gc, kc, vc = xs
            x, gc = jax.lax.scan(ssm_body, x, (gp, gc))
            x, kc, vc = shared_step(x, kc, vc)
            return x, (gc, kc, vc)

        x, (new_head, ks, vs) = jax.lax.scan(
            group, x, (head, c_head, cache["attn_k"], cache["attn_v"]))
        if rest:
            x, new_tail = jax.lax.scan(ssm_body, x, (tail, c_tail))
        else:
            new_tail = c_tail
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        new_ssm = jax.tree.map(
            lambda h, t: jnp.concatenate(
                [h.reshape((covered,) + h.shape[2:]), t], axis=0),
            new_head, new_tail)
        logits = _logits(params, x, cfg, dtype)
        return logits, {"ssm": new_ssm, "attn_k": ks, "attn_v": vs}

    return Model(cfg, init, forward, init_cache, decode_step)


# ---------------------------------------------------------------------------
# hybrid, the published Zamba2 block (transformers 4.57,
# models/zamba2/modeling_zamba2.py: Zamba2HybridLayer,
# Zamba2AttentionDecoderLayer, Zamba2Attention, Zamba2MLP)

def _tree_slice(tree, lo: int, hi: int):
    return jax.tree.map(lambda a: a[lo:hi], tree)


def _tree_pick(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


def _init_shared_block(cfg: ModelConfig):
    d = cfg.d_model
    d_in = 2 * d                  # the block reads [h, embedding]
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def one(rng):
        r = split_tree(rng, 5)
        return {
            "norm1": {"scale": jnp.zeros((d_in,), jnp.float32)},
            "norm2": {"scale": jnp.zeros((d,), jnp.float32)},
            "attn": {"wq": dense_init(r[0], (d_in, hq)),
                     "wk": dense_init(r[1], (d_in, hkv)),
                     "wv": dense_init(r[2], (d_in, hkv)),
                     "wo": dense_init(r[3], (hq, d))},
            "mlp": init_mlp(r[4], d, cfg.d_ff),
        }
    return one


def _init_application(cfg: ModelConfig):
    """One application's own weights: the MLP adapter and the output linear
    (Zamba2-7B has no attention adapter)."""
    d, rank = cfg.d_model, cfg.adapter_rank

    def one(rng):
        r = split_tree(rng, 4)
        return {"linear": {"w": dense_init(r[0], (d, d))},
                "mlp_adapter": {"a": dense_init(r[1], (d, rank)),
                                "gate": dense_init(r[2], (rank, cfg.d_ff)),
                                "up": dense_init(r[3], (rank, cfg.d_ff))}}
    return one


def zamba2_attn_scale(cfg: ModelConfig) -> float:
    """The published block scales scores by (hd/2)^-1/2: its heads are twice
    d_model / n_heads wide because they read the concatenated input."""
    return (cfg.head_dim / 2) ** -0.5


def zamba2_attention(p, u, cfg: ModelConfig, dtype, positions, attend):
    """The shared block's attention on its normed input u (B, S, 2·d):
    ``a·W_o`` (B, S, d). ``attend(q, k, v)`` -> (B, S, Hq, hd)."""
    b, s = u.shape[:2]
    with jax.named_scope("attn"):
        q, k, v = qkv_proj(p, u, cfg, dtype)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        a = attend(q, k, v)
        t = a.reshape(b, s, -1) @ p["wo"].astype(dtype)
        return checkpoint_name(t, "attn_out")


def zamba2_mlp(p, ad, m, dtype):
    """The shared block's gated GELU (erf) MLP on its normed input m
    (B, S, d), the application's adapter ``ad`` added to the gate and up
    projections."""
    with jax.named_scope("mlp"):
        g = m @ p["gate"].astype(dtype)
        u = m @ p["up"].astype(dtype)
        with jax.named_scope("adapter"):
            low = m @ ad["a"].astype(dtype)
            g = g + low @ ad["gate"].astype(dtype)
            u = u + low @ ad["up"].astype(dtype)
        return (jax.nn.gelu(g, approximate=False) * u) @ p["down"].astype(dtype)


def build_zamba2(cfg: ModelConfig, plan: Optional[ParallelPlan] = None,
                 mesh=None, batch_axes=("data",)) -> Model:
    """Mamba-2 layers with the published Zamba2 shared block.

    Application j, at layer i = ``hybrid_layer_ids[j]``, with block
    b = j mod ``num_mem_blocks`` and e the embedding output:
    ``u = RMSNorm([h, e])``; ``q, k, v = u·W_b``; rotary (rotate-half) on
    q and k; ``a = causal softmax(q·kᵀ·(hd/2)^-1/2)·v``; ``t = a·W_o``;
    ``m = RMSNorm(t)``; ``[g|p] = m·W_gu + (m·A_j)·B_j``;
    ``t = (gelu_erf(g)·p)·W_down·Lin_j``; then
    ``h ← h + Mamba_i(RMSNorm(h + t))``: the block's output enters the
    Mamba-2 layer's input, not the residual.
    The hybrid layer (application and Mamba-2 layer) is rematerialized as one
    unit, and the Mamba-2 layers between applications run as scans.
    """
    plan = plan or ParallelPlan()
    dtype = jnp.dtype(plan.compute_dtype)
    apps = cfg.shared_applications
    assert list(apps) == sorted(set(apps)), apps
    n_blocks = cfg.shared_blocks
    # (lo, hi, application) runs: Mamba-2 layers lo..hi-1, then the hybrid
    # layer at hi when an application index is given
    runs = [(0 if j == 0 else apps[j - 1] + 1, i, j)
            for j, i in enumerate(apps)]
    runs.append((apps[-1] + 1 if apps else 0, cfg.n_layers, None))
    scale = zamba2_attn_scale(cfg)
    cq, ckv = _seq_constrainers(plan, mesh, batch_axes)
    cx = _residual_constrainer(mesh, batch_axes)

    def init_layer(rng):
        return {
            "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "ssm": ssm_lib.init_ssm(rng, cfg),
        }

    def init(rng):
        r = split_tree(rng, 5)
        params = {
            "embed": {"tok": dense_init(r[0], (_padded_vocab(cfg, plan), cfg.d_model), in_axis=-1)},
            "layers": _stacked_init(r[1], cfg.n_layers, init_layer),
            "final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
        }
        if apps:
            params["shared"] = _stacked_init(r[2], n_blocks,
                                             _init_shared_block(cfg))
            params["apps"] = _stacked_init(r[3], len(apps),
                                           _init_application(cfg))
        if not cfg.tie_embeddings:
            params["lm_head"] = {"w": dense_init(r[4], (cfg.d_model, _padded_vocab(cfg, plan)))}
        return params

    def shared_block(bp, ap, x, emb, positions, attend):
        """One application on (B, S, d) inputs: the block's output after the
        application's linear. ``attend(q, k, v)`` runs the attention."""
        with jax.named_scope("shared_block"):
            with jax.named_scope("norm"):
                u = rms_norm(jnp.concatenate([x, emb], -1),
                             bp["norm1"]["scale"], cfg.rms_eps)
            t = zamba2_attention(bp["attn"], u, cfg, dtype, positions, attend)
            with jax.named_scope("norm"):
                m = rms_norm(t, bp["norm2"]["scale"], cfg.rms_eps)
            t = zamba2_mlp(bp["mlp"], ap["mlp_adapter"], m, dtype)
            with jax.named_scope("adapter"):
                return t @ ap["linear"]["w"].astype(dtype)

    def mamba(x, lp, t=None):
        """Mamba-2 layer ``x + Mamba(RMSNorm(x + t))``; t is an
        application's output, or None."""
        x = cx(x)
        with jax.named_scope("norm"):
            h = rms_norm(x if t is None else x + t, lp["norm1"]["scale"],
                         cfg.rms_eps)
        with jax.named_scope("mixer"):
            y = ssm_lib.ssm_block(lp["ssm"], h, cfg, dtype, plan=plan)
        return x + checkpoint_name(y, "block_out")

    def attend(q, k, v):
        return cq(attention(cq(q), ckv(k), ckv(v), causal=True, scale=scale,
                            impl=plan.attn_impl))

    def hybrid(x, emb, bp, ap, lp):
        positions = jnp.arange(x.shape[1])
        return mamba(x, lp, shared_block(bp, ap, x, emb, positions, attend))

    def forward(params, batch):
        x = _embed(params, batch["tokens"], cfg, dtype)
        emb = x
        body = _remat(lambda xc, lp: (mamba(xc, lp), None), plan.remat)
        hybrid_layer = _remat(hybrid, plan.remat)
        for lo, hi, j in runs:
            if hi > lo:
                x, _ = jax.lax.scan(body, x,
                                    _tree_slice(params["layers"], lo, hi))
            if j is not None:
                x = hybrid_layer(x, emb,
                                 _tree_pick(params["shared"], j % n_blocks),
                                 _tree_pick(params["apps"], j),
                                 _tree_pick(params["layers"], hi))
        return _head(params, x, cfg, dtype, jnp.float32(0.0))

    def init_cache(batch: int, max_seq: int):
        one = ssm_lib.init_ssm_cache(cfg, batch, dtype)
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "ssm": jax.tree.map(
                lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one),
            "attn_k": jnp.zeros((len(apps), batch, max_seq, hkv, hd), dtype),
            "attn_v": jnp.zeros((len(apps), batch, max_seq, hkv, hd), dtype),
        }

    def decode_step(params, cache, tokens, pos):
        from repro.serve.attention import decode_attention  # noqa: PLC0415
        x = _embed(params, tokens, cfg, dtype)               # (B, d)
        emb = x[:, None, :]
        positions = jnp.asarray(pos)[None]

        def ssm_body(x, xs, t=None):
            lp, c = xs
            h = rms_norm(x if t is None else x + t, lp["norm1"]["scale"],
                         cfg.rms_eps)
            y, c = ssm_lib.ssm_step(lp["ssm"], h, c, cfg, dtype)
            return x + y, c

        ssm_parts, ks, vs = [], [], []
        for lo, hi, j in runs:
            if hi > lo:
                x, c = jax.lax.scan(ssm_body, x, (
                    _tree_slice(params["layers"], lo, hi),
                    _tree_slice(cache["ssm"], lo, hi)))
                ssm_parts.append(c)
            if j is None:
                continue
            kv = {}

            def attend_cached(q, k, v, j=j, kv=kv):
                a, kv["k"], kv["v"] = decode_attention(
                    q, cache["attn_k"][j], cache["attn_v"][j], k, v, pos,
                    mesh=mesh, batch_axes=batch_axes, scale=scale)
                return a

            t = shared_block(_tree_pick(params["shared"], j % n_blocks),
                             _tree_pick(params["apps"], j), x[:, None, :],
                             emb, positions, attend_cached)
            x, c = ssm_body(x, (_tree_pick(params["layers"], hi),
                                _tree_pick(cache["ssm"], hi)), t[:, 0, :])
            ssm_parts.append(jax.tree.map(lambda a: a[None], c))
            ks.append(kv["k"])
            vs.append(kv["v"])
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        new_cache = {
            "ssm": jax.tree.map(lambda *a: jnp.concatenate(a, axis=0),
                                *ssm_parts),
            "attn_k": jnp.stack(ks) if ks else cache["attn_k"],
            "attn_v": jnp.stack(vs) if vs else cache["attn_v"],
        }
        return _logits(params, x, cfg, dtype), new_cache

    return Model(cfg, init, forward, init_cache, decode_step)


# ---------------------------------------------------------------------------
# encoder-decoder (whisper): frame-embedding frontend stub + cross attention

def build_enc_dec(cfg: ModelConfig, plan: Optional[ParallelPlan] = None,
                  mesh=None, batch_axes=("data",)) -> Model:
    plan = plan or ParallelPlan()
    dtype = jnp.dtype(plan.compute_dtype)
    impl = plan.attn_impl

    def init_enc_layer(rng):
        r = split_tree(rng, 2)
        return {
            "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "norm2": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "attn": init_attn(r[0], cfg),
            "mlp": init_mlp(r[1], cfg.d_model, cfg.d_ff),
        }

    def init_dec_layer(rng):
        r = split_tree(rng, 3)
        return {
            "norm1": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "norm2": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "norm3": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "attn": init_attn(r[0], cfg),
            "xattn": init_attn(r[1], cfg),
            "mlp": init_mlp(r[2], cfg.d_model, cfg.d_ff),
        }

    def init(rng):
        r = split_tree(rng, 4)
        return {
            "embed": {"tok": dense_init(r[0], (_padded_vocab(cfg, plan), cfg.d_model), in_axis=-1)},
            "encoder": {
                "layers": _stacked_init(r[1], cfg.enc_layers, init_enc_layer),
                "final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            },
            "layers": _stacked_init(r[2], cfg.n_layers, init_dec_layer),
            "final_norm": {"scale": jnp.zeros((cfg.d_model,), jnp.float32)},
            "lm_head": {"w": dense_init(r[3], (cfg.d_model, _padded_vocab(cfg, plan)))},
        }

    cq, ckv = _seq_constrainers(plan, mesh, batch_axes)
    cx = _residual_constrainer(mesh, batch_axes)

    def encode(params, frames):
        x = frames.astype(dtype)
        x = x + sinusoidal_pos_emb(jnp.arange(x.shape[1]), cfg.d_model).astype(dtype)

        def body(xc, lp):
            xc = cx(xc)
            h = rms_norm(xc, lp["norm1"]["scale"], cfg.rms_eps)
            q, k, v = qkv_proj(lp["attn"], h, cfg, dtype)
            a = attention(q, k, v, causal=False, impl=impl)
            a = checkpoint_name(
                a.reshape(xc.shape[0], xc.shape[1], -1) @ lp["attn"]["wo"].astype(dtype),
                "attn_out")
            xc = xc + a
            h = rms_norm(xc, lp["norm2"]["scale"], cfg.rms_eps)
            return xc + mlp_block(lp["mlp"], h, dtype), None

        x, _ = jax.lax.scan(_remat(body, plan.remat), x, params["encoder"]["layers"])
        return rms_norm(x, params["encoder"]["final_norm"]["scale"], cfg.rms_eps)

    def _xattn(lp, x, enc_kv):
        b, s = x.shape[:2]
        hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
        q = (x @ lp["xattn"]["wq"].astype(dtype)).reshape(b, s, hq, hd)
        k, v = enc_kv
        a = attention(q, k, v, causal=False, impl=impl)
        return a.reshape(b, s, -1) @ lp["xattn"]["wo"].astype(dtype)

    def _enc_kv(lp, enc_out):
        b, f = enc_out.shape[:2]
        hd, hkv = cfg.head_dim, cfg.n_kv_heads
        k = (enc_out @ lp["xattn"]["wk"].astype(dtype)).reshape(b, f, hkv, hd)
        v = (enc_out @ lp["xattn"]["wv"].astype(dtype)).reshape(b, f, hkv, hd)
        return k, v

    def forward(params, batch):
        enc_out = encode(params, batch["frames"])
        tokens = batch["tokens"]
        x = _embed(params, tokens, cfg, dtype)
        x = x + sinusoidal_pos_emb(jnp.arange(tokens.shape[1]),
                                   cfg.d_model).astype(dtype)

        def body(xc, lp):
            xc = cx(xc)
            h = rms_norm(xc, lp["norm1"]["scale"], cfg.rms_eps)
            q, k, v = qkv_proj(lp["attn"], h, cfg, dtype)
            q, k, v = cq(q), ckv(k), ckv(v)
            a = cq(attention(q, k, v, causal=True, impl=impl))
            a = checkpoint_name(
                a.reshape(xc.shape[0], xc.shape[1], -1) @ lp["attn"]["wo"].astype(dtype),
                "attn_out")
            xc = xc + a
            h = rms_norm(xc, lp["norm2"]["scale"], cfg.rms_eps)
            xc = xc + _xattn(lp, h, _enc_kv(lp, enc_out))
            h = rms_norm(xc, lp["norm3"]["scale"], cfg.rms_eps)
            return xc + mlp_block(lp["mlp"], h, dtype), None

        x, _ = jax.lax.scan(_remat(body, plan.remat), x, params["layers"])
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        return _logits(params, x, cfg, dtype), jnp.float32(0.0)

    def init_cache(batch: int, max_seq: int):
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "k": jnp.zeros((cfg.n_layers, batch, max_seq, hkv, hd), dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_seq, hkv, hd), dtype),
            "cross_k": jnp.zeros((cfg.n_layers, batch, cfg.enc_frames, hkv, hd), dtype),
            "cross_v": jnp.zeros((cfg.n_layers, batch, cfg.enc_frames, hkv, hd), dtype),
        }

    def decode_step(params, cache, tokens, pos):
        from repro.serve.attention import decode_attention  # noqa: PLC0415
        b = tokens.shape[0]
        x = _embed(params, tokens, cfg, dtype)[:, None, :]
        positions = jnp.asarray(pos)[None]
        x = x + sinusoidal_pos_emb(positions, cfg.d_model).astype(dtype)[None]

        def body(x, xs):
            lp, kc, vc, xk, xv = xs
            h = rms_norm(x, lp["norm1"]["scale"], cfg.rms_eps)
            q, k, v = qkv_proj(lp["attn"], h, cfg, dtype)
            a, kc, vc = decode_attention(q, kc, vc, k, v, pos,
                                         mesh=mesh, batch_axes=batch_axes)
            x = x + a.reshape(b, 1, -1) @ lp["attn"]["wo"].astype(dtype)
            h = rms_norm(x, lp["norm2"]["scale"], cfg.rms_eps)
            x = x + _xattn(lp, h, (xk, xv))
            h = rms_norm(x, lp["norm3"]["scale"], cfg.rms_eps)
            return x + mlp_block(lp["mlp"], h, dtype), (kc, vc)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["cross_k"], cache["cross_v"]))
        x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
        logits = _logits(params, x[:, 0, :], cfg, dtype)
        new_cache = dict(cache, k=ks, v=vs)
        return logits, new_cache

    def fill_cross(params, cache, frames):
        """Run the encoder and populate the cross-attention K/V cache."""
        enc_out = encode(params, frames)

        def per_layer(_, lp):
            return None, _enc_kv(lp, enc_out)

        _, (xk, xv) = jax.lax.scan(per_layer, None, params["layers"])
        return dict(cache, cross_k=xk, cross_v=xv)

    return Model(cfg, init, forward, init_cache, decode_step,
                 extras={"encode": encode, "fill_cross": fill_cross})


# ---------------------------------------------------------------------------

def gspmd_kernel_plan(plan: ParallelPlan, mesh) -> ParallelPlan:
    """The plan the GSPMD model path runs its kernels under.

    XLA's SPMD partitioner cannot split a Mosaic (Pallas TPU) kernel, so on a
    mesh of more than one device an ``"auto"`` kernel choice resolves to
    XLA here. An explicit ``"pallas"`` cannot be honoured on a TPU backend
    and raises; off-TPU the kernel runs interpreted, as plain HLO that
    partitions. The shard_map paths (the executor's overlap TP, cp and ep,
    and the pipeline) build their layers from the plan as given and call the
    kernels per shard."""
    if mesh is None or mesh.size == 1:
        return plan
    explicit = [k for k in KERNEL_KNOBS if getattr(plan, k) == "pallas"]
    if explicit and jax.default_backend() == "tpu":
        raise ValueError(
            f"{', '.join(explicit)}='pallas' cannot run on the GSPMD path of "
            f"a {mesh.size}-device mesh: GSPMD cannot partition a Mosaic "
            "kernel; use 'auto' or a shard_map path (tp_impl='overlap')")
    return dataclasses.replace(plan, **{k: "xla" for k in KERNEL_KNOBS
                                        if getattr(plan, k) == "auto"})


def build_model(cfg: ModelConfig, plan: Optional[ParallelPlan] = None,
                mesh=None, batch_axes=("data",)) -> Model:
    if plan is not None:
        plan.validate(cfg)
        plan = gspmd_kernel_plan(plan, mesh)
    if cfg.family == Family.SSM:
        return build_ssm(cfg, plan, mesh, batch_axes)
    if cfg.family == Family.HYBRID:
        return build_hybrid(cfg, plan, mesh, batch_axes)
    if cfg.is_enc_dec:
        return build_enc_dec(cfg, plan, mesh, batch_axes)
    return build_decoder_only(cfg, plan, mesh, batch_axes)
