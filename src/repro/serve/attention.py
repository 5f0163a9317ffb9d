"""Distributed decode attention (survey §4.1.4, TPU adaptation — DESIGN.md §2).

KV cache layout: ``(batch@data, seq@model, kv_heads, head_dim)``. Sequence
sharding is the only dimension that scales for every assigned arch (GQA kv_heads
of 8–32 < model axis 16) and every context length (long_500k: 512k × model16 =
32k rows/device).

The GPU-survey approach is ring attention (P2P chunk rotation). On a TPU torus
XLA strongly prefers whole-axis collectives, so we adapt: each ``model`` rank
computes exact attention over its local KV chunk, then one logsumexp-combine
``psum`` merges (max, denominator, weighted output). Exact result, O(S/N)
memory, one small all-reduce of (B, H, hd)-sized tensors per layer instead of N
ring steps.

The cache *write* needs no communication: the rank owning position ``pos``
applies a masked dynamic_update_slice; everyone else no-ops.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.compat import shard_map

from repro.models.layers import NEG_INF, _softcap


def _local_decode_attn(q, k, v, *, valid_mask, softcap, scale):
    """q: (B, Hkv, G, hd); k/v: (B, T_loc, Hkv, hd); valid_mask: (B?, T_loc) bool.

    Returns un-normalized (o (B,Hkv,G,hd) fp32, m (B,Hkv,G), l (B,Hkv,G)).
    """
    s = jnp.einsum("bkgd,btkd->bkgt", q, k, preferred_element_type=jnp.float32)
    s = _softcap(s * scale, softcap)
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None]) * valid_mask[:, None, None, :]
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def combine_lse(parts):
    """Merge [(o, m, l), ...] partial attention results exactly."""
    ms = jnp.stack([m for _, m, _ in parts])
    m = ms.max(axis=0)
    o = sum(op * jnp.exp(mp - m)[..., None] for op, mp, _ in parts)
    l = sum(lp * jnp.exp(mp - m) for _, mp, lp in parts)
    return o, m, l


def decode_attention(
    q: jax.Array,            # (B, 1, Hq, hd) — replicated over model axis
    k_cache: jax.Array,      # (B, T, Hkv, hd) — seq sharded over model axis
    v_cache: jax.Array,
    k_new: jax.Array,        # (B, 1, Hkv, hd) current token's K/V
    v_new: jax.Array,
    pos,                     # scalar int: index of the current token
    *,
    window: int = 0,
    softcap: float = 0.0,
    mesh: Optional[Mesh] = None,
    batch_axes: Tuple[str, ...] = ("data",),
    scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out (B, 1, Hq, hd), new_k_cache, new_v_cache).

    Positions 0..pos-1 of the cache are valid history; the current token's K/V
    are written at ``pos`` and attended to (self-attention includes self).
    With ``window > 0`` only keys with pos - j < window participate.
    """
    b, _, hq, hd = q.shape
    hkv = k_cache.shape[2]
    g = hq // hkv
    scale = hd ** -0.5 if scale is None else scale
    qg = q.reshape(b, hkv, g, hd)

    def _window_mask(valid, jpos):
        """Apply sliding-window constraint; ``window`` may be a traced scalar
        (per-layer metadata scanned through the decode loop)."""
        if isinstance(window, int) and window == 0:
            return valid
        w = jnp.asarray(window)
        return valid & jnp.where(w > 0, (pos - jpos) < w, True)

    if mesh is None or "model" not in mesh.shape:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, pos, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, pos, axis=1)
        t = k_cache.shape[1]
        jpos = jnp.arange(t)
        valid = _window_mask(jpos <= pos, jpos)
        valid = jnp.broadcast_to(valid, (b, t))
        o, m, l = _local_decode_attn(qg, k_cache, v_cache, valid_mask=valid,
                                     softcap=softcap, scale=scale)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return out.reshape(b, 1, hq, hd), k_cache, v_cache

    tp = mesh.shape["model"]
    t_loc = k_cache.shape[1] // tp

    def local(qg_, kc, vc, kn, vn, pos_):
        rank = jax.lax.axis_index("model")
        start = rank * t_loc
        # masked cache write: only the owner rank applies the DUS
        local_idx = jnp.clip(pos_ - start, 0, t_loc - 1)
        own = (pos_ >= start) & (pos_ < start + t_loc)
        kc2 = jax.lax.dynamic_update_slice_in_dim(kc, kn, local_idx, axis=1)
        vc2 = jax.lax.dynamic_update_slice_in_dim(vc, vn, local_idx, axis=1)
        kc = jnp.where(own, kc2, kc)
        vc = jnp.where(own, vc2, vc)

        jpos = start + jnp.arange(t_loc)
        valid = jpos <= pos_
        if not (isinstance(window, int) and window == 0):
            w = jnp.asarray(window)
            valid &= jnp.where(w > 0, (pos_ - jpos) < w, True)
        valid = jnp.broadcast_to(valid, (kc.shape[0], t_loc))
        o, m, l = _local_decode_attn(qg_, kc, vc, valid_mask=valid,
                                     softcap=softcap, scale=scale)
        # exact logsumexp combine across the model axis
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        o = jax.lax.psum(o * corr[..., None], "model")
        l = jax.lax.psum(l * corr, "model")
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(qg_.dtype)
        return out, kc, vc

    baxes = batch_axes if batch_axes else None   # () -> replicated batch
    cache_spec = P(baxes, "model", None, None)
    rep_spec = P(baxes, None, None, None)
    out, k_cache, v_cache = shard_map(
        local, mesh=mesh,
        in_specs=(P(baxes, None, None, None), cache_spec, cache_spec,
                  rep_spec, rep_spec, P()),
        out_specs=(P(baxes, None, None, None), cache_spec, cache_spec),
    )(qg, k_cache, v_cache, k_new, v_new, pos)
    return out.reshape(b, 1, hq, hd), k_cache, v_cache
