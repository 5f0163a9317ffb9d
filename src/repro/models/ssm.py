"""Mamba2 — State Space Duality (SSD) layer [arXiv:2405.21060].

Training/prefill uses the chunked SSD algorithm: quadratic attention-like compute
inside fixed-size chunks, linear state recurrence across chunks (a ``lax.scan``).
Decode uses the O(1) recurrent step form with a conv rolling buffer.

TP note (DESIGN.md §2): the fused in_proj of the reference CUDA implementation is
split into separate per-stream projections (``wz/wx/wB/wC/wdt``) so each output
dim shards cleanly on the ``model`` axis without cutting across stream
boundaries — the TPU/GSPMD-native layout.

The gated RMSNorm normalizes each group's ``d_inner / n_groups`` channels on
their own, as the published block does (``RMSNormGated(group_size=d_inner //
ngroups)``); one group is the whole width. With ``SSMConfig.conv_bias`` the
depthwise conv carries a bias per channel (``conv_bias_x/B/C``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import ModelConfig
from .layers import dense_init, rms_norm, split_tree


def ssm_dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.n_groups, s.d_state


def init_ssm(rng, cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    di, nh, g, n = ssm_dims(cfg)
    r = split_tree(rng, 8)
    # A init in [1, 16) as in the reference implementation
    a = jax.random.uniform(r[5], (nh,), jnp.float32, 1.0, 16.0)
    return {
        "wz": dense_init(r[0], (d, di)),
        "wx": dense_init(r[1], (d, di)),
        "wB": dense_init(r[2], (d, g * n)),
        "wC": dense_init(r[3], (d, g * n)),
        "wdt": dense_init(r[4], (d, nh)),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            r[6], (nh,), jnp.float32, np.log(1e-3), np.log(1e-1))))),
        "A_log": jnp.log(a),
        "D": jnp.ones((nh,), jnp.float32),
        "conv_x": jnp.zeros((di, s.d_conv), jnp.float32),
        "conv_B": jnp.zeros((g * n, s.d_conv), jnp.float32),
        "conv_C": jnp.zeros((g * n, s.d_conv), jnp.float32),
        "scale": jnp.zeros((di,), jnp.float32),     # gated RMSNorm weight
        "out_proj": dense_init(r[7], (di, d)),
    } | ({"conv_bias_x": jnp.zeros((di,), jnp.float32),
          "conv_bias_B": jnp.zeros((g * n,), jnp.float32),
          "conv_bias_C": jnp.zeros((g * n,), jnp.float32)}
         if s.conv_bias else {})


def gated_rms_norm(y, z, scale, groups: int, eps: float):
    """RMSNorm of ``y * silu(z)`` over each of ``groups`` equal channel
    groups. y, z: (..., C); scale: (C,) as a (1 + scale) weight."""
    yz = y * jax.nn.silu(z)
    if groups == 1:     # the whole width, with no size-1 group axis
        return rms_norm(yz, scale, eps)
    grouped = yz.reshape(yz.shape[:-1] + (groups, yz.shape[-1] // groups))
    return rms_norm(grouped, scale.reshape(groups, -1), eps).reshape(yz.shape)


def _causal_conv(x, w, dtype, left=None, bias=None):
    """Depthwise causal conv1d. x: (B, L, C), w: (C, K), bias: (C,) or None.

    ``left`` (B, K-1, C) replaces the zero left-padding with real context —
    the context-parallel executor passes the previous cp rank's halo so the
    conv is seamless across sequence shards.
    """
    k = w.shape[-1]
    if left is None:
        xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([left.astype(x.dtype), x], axis=1)
    # windowed sum: out[:, t, c] = sum_j x[:, t+j, c] * w[c, j]
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + xp[:, j:j + x.shape[1], :] * w[None, None, :, j].astype(dtype)
    if bias is not None:
        out = out + bias.astype(dtype)
    return out


def _segsum(x):
    """x: (..., q) -> (..., q, q) with out[i, j] = sum_{k=j+1..i} x[k]; -inf above diag."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_scan(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD. Shapes:
      x: (b, l, h, p)   dt: (b, l, h)   A: (h,) negative   B, C: (b, l, g, n)
    Returns (y: (b, l, h, p), final_state: (b, h, p, n)).
    """
    b, l, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert l % chunk == 0, (l, chunk)
    c, q = l // chunk, chunk
    hpg = h // g

    xd = (x * dt[..., None]).astype(jnp.float32)             # discretized input
    dA = (dt * A).astype(jnp.float32)                        # (b, l, h) log-decays

    # chunked views
    xc = xd.reshape(b, c, q, g, hpg, p)
    Bc = B.reshape(b, c, q, g, n).astype(jnp.float32)
    Cc = C.reshape(b, c, q, g, n).astype(jnp.float32)
    dAc = dA.reshape(b, c, q, h).transpose(0, 1, 3, 2)       # (b, c, h, q)
    dA_cs = jnp.cumsum(dAc, axis=-1)                          # (b, c, h, q)

    # 1) intra-chunk (diagonal blocks): attention-like with decay kernel
    Ldec = jnp.exp(_segsum(dAc))                              # (b, c, h, q, q)
    Ldec = Ldec.reshape(b, c, g, hpg, q, q)
    y_diag = jnp.einsum("bcqgn,bckgn,bcghqk,bckghp->bcqghp", Cc, Bc, Ldec, xc)

    # 2) chunk-final states
    decay_states = jnp.exp(dA_cs[..., -1:] - dA_cs)           # (b, c, h, q)
    ds = decay_states.reshape(b, c, g, hpg, q)
    states = jnp.einsum("bckgn,bcghk,bckghp->bcghpn", Bc, ds, xc)  # (b,c,g,hpg,p,n)
    states = states.reshape(b, c, h, p, n)

    # 3) inter-chunk recurrence (lax.scan over chunks)
    chunk_decay = jnp.exp(dA_cs[..., -1])                     # (b, c, h)
    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(carry, inp):
        st, dec = inp                                         # (b,h,p,n), (b,h)
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev                                      # emit state *entering* chunk

    states_t = states.transpose(1, 0, 2, 3, 4)                # (c, b, h, p, n)
    decay_t = chunk_decay.transpose(1, 0, 2)                  # (c, b, h)
    final, prev_states = jax.lax.scan(step, s0, (states_t, decay_t))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)        # (b, c, h, p, n)

    # 4) contribution of carried-in state to each position
    state_decay = jnp.exp(dA_cs)                              # (b, c, h, q)
    sd = state_decay.reshape(b, c, g, hpg, q)
    pv = prev_states.reshape(b, c, g, hpg, p, n)
    y_off = jnp.einsum("bcqgn,bcghpn,bcghq->bcqghp", Cc, pv, sd)

    y = (y_diag + y_off).reshape(b, l, h, p)
    return y, final


def ssm_block(p, x, cfg: ModelConfig, dtype, initial_state=None, plan=None):
    """Full Mamba2 block forward. x: (B, L, d) -> (B, L, d).

    The SSD scan runs through :func:`repro.kernels.dispatch.dispatch_ssd_scan`
    (``impl = plan.ssm_impl``): the fused Pallas kernel keeps decay matrices
    in VMEM in both passes; the XLA twin is this module's :func:`ssd_scan`.
    Unaligned lengths are padded to the chunk boundary by the dispatcher —
    never collapsed into one whole-sequence chunk with an O(L²) decay matrix.
    """
    from repro.kernels.dispatch import dispatch_ssd_scan  # noqa: PLC0415

    s = cfg.ssm
    di, nh, g, n = ssm_dims(cfg)
    b, l, d = x.shape

    z = x @ p["wz"].astype(dtype)
    xin = x @ p["wx"].astype(dtype)
    Bv = x @ p["wB"].astype(dtype)
    Cv = x @ p["wC"].astype(dtype)
    dt = jax.nn.softplus((x @ p["wdt"].astype(dtype)).astype(jnp.float32)
                         + p["dt_bias"])                      # (b, l, nh)

    xin = jax.nn.silu(_causal_conv(xin, p["conv_x"], dtype,
                                   bias=p.get("conv_bias_x")))
    Bv = jax.nn.silu(_causal_conv(Bv, p["conv_B"], dtype,
                                  bias=p.get("conv_bias_B")))
    Cv = jax.nn.silu(_causal_conv(Cv, p["conv_C"], dtype,
                                  bias=p.get("conv_bias_C")))

    A = -jnp.exp(p["A_log"])                                  # (nh,)
    xh = xin.reshape(b, l, nh, s.head_dim)
    y, _ = dispatch_ssd_scan(
        xh, dt, A, Bv.reshape(b, l, g, n), Cv.reshape(b, l, g, n),
        chunk=s.chunk, impl=plan.ssm_impl if plan is not None else "auto",
        initial_state=initial_state)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(b, l, di).astype(dtype)
    y = gated_rms_norm(y, z, p["scale"], g, cfg.rms_eps)
    return y @ p["out_proj"].astype(dtype)


# ---------------------------------------------------------------------------
# decode (recurrent step form)

def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> Dict:
    s = cfg.ssm
    di, nh, g, n = ssm_dims(cfg)
    k = s.d_conv - 1
    return {
        "conv_x": jnp.zeros((batch, k, di), dtype),
        "conv_B": jnp.zeros((batch, k, g * n), dtype),
        "conv_C": jnp.zeros((batch, k, g * n), dtype),
        "state": jnp.zeros((batch, nh, s.head_dim, n), jnp.float32),
    }


def _conv_step(cache_row, x_t, w, dtype, bias=None):
    """cache_row: (B, K-1, C); x_t: (B, C) -> (out (B, C), new cache)."""
    window = jnp.concatenate([cache_row, x_t[:, None, :]], axis=1)   # (B, K, C)
    out = jnp.einsum("bkc,ck->bc", window.astype(dtype), w.astype(dtype))
    if bias is not None:
        out = out + bias.astype(dtype)
    return out, window[:, 1:, :]


def ssm_step(p, x_t, cache, cfg: ModelConfig, dtype) -> Tuple[jax.Array, Dict]:
    """One decode step. x_t: (B, d) -> (y (B, d), cache)."""
    s = cfg.ssm
    di, nh, g, n = ssm_dims(cfg)
    bsz = x_t.shape[0]

    z = x_t @ p["wz"].astype(dtype)
    xin = x_t @ p["wx"].astype(dtype)
    Bv = x_t @ p["wB"].astype(dtype)
    Cv = x_t @ p["wC"].astype(dtype)
    dt = jax.nn.softplus((x_t @ p["wdt"].astype(dtype)).astype(jnp.float32)
                         + p["dt_bias"])                      # (B, nh)

    xin, cx = _conv_step(cache["conv_x"], xin, p["conv_x"], dtype,
                         p.get("conv_bias_x"))
    Bv, cb = _conv_step(cache["conv_B"], Bv, p["conv_B"], dtype,
                        p.get("conv_bias_B"))
    Cv, cc = _conv_step(cache["conv_C"], Cv, p["conv_C"], dtype,
                        p.get("conv_bias_C"))
    xin, Bv, Cv = jax.nn.silu(xin), jax.nn.silu(Bv), jax.nn.silu(Cv)

    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                      # (B, nh)
    xh = xin.reshape(bsz, nh, s.head_dim).astype(jnp.float32)
    Bg = Bv.reshape(bsz, g, n).astype(jnp.float32)
    Cg = Cv.reshape(bsz, g, n).astype(jnp.float32)
    hpg = nh // g

    # state: (B, nh, p, n)
    Bh = jnp.repeat(Bg, hpg, axis=1)                          # (B, nh, n)
    Ch = jnp.repeat(Cg, hpg, axis=1)
    new_state = (cache["state"] * dA[..., None, None]
                 + jnp.einsum("bhp,bhn->bhpn", xh * dt[..., None], Bh))
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(bsz, di).astype(dtype)
    y = gated_rms_norm(y, z, p["scale"], g, cfg.rms_eps)
    y = y @ p["out_proj"].astype(dtype)
    new_cache = {"conv_x": cx, "conv_B": cb, "conv_C": cc, "state": new_state}
    return y, new_cache
