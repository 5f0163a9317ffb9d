"""Plain reference of Zamba2-7B as published (transformers 4.57,
models/zamba2/modeling_zamba2.py), at the shapes of the configuration file.

Token embedding e; Mamba-2 layers ``h + Mamba(RMSNorm(h))``; before each
layer of ``hybrid_layer_ids`` (those within the depth) one application j of
shared block j mod ``num_mem_blocks``:

    u = RMSNorm([h, e])                        2·d wide
    q, k, v = u·W_q, u·W_k, u·W_v              no attention adapter
    q, k = rotary(q), rotary(k)                rotate-half, all head dims
    a = causal softmax(q·kᵀ·(hd/2)^-1/2)·v
    t = RMSNorm(a·W_o)
    [g | p] = t·W_gu + (t·A_j)·B_j             the MLP adapter
    t = (gelu_erf(g)·p)·W_down·Lin_j           the application's own linear
    h = h + Mamba(RMSNorm(h + t))              the residual is h, not h + t

then a final RMSNorm and the head tied to the embedding. The Mamba-2 layer's
conv carries a bias and its gated RMSNorm normalizes each group on its own.
Norm weights are (1 + scale), as the trainer's tree holds them. dt is not
clamped: the published CUDA path passes no ``time_step_limit``
(transformers' torch fallback clamps dt at ``time_step_min``). Parameters
are laid out as the trainer's tree; nothing of the program is imported.
"""

import jax
import jax.numpy as jnp

from bench import reference as R

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(c):
    d = c["hidden_size"]
    di = c["expand"] * d
    return d, di, di // c["head_dim"], c["n_groups"], c["state_size"]


def _applications(c):
    return [i for i in c["hybrid_layer_ids"] if i < c["num_hidden_layers"]]


def param_spec(c):
    d, di, nh, g, n = _dims(c)
    L, k = c["num_hidden_layers"], c["conv_kernel"]
    ssm = {"wz": (d, di), "wx": (d, di), "wB": (d, g * n), "wC": (d, g * n),
           "wdt": (d, nh), "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
           "conv_x": (di, k), "conv_B": (g * n, k), "conv_C": (g * n, k),
           "scale": (di,), "out_proj": (di, d)}
    if c["use_conv_bias"]:
        ssm.update(conv_bias_x=(di,), conv_bias_B=(g * n,),
                   conv_bias_C=(g * n,))
    spec = [(("embed", "tok"), (c["vocab_size"], d)),
            (("final_norm", "scale"), (d,)),
            (("layers", "norm1", "scale"), (L, d))]
    spec += [(("layers", "ssm", k_), (L,) + s) for k_, s in ssm.items()]
    if not c["tie_word_embeddings"]:
        spec.append((("lm_head", "w"), (d, c["vocab_size"])))
    # Zamba2-7B's block: the MLP adapter and no attention adapter
    assert c["use_shared_mlp_adapter"] and not c["use_shared_attention_adapter"]
    apps = len(_applications(c))
    if apps:
        nb = min(c["num_mem_blocks"], apps)
        hq = c["num_attention_heads"] * c["attention_head_dim"]
        hkv = c["num_key_value_heads"] * c["attention_head_dim"]
        ff, r = c["intermediate_size"], c["adapter_rank"]
        block = {("norm1", "scale"): (2 * d,), ("norm2", "scale"): (d,),
                 ("attn", "wq"): (2 * d, hq), ("attn", "wk"): (2 * d, hkv),
                 ("attn", "wv"): (2 * d, hkv), ("attn", "wo"): (hq, d),
                 ("mlp", "gate"): (d, ff), ("mlp", "up"): (d, ff),
                 ("mlp", "down"): (ff, d)}
        app = {("linear", "w"): (d, d), ("mlp_adapter", "a"): (d, r),
               ("mlp_adapter", "gate"): (r, ff), ("mlp_adapter", "up"): (r, ff)}
        spec += [(("shared",) + p, (nb,) + s) for p, s in block.items()]
        spec += [(("apps",) + p, (apps,) + s) for p, s in app.items()]
    return sorted(spec)


def gated_norm(y, z, scale, groups, eps):
    """RMSNorm of y·silu(z) over each group's channels, times (1 + scale)."""
    yz = y * jax.nn.silu(z)
    yg = yz.reshape(yz.shape[:-1] + (groups, yz.shape[-1] // groups))
    yg = yg * jax.lax.rsqrt(jnp.mean(jnp.square(yg), -1, keepdims=True) + eps)
    return yg.reshape(yz.shape) * (1.0 + scale)


def mamba2_block(p, x, c, cast):
    """Mamba-2 mixer on its normed input x (B, L, d) -> (B, L, d)."""
    d, di, nh, g, n = _dims(c)
    bsz, l, _ = x.shape
    bias = (lambda s: p["conv_bias_" + s]) if c["use_conv_bias"] else (
        lambda s: 0.0)
    z = R.mm(x, p["wz"], cast)
    xin = jax.nn.silu(R.causal_conv(R.mm(x, p["wx"], cast), p["conv_x"])
                      + bias("x"))
    bv = jax.nn.silu(R.causal_conv(R.mm(x, p["wB"], cast), p["conv_B"])
                     + bias("B"))
    cv = jax.nn.silu(R.causal_conv(R.mm(x, p["wC"], cast), p["conv_C"])
                     + bias("C"))
    dt = jax.nn.softplus(R.mm(x, p["wdt"], cast) + p["dt_bias"])   # (B, L, H)
    a = -jnp.exp(p["A_log"])
    xh = cast(xin).reshape(bsz, l, nh, c["head_dim"])
    heads = lambda t: jnp.repeat(cast(t).reshape(bsz, l, g, n), nh // g, axis=2)
    y = R.ssd_minimal(xh * dt[..., None], dt * a, heads(bv), heads(cv),
                      c["ref_chunk"])
    y = (y + xh * p["D"][:, None]).reshape(bsz, l, di)
    y = gated_norm(y, z, p["scale"], g, c["rms_norm_eps"])
    return R.mm(y, p["out_proj"], cast)


def mamba2_layer(lp, h, c, cast, t=None):
    u = h if t is None else h + t
    return h + mamba2_block(lp["ssm"], R.rms_norm(u, lp["norm1"]["scale"],
                                                  c["rms_norm_eps"]), c, cast)


def causal_attention(q, k, v, scale, block):
    """Causal softmax attention, one block of queries at a time.
    q, k, v: (B, S, H, D)."""
    bsz, s, h, d = q.shape
    nb = s // block
    qb = q.reshape(bsz, nb, block, h, d).transpose(1, 0, 2, 3, 4)

    def one(args):
        i, qi = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST) * scale
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(one), (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(bsz, s, h, d)


def attention(p, u, c, cast):
    """The block's attention on its normed input u (B, S, 2d): a·W_o."""
    hd = c["attention_head_dim"]
    bsz, s, _ = u.shape
    proj = {w: R.mm(u, p["w" + w], cast).reshape(bsz, s, heads, hd)
            for w, heads in (("q", c["num_attention_heads"]),
                             ("k", c["num_key_value_heads"]),
                             ("v", c["num_key_value_heads"]))}
    q, k = R.rope(proj["q"], c["rope_theta"]), R.rope(proj["k"], c["rope_theta"])
    rep = c["num_attention_heads"] // c["num_key_value_heads"]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, proj["v"]))
    o = causal_attention(cast(q), cast(k), cast(v), (hd / 2) ** -0.5,
                         min(c["ref_attn_block"], s))
    return R.mm(o.reshape(bsz, s, -1), p["wo"], cast)


def mlp(p, ad, t, cast):
    """The block's gated GELU (erf) MLP on its normed input, the
    application's adapter ``ad`` on the gate and up projections."""
    g, u = R.mm(t, p["gate"], cast), R.mm(t, p["up"], cast)
    low = R.mm(t, ad["a"], cast)
    g, u = g + R.mm(low, ad["gate"], cast), u + R.mm(low, ad["up"], cast)
    return R.mm(jax.nn.gelu(g, approximate=False) * u, p["down"], cast)


def shared_block(bp, ap, h, e, c, cast):
    """One application's output t (B, S, d), after its own linear."""
    eps = c["rms_norm_eps"]
    u = R.rms_norm(jnp.concatenate([h, e], axis=-1), bp["norm1"]["scale"], eps)
    t = R.rms_norm(attention(bp["attn"], u, c, cast), bp["norm2"]["scale"],
                   eps)
    return R.mm(mlp(bp["mlp"], ap["mlp_adapter"], t, cast), ap["linear"]["w"],
                cast)


def forward(params, tokens, c, cast):
    L = c["num_hidden_layers"]
    e = params["embed"]["tok"][tokens]
    h = e
    mamba = jax.checkpoint(lambda lp, x: (mamba2_layer(lp, x, c, cast), None))
    hybrid = jax.checkpoint(lambda bp, ap, lp, x: mamba2_layer(
        lp, x, c, cast, shared_block(bp, ap, x, e, c, cast)))
    pick = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    lo = 0
    for j, i in enumerate(_applications(c) + [None]):
        hi = L if i is None else i
        if hi > lo:
            h, _ = jax.lax.scan(lambda x, lp: mamba(lp, x), h,
                                jax.tree.map(lambda a: a[lo:hi],
                                             params["layers"]))
        if i is not None:
            nb = params["shared"]["norm2"]["scale"].shape[0]
            h = hybrid(pick(params["shared"], j % nb), pick(params["apps"], j),
                       pick(params["layers"], i), h)
            lo = i + 1
    h = R.rms_norm(h, params["final_norm"]["scale"], c["rms_norm_eps"])
    head = (params["embed"]["tok"].T if c["tie_word_embeddings"]
            else params["lm_head"]["w"])
    return R.mm(h, head, cast)
