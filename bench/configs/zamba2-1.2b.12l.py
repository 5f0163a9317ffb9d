"""Plain reference of zamba2-1.2b as the trainer runs it: token embedding,
groups of ``shared_attention_every`` residual Mamba-2 blocks each followed by
one application of the weight-shared attention block, a final RMSNorm and an
untied output head. Parameters are laid out as the trainer's tree."""

import jax

from bench import reference as R
from bench.flops import ssm_dims


def param_spec(c):
    d, di, nh, g, n = ssm_dims(c)
    L, k = c["num_hidden_layers"], c["conv_kernel"]
    hq = c["num_attention_heads"] * c["attention_head_dim"]
    hkv = c["num_key_value_heads"] * c["attention_head_dim"]
    ff = c["intermediate_size"]
    ssm = {"wz": (d, di), "wx": (d, di), "wB": (d, g * n), "wC": (d, g * n),
           "wdt": (d, nh), "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
           "conv_x": (di, k), "conv_B": (g * n, k), "conv_C": (g * n, k),
           "scale": (di,), "out_proj": (di, d)}
    spec = [(("embed", "tok"), (c["vocab_size"], d)),
            (("final_norm", "scale"), (d,)),
            (("lm_head", "w"), (d, c["vocab_size"])),
            (("layers", "norm1", "scale"), (L, d)),
            (("shared_attn", "norm1", "scale"), (d,)),
            (("shared_attn", "norm2", "scale"), (d,)),
            (("shared_attn", "attn", "wq"), (d, hq)),
            (("shared_attn", "attn", "wk"), (d, hkv)),
            (("shared_attn", "attn", "wv"), (d, hkv)),
            (("shared_attn", "attn", "wo"), (hq, d)),
            (("shared_attn", "mlp", "gate"), (d, ff)),
            (("shared_attn", "mlp", "up"), (d, ff)),
            (("shared_attn", "mlp", "down"), (ff, d))]
    spec += [(("layers", "ssm", k_), (L,) + s) for k_, s in ssm.items()]
    return sorted(spec)


def forward(params, tokens, c, cast):
    every, L = c["shared_attention_every"], c["num_hidden_layers"]
    x = params["embed"]["tok"][tokens]
    sp = params["shared_attn"]
    for start in range(0, L, every):
        group = jax.tree.map(lambda a: a[start:start + every], params["layers"])
        x = R.mamba2_layers(group, x, c, cast)
        if start + every <= L:
            x = jax.checkpoint(lambda s, h: R.shared_attention_block(s, h, c, cast))(sp, x)
    x = R.rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return R.mm(x, params["lm_head"]["w"], cast)
