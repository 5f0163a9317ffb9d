"""Plain reference of mamba2-370m as the trainer runs it: token embedding,
48 residual Mamba-2 blocks (pre-norm), a final RMSNorm and the output head
tied to the embedding. Parameters are laid out as the trainer's tree."""

from bench import reference as R
from bench.flops import ssm_dims


def param_spec(c):
    d, di, nh, g, n = ssm_dims(c)
    L, k = c["num_hidden_layers"], c["conv_kernel"]
    ssm = {"wz": (d, di), "wx": (d, di), "wB": (d, g * n), "wC": (d, g * n),
           "wdt": (d, nh), "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
           "conv_x": (di, k), "conv_B": (g * n, k), "conv_C": (g * n, k),
           "scale": (di,), "out_proj": (di, d)}
    spec = [(("embed", "tok"), (c["vocab_size"], d)),
            (("final_norm", "scale"), (d,)),
            (("layers", "norm1", "scale"), (L, d))]
    spec += [(("layers", "ssm", k_), (L,) + s) for k_, s in ssm.items()]
    return sorted(spec)


def forward(params, tokens, c, cast):
    x = params["embed"]["tok"][tokens]
    x = R.mamba2_layers(params["layers"], x, c, cast)
    x = R.rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    return R.mm(x, params["embed"]["tok"].T, cast)
