"""Plain float32 reference of the benchmark's training step.

Written from the published descriptions in straightforward ``jax.numpy``,
importing nothing of the program under test and taking nothing it made:
weights come from :func:`init_params` and the seed, batches from
``bench/data.py``. Every matrix product runs at ``HIGHEST`` precision.

- Mamba-2 blocks use the SSD "minimal discrete" listing of the Mamba-2 paper
  (arXiv:2405.21060, Listing 1) at a chunk of 64, not the program's 128.
- Attention is plain causal softmax attention, in blocks of queries.
- The step is the trainer's: cross entropy plus z-loss, mean over every
  position; global-norm clipping; AdamW (b1 0.9, b2 0.95, eps 1e-8,
  decoupled decay on every leaf of rank above 1, as the trainer's optimizer
  states it); linear warm-up then cosine decay to a tenth.

Rows are processed in blocks and each layer is recomputed in the backward
pass, so the reference fits one chip next to nothing else.

``cast`` selects the precision of every matrix-product operand: ``"f32"``
(the reference), ``"bf16"`` (a witness at the program's stated precision) and
``"fp8"`` (the control, one step below bf16: e4m3 operands and e5m2
gradients, each with a per-tensor scale).
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0          # largest finite float8_e4m3fn
FP8_E5M2_MAX = 57344.0   # largest finite float8_e5m2


# ---------------------------------------------------------------------------
# weights from the seed

def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, name: str, shape: Tuple[int, ...]) -> jax.Array:
    """One leaf, drawn by the kind its name gives it."""
    if name == "scale":                       # (1 + scale) norm weights
        return jnp.zeros(shape, jnp.float32)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":                       # A in [1, 16), Mamba-2's init
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":                     # softplus^-1 of dt in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.startswith("conv_"):              # depthwise conv, fan-in = width
        bound = 1.0 / math.sqrt(shape[-1])
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    fan_in = shape[-1] if name == "tok" else shape[-2]
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            / math.sqrt(fan_in))


def init_params(spec: Sequence[Tuple[Tuple[str, ...], Tuple[int, ...]]],
                key: jax.Array) -> Dict[str, Any]:
    """Nested dict of fp32 leaves for ``spec`` ((path, shape) pairs); each
    leaf's key folds the CRC32 of its path into ``key``. Jit it with
    ``spec`` static to make the whole tree on the device in one call."""
    out: Dict[str, Any] = {}
    for path, shape in spec:
        k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = _leaf(k, path[-1], tuple(shape))
    return out


def flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    """{"a/b/c": leaf} for a nested dict."""
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out


# ---------------------------------------------------------------------------
# precision of matrix-product operands

def _quantize(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """fp8 training's usual recipe: operands in e4m3, their gradients in
    e5m2, each tensor scaled by its own largest magnitude."""
    return _quantize(x, jnp.float8_e4m3fn, FP8_MAX)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_quantize(g, jnp.float8_e5m2, FP8_E5M2_MAX),))


CASTS: Dict[str, Callable] = {
    "f32": lambda x: x,
    "bf16": lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
    "fp8": _fp8,
}


def mm(a, b, cast):
    return jnp.matmul(cast(a), cast(b), precision=HIGHEST)


# ---------------------------------------------------------------------------
# layers

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def causal_conv(x, w):
    """Depthwise causal conv. x: (B, L, C), w: (C, K)."""
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + x.shape[1], :] * w[None, None, :, j]
               for j in range(k))


def segsum(x):
    """x: (..., T) -> (..., T, T), out[i, j] = x[j+1] + ... + x[i] for
    j <= i and -inf above the diagonal (the paper's stable form)."""
    t = x.shape[-1]
    xr = jnp.broadcast_to(x[..., None], x.shape + (t,))
    xr = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xr, 0.0)
    cs = jnp.cumsum(xr, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool), 0), cs, -jnp.inf)


def ssd_minimal(x, a, b, c, block: int):
    """Mamba-2 paper, Listing 1. x: (B, L, H, P) already times dt;
    a: (B, L, H) = dt * A; b, c: (B, L, H, N). Returns y (B, L, H, P)."""
    bs, l, h, p = x.shape
    nc = l // block
    x, a, b, c = (t.reshape((bs, nc, block) + t.shape[2:]) for t in (x, a, b, c))
    a = a.transpose(0, 3, 1, 2)                                # (B, H, C, L)
    a_cs = jnp.cumsum(a, axis=-1)
    ein = lambda s, *ops: jnp.einsum(s, *ops, precision=HIGHEST)
    y_diag = ein("bclhn,bcshn,bhcls,bcshp->bclhp", c, b,
                 jnp.exp(segsum(a)), x)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)
    states = ein("bclhn,bhcl,bclhp->bchpn", b, decay_states, x)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = ein("bclhn,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bs, l, h, p)


def mamba2_block(p, x, c, cast):
    """Mamba-2 block on the residual input x (B, L, d) -> (B, L, d)."""
    d_inner = c["expand"] * c["hidden_size"]
    hd, n, g = c["head_dim"], c["state_size"], c["n_groups"]
    nh = d_inner // hd
    bsz, l, _ = x.shape
    z = mm(x, p["wz"], cast)
    xin = mm(x, p["wx"], cast)
    bv = mm(x, p["wB"], cast)
    cv = mm(x, p["wC"], cast)
    dt = jax.nn.softplus(mm(x, p["wdt"], cast) + p["dt_bias"])   # (B, L, H)
    xin = jax.nn.silu(causal_conv(xin, p["conv_x"]))
    bv = jax.nn.silu(causal_conv(bv, p["conv_B"]))
    cv = jax.nn.silu(causal_conv(cv, p["conv_C"]))
    a = -jnp.exp(p["A_log"])
    xh = cast(xin).reshape(bsz, l, nh, hd)
    heads = lambda t: jnp.repeat(cast(t).reshape(bsz, l, g, n), nh // g, axis=2)
    y = ssd_minimal(xh * dt[..., None], dt * a, heads(bv), heads(cv),
                    c["ref_chunk"])
    y = (y + xh * p["D"][:, None]).reshape(bsz, l, d_inner)
    y = rms_norm(y * jax.nn.silu(z), p["scale"], c["rms_norm_eps"])
    return mm(y, p["out_proj"], cast)


def mamba2_layers(layers, x, c, cast):
    """Residual Mamba-2 layers stacked on their leading axis."""
    def body(h, lp):
        y = mamba2_block(lp["ssm"], rms_norm(h, lp["norm1"]["scale"],
                                             c["rms_norm_eps"]), c, cast)
        return h + y, None
    x, _ = jax.lax.scan(jax.checkpoint(body), x, layers)
    return x


def rope(x, theta):
    """Rotary embedding on halves, positions 0..S-1. x: (B, S, H, D)."""
    s, half = x.shape[1], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, block: int):
    """Softmax attention with a causal mask, one block of queries at a time.
    q, k, v: (B, S, H, D)."""
    bsz, s, h, d = q.shape
    nb = s // block
    qb = q.reshape(bsz, nb, block, h, d).transpose(1, 0, 2, 3, 4)

    def one(args):
        i, qi = args
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST) / math.sqrt(d)
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(jnp.arange(s)[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                          precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(one), (jnp.arange(nb), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(bsz, s, h, d)


def shared_attention_block(sp, x, c, cast):
    """Zamba2's shared block as the trainer runs it: pre-norm causal
    attention with rotary embedding, then a pre-norm SwiGLU MLP."""
    eps, h, hd = c["rms_norm_eps"], c["num_attention_heads"], c["attention_head_dim"]
    bsz, s, _ = x.shape
    a = rms_norm(x, sp["norm1"]["scale"], eps)
    q, k, v = (mm(a, sp["attn"][w], cast).reshape(bsz, s, h, hd)
               for w in ("wq", "wk", "wv"))
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    o = causal_attention(cast(q), cast(k), cast(v), c["ref_attn_block"])
    x = x + mm(o.reshape(bsz, s, h * hd), sp["attn"]["wo"], cast)
    m = rms_norm(x, sp["norm2"]["scale"], eps)
    gated = jax.nn.silu(mm(m, sp["mlp"]["gate"], cast)) * mm(m, sp["mlp"]["up"], cast)
    return x + mm(gated, sp["mlp"]["down"], cast)


# ---------------------------------------------------------------------------
# the training step

def nll_sum(logits, labels, z_loss):
    """Summed cross entropy plus z-loss over every position."""
    m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    label = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - label + z_loss * jnp.square(lse))


def lr_at(step: int, h: Dict[str, float]) -> float:
    """Linear warm-up from (step+1)/warmup, then cosine to a tenth."""
    if step < h["warmup_steps"]:
        return h["peak_lr"] * min(1.0, (step + 1) / max(h["warmup_steps"], 1))
    frac = min(max((step - h["warmup_steps"])
                   / max(h["total_steps"] - h["warmup_steps"], 1), 0.0), 1.0)
    return h["peak_lr"] * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi * frac)))


def _adamw(params, grads, m, v, step, lr, h):
    b1, b2, eps = 0.9, 0.95, 1e-8
    c1, c2 = 1.0 - b1 ** (step + 1), 1.0 - b2 ** (step + 1)

    def one(p, g, mi, vi):
        mi = b1 * mi + (1.0 - b1) * g
        vi = b2 * vi + (1.0 - b2) * g * g
        wd = h["weight_decay"] if p.ndim > 1 else 0.0
        upd = (mi / c1) / (jnp.sqrt(vi / c2) + eps) + wd * p
        return p - lr * upd, mi, vi

    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def _norms(tree) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(x))) for k, x in flatten(tree).items()}


def train_reference(model, c: Dict[str, Any], hyper: Dict[str, float],
                    seed: int, batches: List[Dict[str, np.ndarray]],
                    cast: str = "f32", rows: str = "all") -> Dict[str, Any]:
    """Follow len(batches) training steps from the seed's weights.

    ``model`` is the configuration's reference module (``param_spec`` and
    ``forward``). ``rows="half"`` leaves the second half of every batch out
    and takes the mean over the rest: one of the faults the check must
    catch. Returns each step's loss, each leaf's norm of the first step's
    clipped gradient and of the parameters' change over all the steps."""
    spec = model.param_spec(c)
    cast_fn = CASTS[cast]
    key = seed_key(seed)
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: init_params(spec, k))(key)
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        m, v = zeros(params), zeros(params)
        rb = c["ref_rows"]

        def block_loss(p, tok, lab):
            return nll_sum(model.forward(p, tok, c, cast_fn), lab, hyper["z_loss"])

        @functools.partial(jax.jit, donate_argnums=(1,))
        def acc_block(p, acc, tok, lab):
            loss, g = jax.value_and_grad(block_loss)(p, tok, lab)
            return loss, jax.tree.map(jnp.add, acc, g)

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def finish(p, acc, m, v, n_tok, step, lr):
            g = jax.tree.map(lambda x: x / n_tok, acc)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(1.0, hyper["grad_clip"]
                                                       / jnp.maximum(gn, 1e-12)), g)
            p2, m2, v2 = _adamw(p, g, m, v, step, lr, hyper)
            return p2, m2, v2, _norms(g)

        losses, first_grad = [], None
        for step, bt in enumerate(batches):
            tok, lab = bt["tokens"], bt["labels"]
            if rows == "half":
                tok, lab = tok[:tok.shape[0] // 2], lab[:lab.shape[0] // 2]
            acc, total = zeros(params), 0.0
            for r in range(0, tok.shape[0], rb):
                loss, acc = acc_block(params, acc, jnp.asarray(tok[r:r + rb]),
                                      jnp.asarray(lab[r:r + rb]))
                total += float(loss)
            losses.append(total / tok.size)
            params, m, v, gn = finish(params, acc, m, v, float(tok.size),
                                      step, lr_at(step, hyper))
            if step == 0:
                first_grad = {k: float(x) for k, x in gn.items()}
        del m, v
        change = jax.jit(lambda a, k: _norms(jax.tree.map(
            jnp.subtract, a, init_params(spec, k))))(params, key)
    return {"losses": losses, "grad": first_grad,
            "change": {k: float(x) for k, x in change.items()}}
