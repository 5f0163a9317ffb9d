"""Gradients of the fused Pallas attention vs the XLA blockwise oracle, the
kernel-dispatch rules, and a train-step smoke with ``attn_impl="pallas"``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Family, InputShape, ModelConfig, ParallelPlan
from repro.data import SyntheticDataset
from repro.kernels import flash_attention, select_impl
from repro.models import build_model
from repro.models.layers import attention, attention_blockwise, attention_direct
from repro.train import Hyper, init_train_state, make_train_step

jax.config.update("jax_platform_name", "cpu")


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _hm(x):  # kernel head-major (B,H,S,hd) <-> models (B,S,H,hd)
    return x.transpose(0, 2, 1, 3)


GRAD_CASES = [
    # (b, hq, hkv, s, t, hd, causal, window, softcap, q_offset)
    (1, 4, 2, 64, 64, 32, True, 0, 0.0, 0),        # GQA
    (2, 2, 2, 48, 48, 32, True, 0, 0.0, 0),        # unaligned seq len
    (1, 2, 1, 64, 64, 32, True, 12, 0.0, 0),       # sliding window + GQA
    (1, 2, 2, 64, 64, 32, True, 0, 15.0, 0),       # logit softcap
    (1, 2, 2, 64, 64, 32, False, 0, 0.0, 0),       # bidirectional
    (1, 2, 2, 32, 96, 32, True, 0, 0.0, 64),       # chunked-prefill q_offset
    (1, 4, 1, 40, 72, 32, True, 16, 30.0, 32),     # everything, unaligned
]


@pytest.mark.parametrize("case", GRAD_CASES)
def test_flash_grad_matches_blockwise_oracle(case):
    b, hq, hkv, s, t, hd, causal, window, cap, qoff = case
    rng = np.random.default_rng(abs(hash(case)) % 2**32)
    q = _rand(rng, (b, hq, s, hd))
    k = _rand(rng, (b, hkv, t, hd))
    v = _rand(rng, (b, hkv, t, hd))
    w = _rand(rng, (b, hq, s, hd))          # cotangent weighting
    kw = dict(causal=causal, window=window, softcap=cap, q_offset=qoff)

    def fused(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=32, block_k=32,
                                       **kw) * w)

    def oracle(q, k, v):
        out = attention_blockwise(_hm(q), _hm(k), _hm(v), block_size=8, **kw)
        return jnp.sum(_hm(out) * w)

    np.testing.assert_allclose(float(fused(q, k, v)), float(oracle(q, k, v)),
                               rtol=1e-4)
    g_fused = jax.grad(fused, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(oracle, argnums=(0, 1, 2))(q, k, v)
    for name, a, r in zip("qkv", g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-3,
                                   atol=1e-3, err_msg=f"d{name} {case}")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_grad_dtype_preserved(dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), dtype)
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, block_q=32, block_k=32).astype(jnp.float32))
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, q, q)
    for g in grads:
        assert g.dtype == dtype
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# dispatch layer


def test_dispatch_rules():
    # explicit choices always honored (static masks)
    assert select_impl("xla", head_dim=128, window=0, q_offset=0) == "xla"
    assert select_impl("pallas", head_dim=128, window=0, q_offset=0) == "pallas"
    # traced mask params (gemma2 alternation): auto takes XLA, an explicit
    # pallas request raises instead of quietly running XLA
    traced = jnp.int32(4)
    assert select_impl("auto", head_dim=128, window=traced, q_offset=0) == "xla"
    with pytest.raises(ValueError, match="static window"):
        select_impl("pallas", head_dim=128, window=traced, q_offset=0)
    # auto never picks the interpreter off-TPU
    expected = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert select_impl("auto", head_dim=128, window=0, q_offset=0) == expected
    with pytest.raises(ValueError):
        select_impl("cuda", head_dim=128, window=0, q_offset=0)


def test_dispatch_pallas_matches_xla_in_model_layout():
    rng = np.random.default_rng(1)
    q = _rand(rng, (2, 48, 4, 32))
    k = _rand(rng, (2, 48, 2, 32))
    v = _rand(rng, (2, 48, 2, 32))
    a = attention(q, k, v, causal=True, window=8, impl="xla")
    b = attention(q, k, v, causal=True, window=8, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


def test_unaligned_long_kv_stays_blockwise(monkeypatch):
    """KV lengths that don't divide the block size must pad + stay blockwise,
    never silently fall back to the O(S·T) direct path."""
    import repro.models.layers as L

    rng = np.random.default_rng(2)
    s = t = 72                                  # > 2*32 and 72 % 32 != 0
    q = _rand(rng, (1, s, 2, 16))
    k = _rand(rng, (1, t, 2, 16))
    v = _rand(rng, (1, t, 2, 16))
    ref = attention_direct(q, k, v, causal=True, window=20)

    def _no_direct(*a, **kw):
        raise AssertionError("quadratic fallback taken for unaligned long KV")

    monkeypatch.setattr(L, "attention_direct", _no_direct)
    out = attention(q, k, v, causal=True, window=20, block_size=32, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_blockwise_kv_len_masks_padding():
    rng = np.random.default_rng(3)
    q = _rand(rng, (1, 8, 2, 16))
    k = _rand(rng, (1, 40, 2, 16))
    v = _rand(rng, (1, 40, 2, 16))
    ref = attention_direct(q, k, v, causal=False)
    pad = ((0, 0), (0, 24), (0, 0), (0, 0))
    out = attention_blockwise(q, jnp.pad(k, pad), jnp.pad(v, pad),
                              causal=False, block_size=16, kv_len=40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# end-to-end: the train step differentiates through the fused kernel


def test_train_step_attn_impl_pallas_matches_xla():
    cfg = ModelConfig("t", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab=128)
    shape = InputShape("t", 32, 4, "train")
    ds = SyntheticDataset(cfg, shape)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}

    metrics = {}
    for impl in ("xla", "pallas"):
        plan = ParallelPlan(remat="none", compute_dtype="float32",
                            attn_impl=impl)
        model = build_model(cfg, plan)
        state = init_train_state(model, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(model, plan, Hyper(total_steps=10)))
        _, m = step(state, batch)
        assert np.isfinite(float(m["loss"])), impl
        assert np.isfinite(float(m["grad_norm"])), impl
        metrics[impl] = m

    np.testing.assert_allclose(float(metrics["pallas"]["loss"]),
                               float(metrics["xla"]["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(metrics["pallas"]["grad_norm"]),
                               float(metrics["xla"]["grad_norm"]), rtol=1e-3)
