"""Gradients of the fused expert-GEMM / SSD Pallas kernels vs their XLA
oracles, the per-op dispatch rules, and train-step smokes with
``moe_gemm_impl="pallas"`` / ``ssm_impl="pallas"`` (mirrors
test_attention_grad.py for the two remaining fused kernels)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import InputShape, ParallelPlan, get_smoke_config
from repro.data import SyntheticDataset
from repro.kernels import (
    dispatch_ssd_scan,
    expert_gemm,
    select_gemm_impl,
    select_ssd_impl,
)
from repro.kernels.ref import expert_gemm_ref
from repro.models import build_model
from repro.models.families import gspmd_kernel_plan
from repro.models.ssm import ssd_scan
from repro.train import Hyper, init_train_state, make_train_step

jax.config.update("jax_platform_name", "cpu")


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# expert GEMM


GEMM_GRAD_CASES = [
    # (e, c, d, f, group_sizes)
    (2, 32, 16, 24, None),
    (3, 33, 20, 17, (33, 7, 0)),       # ragged + empty expert, unaligned dims
    (2, 64, 32, 32, (40, 64)),         # boundary straddles a row tile
    (4, 16, 48, 16, (5, 0, 16, 11)),
]


@pytest.mark.parametrize("case", GEMM_GRAD_CASES)
def test_expert_gemm_grad_matches_oracle(case):
    e, c, d, f, gs_t = case
    rng = np.random.default_rng(abs(hash(case)) % 2**32)
    x = _rand(rng, (e, c, d))
    w = _rand(rng, (e, d, f))
    cot = _rand(rng, (e, c, f))            # cotangent weighting
    gs = None if gs_t is None else jnp.asarray(gs_t, jnp.int32)

    def fused(x, w):
        return jnp.sum(expert_gemm(x, w, gs, block_c=16, block_f=16,
                                   block_d=16) * cot)

    def oracle(x, w):
        return jnp.sum(expert_gemm_ref(x, w, gs) * cot)

    np.testing.assert_allclose(float(fused(x, w)), float(oracle(x, w)),
                               rtol=1e-5)
    g_fused = jax.grad(fused, argnums=(0, 1))(x, w)
    g_ref = jax.grad(oracle, argnums=(0, 1))(x, w)
    for name, a, r in zip(("dx", "dw"), g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name} {case}")


def test_expert_gemm_group_sizes_zero_expert():
    """An expert with zero load must emit zero outputs and zero grads."""
    rng = np.random.default_rng(0)
    x = _rand(rng, (2, 16, 8))
    w = _rand(rng, (2, 8, 8))
    gs = jnp.asarray([0, 16], jnp.int32)
    out = expert_gemm(x, w, gs, block_c=8, block_f=8, block_d=8)
    assert float(jnp.abs(out[0]).max()) == 0.0
    dx, dw = jax.grad(
        lambda x, w: jnp.sum(expert_gemm(x, w, gs, block_c=8, block_f=8,
                                         block_d=8)), argnums=(0, 1))(x, w)
    assert float(jnp.abs(dx[0]).max()) == 0.0
    assert float(jnp.abs(dw[0]).max()) == 0.0
    assert float(jnp.abs(dx[1]).max()) > 0.0


# ---------------------------------------------------------------------------
# SSD chunk scan


SSD_GRAD_CASES = [
    # (b, l, h, p, g, n, chunk[, backward head block forced by a small
    # budget]); a head block spans a multiple of 128 lanes, or all H·P
    (1, 32, 2, 4, 1, 4, 8),
    (2, 48, 4, 64, 2, 8, 16),      # GQA-style g < h
    (1, 24, 4, 64, 2, 4, 24),      # single chunk, g < h
    (1, 32, 8, 4, 1, 8, 8),        # one group, one block of all 8 heads
    (2, 48, 8, 64, 2, 8, 16, 2),   # two head blocks per group, g = 2
    (1, 16, 6, 128, 3, 4, 16, 1),  # single chunk, g = 3, one head a block
    (1, 64, 6, 128, 1, 16, 16, 3), # blocks of 3 of 6 heads
]


@pytest.mark.parametrize("case", SSD_GRAD_CASES)
def test_ssd_grad_matches_oracle(case, force_head_block):
    from repro.kernels import ssd_chunk_scan
    b, l, h, p, g, n, chunk, *forced = case
    if forced:
        force_head_block(forced[0], h // g, p, n, chunk, backward=True,
                         groups=g)
    rng = np.random.default_rng(abs(hash(case)) % 2**32)
    x = _rand(rng, (b, l, h, p))
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _rand(rng, (b, l, g, n))
    C = _rand(rng, (b, l, g, n))
    cy = _rand(rng, (b, l, h, p))
    cst = _rand(rng, (b, h, p, n))         # cotangent on the final state too

    def fused(x, dt, A, B, C):
        y, st = ssd_chunk_scan(
            x, dt.transpose(0, 2, 1), A, B.transpose(0, 2, 1, 3),
            C.transpose(0, 2, 1, 3), chunk=chunk)
        return jnp.sum(y * cy) + jnp.sum(st * cst)

    def oracle(x, dt, A, B, C):
        y, st = ssd_scan(x, dt, A, B, C, chunk=chunk)
        return jnp.sum(y * cy) + jnp.sum(st * cst)

    np.testing.assert_allclose(float(fused(x, dt, A, B, C)),
                               float(oracle(x, dt, A, B, C)), rtol=1e-5)
    g_fused = jax.grad(fused, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    g_ref = jax.grad(oracle, argnums=(0, 1, 2, 3, 4))(x, dt, A, B, C)
    for name, a, r in zip(("dx", "ddt", "dA", "dB", "dC"), g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name} {case}")


# ---------------------------------------------------------------------------
# dispatch layer


def test_per_op_dispatch_rules():
    # explicit choices always honored
    for sel in (select_gemm_impl, select_ssd_impl):
        assert sel("xla") == "xla"
        assert sel("pallas") == "pallas"
        # auto never picks the interpreter off-TPU
        expected = "pallas" if jax.default_backend() == "tpu" else "xla"
        assert sel("auto") == expected
        with pytest.raises(ValueError):
            sel("cuda")
    # the fused SSD kernel starts from a zero state: auto takes the XLA scan,
    # an explicit pallas request raises
    assert select_ssd_impl("auto", has_initial_state=True) == "xla"
    with pytest.raises(ValueError, match="zero state"):
        select_ssd_impl("pallas", has_initial_state=True)


def test_ssd_auto_needs_a_lane_legal_head_block(monkeypatch):
    """On a TPU, auto takes the kernel only where some head block spans a
    multiple of 128 lanes (or every head); else the XLA scan."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert select_ssd_impl("auto") == "pallas"
    assert select_ssd_impl("auto", lanes_ok=False) == "xla"


def test_gspmd_kernel_plan_rules(monkeypatch):
    """On a mesh the GSPMD model path resolves "auto" kernels to XLA (GSPMD
    cannot partition a Mosaic kernel) and refuses an explicit "pallas" on a
    TPU backend; one device, or no mesh, keeps the plan as given."""
    class Mesh:                              # the one attribute read
        def __init__(self, size):
            self.size = size

    plan = ParallelPlan(attn_impl="auto", moe_gemm_impl="xla",
                        ssm_impl="pallas")
    assert gspmd_kernel_plan(plan, None) is plan
    assert gspmd_kernel_plan(plan, Mesh(1)) is plan
    got = gspmd_kernel_plan(plan, Mesh(4))   # off-TPU pallas interprets
    assert (got.attn_impl, got.moe_gemm_impl, got.ssm_impl) == \
        ("xla", "xla", "pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="ssm_impl='pallas'.*GSPMD"):
        gspmd_kernel_plan(plan, Mesh(4))
    assert gspmd_kernel_plan(plan, Mesh(1)) is plan


def test_plan_validates_impl_knobs():
    cfg = get_smoke_config("mamba2-370m")
    ParallelPlan(moe_gemm_impl="pallas", ssm_impl="pallas").validate(cfg)
    with pytest.raises(ValueError):
        ParallelPlan(moe_gemm_impl="cuda").validate(cfg)
    with pytest.raises(ValueError):
        ParallelPlan(ssm_impl="triton").validate(cfg)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_dispatch_ssd_scan_pads_unaligned_lengths(impl):
    """l % chunk != 0 must pad to the boundary (dt=0 rides the state through),
    matching the single-chunk exact reformulation — not crash, not collapse."""
    rng = np.random.default_rng(4)
    b, l, h, p, g, n = 1, 40, 2, 4, 1, 4
    x = _rand(rng, (b, l, h, p))
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _rand(rng, (b, l, g, n))
    C = _rand(rng, (b, l, g, n))
    y, st = dispatch_ssd_scan(x, dt, A, B, C, chunk=16, impl=impl)
    y_ref, st_ref = ssd_scan(x, dt, A, B, C, chunk=l)   # chunk-invariant oracle
    assert y.shape == (b, l, h, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), rtol=2e-4,
                               atol=2e-4)


def test_ssm_block_unaligned_keeps_configured_chunk(monkeypatch):
    """ssm_block on an unaligned length must keep the configured chunk size
    (padding to the boundary), never degrade to one whole-sequence chunk whose
    (q, q) decay matrix is quadratic in L."""
    import repro.models.ssm as S
    from repro.core import Family, ModelConfig, SSMConfig

    cfg = ModelConfig("t", Family.SSM, n_layers=1, d_model=32, n_heads=0,
                      n_kv_heads=0, d_ff=0, vocab=64,
                      ssm=SSMConfig(d_state=8, head_dim=16, expand=2, chunk=16))
    p = S.init_ssm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    b, l = 2, 24                               # 16 < l, l % 16 != 0
    x = _rand(rng, (b, l, 32))

    seen = {}
    orig = S.ssd_scan

    def spy(x, dt, A, B, C, chunk, initial_state=None):
        seen["chunk"], seen["l"] = chunk, x.shape[1]
        return orig(x, dt, A, B, C, chunk, initial_state)

    monkeypatch.setattr(S, "ssd_scan", spy)
    out = S.ssm_block(p, x, cfg, jnp.float32, plan=ParallelPlan(ssm_impl="xla"))
    assert out.shape == (b, l, 32)
    assert seen["chunk"] == cfg.ssm.chunk, "collapsed to a whole-sequence chunk"
    assert seen["l"] == 32                    # padded to the chunk boundary

    # numerics unchanged vs the exact whole-sequence reformulation
    monkeypatch.setattr(S, "ssd_scan", orig)
    import dataclasses
    cfg_whole = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm,
                                                                 chunk=l))
    ref = S.ssm_block(p, x, cfg_whole, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-4,
                               atol=5e-4)


# ---------------------------------------------------------------------------
# end-to-end: train steps differentiate through the fused kernels


SHAPE = InputShape("t", 16, 2, "train")


def _train_metrics(cfg, plan):
    ds = SyntheticDataset(cfg, SHAPE)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    model = build_model(cfg, plan)
    state = init_train_state(model, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, plan, Hyper(total_steps=10)))
    _, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(m["grad_norm"]))
    return m


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-moe-16b"])
def test_train_step_moe_gemm_impl_pallas_matches_xla(arch):
    cfg = get_smoke_config(arch)
    metrics = {
        impl: _train_metrics(cfg, ParallelPlan(remat="none",
                                               compute_dtype="float32",
                                               moe_gemm_impl=impl))
        for impl in ("xla", "pallas")
    }
    np.testing.assert_allclose(float(metrics["pallas"]["loss"]),
                               float(metrics["xla"]["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(metrics["pallas"]["grad_norm"]),
                               float(metrics["xla"]["grad_norm"]), rtol=1e-3)


@pytest.mark.parametrize("arch", ["mamba2-370m", "zamba2-1.2b"])
def test_train_step_ssm_impl_pallas_matches_xla(arch):
    cfg = get_smoke_config(arch)
    metrics = {
        impl: _train_metrics(cfg, ParallelPlan(remat="none",
                                               compute_dtype="float32",
                                               ssm_impl=impl))
        for impl in ("xla", "pallas")
    }
    np.testing.assert_allclose(float(metrics["pallas"]["loss"]),
                               float(metrics["xla"]["loss"]), rtol=1e-5)
    np.testing.assert_allclose(float(metrics["pallas"]["grad_norm"]),
                               float(metrics["xla"]["grad_norm"]), rtol=1e-3)
