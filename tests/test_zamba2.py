"""Zamba2-7B's published shared block (``models.families.build_zamba2``)
against the plain float32 reference of the benchmark's configuration
(``bench/configs/zamba2-7b.7l-tp2.py``), on weights drawn from a seed, at tiny
uncut layouts (two Mamba-2 groups, both shared blocks, three applications),
in float32 under ``highest`` precision; the chip's share of each layer adds
up to the uncut layer; decoding through the cache; a tiny run of the
benchmark cell; the name scopes of the step; the trainer's CLI."""

import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference as R
from bench.tests.tiny import tiny_cell, use_test_cache
from repro.core import ParallelPlan, get_smoke_config
from repro.kernels import ssd_scan
from repro.models import build_model, families
from repro.models import ssm as ssm_lib
from repro.models.layers import attention
from repro.train import Hyper, init_train_state, make_train_step

CELL = "zamba2-7b.7l-tp2.pretrain-4k"
SEED = 2 ** 31 + 9
F32 = ParallelPlan(remat="none", compute_dtype="float32")
# The program and the reference differ in association only (SSD chunk 32
# against 16, attention in one block against blocks of 16, split against
# fused projections): float32 rounding, a few ulps of the largest value.
TOL = 1e-5


def _layout(**over):
    """(reference module, its config, the program's ModelConfig) of a tiny
    uncut layout: 6 layers, two groups, applications before layers 1, 3
    and 4 (blocks 0, 1, 0), MLP adapters of rank 8."""
    cell = tiny_cell(CELL)
    c = cell.config
    c.update(num_hidden_layers=6, expand=2, n_groups=2, adapter_rank=8,
             hybrid_layer_ids=[1, 3, 4], ref_attn_block=16)
    c.update(over)
    cfg = dataclasses.replace(
        harness.program_config(c), hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
        adapter_rank=c["adapter_rank"], num_mem_blocks=c["num_mem_blocks"])
    return cell.model, c, cfg


def _params(ref, c):
    return R.init_params(ref.param_spec(c), R.seed_key(SEED))


def _tokens(c, b=2, s=64, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, c["vocab_size"], (b, s)), jnp.int32)


def _close(got, want, tol=TOL):
    scale = float(jnp.max(jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    assert err <= tol * scale, (err, scale)


@pytest.mark.parametrize("blocks", [2, 1])
def test_forward_matches_reference(blocks):
    """Two blocks alternating over three applications, and one block
    shared by all three."""
    ref, c, cfg = _layout(num_mem_blocks=blocks)
    assert cfg.shared_applications == (1, 3, 4)
    assert cfg.shared_blocks == blocks
    model = build_model(cfg, F32)
    params = _params(ref, c)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert harness.leaf_names(params) == harness.leaf_names(want)
    assert ([x.shape for x in jax.tree.leaves(params)]
            == [x.shape for x in jax.tree.leaves(want)])
    tok = _tokens(c)
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(model.forward)(params, {"tokens": tok})
        ref_logits = jax.jit(lambda p, t: ref.forward(
            p, t, c, R.CASTS["f32"]))(params, tok)
    _close(got, ref_logits)


def test_applications_beyond_the_depth_are_left_out():
    """The benchmark's tiny cut keeps 2 layers and the published ids: no
    application, no shared weights, in the program and the reference."""
    ref, c, cfg = _layout(num_hidden_layers=2, hybrid_layer_ids=[6, 11])
    model = build_model(cfg, F32)
    params = _params(ref, c)
    assert "shared" not in params and "apps" not in params
    assert (harness.leaf_names(params) == harness.leaf_names(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    tok = _tokens(c)
    with jax.default_matmul_precision("highest"):
        _close(jax.jit(model.forward)(params, {"tokens": tok})[0],
               jax.jit(lambda p, t: ref.forward(
                   p, t, c, R.CASTS["f32"]))(params, tok))


def test_loss_gradients_match_reference():
    ref, c, cfg = _layout()
    model = build_model(cfg, F32)
    params = _params(ref, c)
    tok, lab = _tokens(c, seed=1), _tokens(c, seed=2)
    with jax.default_matmul_precision("highest"):
        gp = jax.jit(jax.grad(lambda p: R.nll_sum(
            model.forward(p, {"tokens": tok})[0], lab, 1e-4)))(params)
        gr = jax.jit(jax.grad(lambda p: R.nll_sum(
            ref.forward(p, tok, c, R.CASTS["f32"]), lab, 1e-4)))(params)
    gp, gr = R.flatten(gp), R.flatten(gr)
    assert list(gp) == list(gr)
    # each leaf within float32 rounding of its own largest entry; the
    # gradients sum over 128 positions and pass back through six layers
    for name in gr:
        err = float(jnp.max(jnp.abs(gp[name] - gr[name])))
        assert err <= 1e-4 * float(jnp.max(jnp.abs(gr[name]))), name


def _split(x, k, axis, parts=2):
    n = x.shape[axis] // parts
    return jax.lax.slice_in_dim(x, k * n, (k + 1) * n, axis=axis)


def _mixer_half(p, k, c):
    """Group k's share of one Mamba-2 mixer's weights."""
    chans = {"wz": 1, "wx": 1, "out_proj": 0, "conv_x": 0, "conv_bias_x": 0,
             "scale": 0, "wdt": 1, "dt_bias": 0, "A_log": 0, "D": 0,
             "wB": 1, "wC": 1, "conv_B": 0, "conv_C": 0, "conv_bias_B": 0,
             "conv_bias_C": 0}
    return {name: _split(w, k, chans[name]) for name, w in p.items()}


def test_mixer_shares_add_up():
    """Each group's heads, with their B/C, conv and norm channels, on one
    chip: the two halves' outputs sum to the uncut mixer's."""
    ref, c, cfg = _layout()
    p = jax.tree.map(lambda a: a[0], _params(ref, c)["layers"]["ssm"])
    half = dataclasses.replace(cfg, ssm=dataclasses.replace(
        cfg.ssm, expand=cfg.ssm.expand // 2, n_groups=1))
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (2, 64, c["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: ref.mamba2_block(
            p, x, c, R.CASTS["f32"]))(p, x)
        half_block = jax.jit(lambda p, x: ssm_lib.ssm_block(
            p, x, half, jnp.float32, plan=F32))
        got = sum(half_block(_mixer_half(p, k, c), x) for k in range(2))
    _close(got, want)


def test_attention_shares_add_up():
    """Half of the heads a chip (q/k/v columns, o rows): the halves'
    a·W_o sum to the uncut one."""
    ref, c, cfg = _layout()
    bp = jax.tree.map(lambda a: a[0], _params(ref, c)["shared"]["attn"])
    half = dataclasses.replace(cfg, n_heads=cfg.n_heads // 2,
                               n_kv_heads=cfg.n_kv_heads // 2)
    u = jnp.asarray(np.random.default_rng(4).standard_normal(
        (2, 64, 2 * c["hidden_size"])), jnp.float32)
    pos = jnp.arange(64)
    attend = lambda q, k, v: attention(
        q, k, v, causal=True, scale=families.zamba2_attn_scale(cfg),
        impl="xla")
    got = 0.0
    with jax.default_matmul_precision("highest"):
        want = ref.attention(bp, u, c, R.CASTS["f32"])
        for k in range(2):
            hp = {"wq": _split(bp["wq"], k, 1), "wk": _split(bp["wk"], k, 1),
                  "wv": _split(bp["wv"], k, 1), "wo": _split(bp["wo"], k, 0)}
            got = got + families.zamba2_attention(
                hp, u, half, jnp.float32, pos, attend)
    _close(got, want)


def test_mlp_shares_add_up():
    """Half of the FFN columns a chip (gate, up, the adapter's gate and up
    alike; down's rows): the halves' outputs sum to the uncut MLP's."""
    ref, c, _ = _layout()
    params = _params(ref, c)
    bp = jax.tree.map(lambda a: a[0], params["shared"]["mlp"])
    ad = jax.tree.map(lambda a: a[0], params["apps"]["mlp_adapter"])
    m = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 64, c["hidden_size"])), jnp.float32)
    got = 0.0
    with jax.default_matmul_precision("highest"):
        want = ref.mlp(bp, ad, m, R.CASTS["f32"])
        for k in range(2):
            hp = {"gate": _split(bp["gate"], k, 1), "up": _split(bp["up"], k, 1),
                  "down": _split(bp["down"], k, 0)}
            had = {"a": ad["a"], "gate": _split(ad["gate"], k, 1),
                   "up": _split(ad["up"], k, 1)}
            got = got + families.zamba2_mlp(hp, had, m, jnp.float32)
    _close(got, want)


def test_decode_through_the_cache_matches_forward():
    ref, c, cfg = _layout()
    model = build_model(cfg, F32)
    params = _params(ref, c)
    b, s = 2, 16
    tok = _tokens(c, b, s)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(model.forward)(params, {"tokens": tok})
        cache = model.init_cache(b, s)
        step = jax.jit(model.decode_step)
        outs = []
        for t in range(s):
            lg, cache = step(params, cache, tok[:, t], jnp.int32(t))
            outs.append(lg)
    # the recurrent step and the chunked scan sum in different orders
    _close(jnp.stack(outs, 1), logits)


def test_ssd_head_block_at_56_heads():
    """Zamba2-7B's chip share: 56 heads in one group, P 64, N 64, chunk
    256. The rule takes divisors that are not powers of two: 28 heads a
    grid step forward, 14 backward, each within the VMEM budget."""
    fwd = ssd_scan.ssd_head_block(56, 64, 64, 256)
    bwd = ssd_scan.ssd_head_block(56, 64, 64, 256, backward=True)
    assert (fwd, bwd) == (28, 14)
    for hb, backward in ((fwd, False), (bwd, True)):
        assert ssd_scan.vmem_bytes(hb, 64, 64, 256, backward) \
            <= ssd_scan.VMEM_BUDGET


def test_tiny_cell_run_is_correct(monkeypatch, tmp_path):
    """The benchmark cell at a tiny size, cut to its own 7 layers so that
    layer 6 is a hybrid layer: the trainer's loop against the reference."""
    use_test_cache(monkeypatch, str(tmp_path / "jax"))
    try:
        cell = tiny_cell(CELL)
        cell.config["num_hidden_layers"] = 7
        assert harness.program_config(cell.config).shared_applications == (6,)
        out = harness.run_cell(cell, 2 ** 31 + 3, 1.0, False,
                               time.perf_counter())
    finally:
        use_test_cache(None, None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.fixture(scope="module")
def step_text():
    """The lowered train step of the smoke preset (full remat)."""
    cfg = get_smoke_config("zamba2-7b")
    plan = ParallelPlan(remat="full", compute_dtype="float32")
    model = build_model(cfg, plan)
    state = init_train_state(model, jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 32), jnp.int32)
    step = jax.jit(make_train_step(model, plan, Hyper(total_steps=10)))
    return step.lower(state, {"tokens": tokens, "labels": tokens}).as_text(
        debug_info=True)


@pytest.mark.parametrize("scope", ["shared_block", "adapter",
                                   "shared_block/attn", "shared_block/mlp",
                                   "shared_block/norm", "mlp/adapter",
                                   "mixer"])
def test_train_step_hlo_carries_the_shared_block_scopes(step_text, scope):
    assert re.search(rf"[\"/(]{scope}[/)]", step_text), scope


def test_trainer_cli_runs_the_smoke_preset(tmp_path, monkeypatch):
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    res = train.main(["--arch", "zamba2-7b", "--steps", "4", "--batch", "2",
                      "--seq", "32", "--remat", "full",
                      "--ckpt-dir", str(tmp_path / "ckpt"),
                      "--log-every", "1"])
    rep = res.report
    assert rep.steps_done == 4 and not rep.anomalies
    assert np.isfinite(rep.losses).all()
