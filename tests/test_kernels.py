"""Per-kernel shape/dtype sweeps asserting allclose against the ref.py oracles
(interpret mode executes the kernel bodies in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import expert_gemm, flash_attention
from repro.kernels.ref import expert_gemm_ref, flash_attention_ref


def _t(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


FLASH_CASES = [
    # (b, hq, hkv, s, t, hd, causal, window, softcap)
    (2, 4, 2, 128, 128, 64, True, 0, 0.0),
    (1, 4, 4, 256, 256, 64, True, 32, 0.0),
    (2, 2, 1, 100, 100, 32, True, 0, 30.0),     # non-divisible seq (padding)
    (1, 8, 2, 128, 128, 128, False, 0, 0.0),
    (1, 2, 2, 64, 192, 64, True, 0, 0.0),       # cross lengths (q != kv)
    (1, 4, 1, 128, 128, 256, True, 4096, 50.0), # gemma2-like head_dim
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_allclose(case, dtype):
    b, hq, hkv, s, t, hd, causal, window, cap = case
    rng = np.random.default_rng(hash(case) % 2**32)
    q = _t(rng, (b, hq, s, hd), dtype)
    k = _t(rng, (b, hkv, t, hd), dtype)
    v = _t(rng, (b, hkv, t, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window,
                              softcap=cap)
    err = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32))
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    assert err.max() < tol, f"{case} {dtype}: max err {err.max()}"


@pytest.mark.parametrize("blocks", [(32, 32, 32), (64, 128, 64)])
def test_flash_attention_block_shape_independence(blocks):
    """Output must not depend on the tiling choice."""
    bq, bk, _ = blocks
    rng = np.random.default_rng(7)
    q = _t(rng, (1, 2, 128, 64), jnp.float32)
    k = _t(rng, (1, 2, 128, 64), jnp.float32)
    v = _t(rng, (1, 2, 128, 64), jnp.float32)
    a = flash_attention(q, k, v, block_q=bq, block_k=bk)
    b = flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


GEMM_CASES = [
    (4, 64, 128, 256),
    (2, 100, 130, 70),       # non-divisible everything (padding)
    (8, 128, 256, 512),
    (1, 32, 512, 64),
]


@pytest.mark.parametrize("case", GEMM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_gemm_allclose(case, dtype):
    e, c, d, f = case
    rng = np.random.default_rng(hash(case) % 2**32)
    x = _t(rng, (e, c, d), dtype)
    w = _t(rng, (e, d, f), dtype)
    out = expert_gemm(x, w, block_c=64, block_f=64, block_d=64)
    ref = expert_gemm_ref(x, w)
    a, r = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    denom = np.maximum(np.abs(r), 1.0)
    rel = (np.abs(a - r) / denom).max()
    tol = 3e-2 if dtype == jnp.bfloat16 else 5e-5   # blocked accumulation order
    assert rel < tol, f"{case} {dtype}: max rel err {rel}"


SSD_CASES = [
    # (b, l, h, p, g, n, chunk[, head block forced by a small VMEM budget])
    (2, 64, 4, 8, 2, 16, 16),
    (1, 128, 2, 16, 1, 32, 32),
    (1, 96, 4, 8, 4, 8, 24),       # chunk not power of two
    (2, 32, 8, 4, 2, 8, 32),       # single chunk
    (1, 64, 8, 8, 1, 16, 16),      # one group, one block of all 8 heads
    (2, 64, 12, 8, 3, 8, 16, 2),   # g = 3, two head blocks per group
    (1, 32, 8, 8, 2, 16, 32, 2),   # single chunk, two head blocks per group
    (1, 48, 6, 8, 1, 8, 16, 3),    # blocks of 3 of 6 heads
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_chunk_scan_allclose(case, force_head_block):
    """Fused SSD kernel vs the pure-jnp ssd_scan oracle (y and final state)."""
    from repro.kernels import ssd_chunk_scan
    from repro.models.ssm import ssd_scan
    b, l, h, p, g, n, chunk, *forced = case
    if forced:
        force_head_block(forced[0], h // g, p, n, chunk)
    rng = np.random.default_rng(hash(case) % 2**32)
    x = _t(rng, (b, l, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, l, h)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _t(rng, (b, l, g, n), jnp.float32)
    C = _t(rng, (b, l, g, n), jnp.float32)
    y_ref, s_ref = ssd_scan(x, dt, A, B, C, chunk=chunk)
    y_k, s_k = ssd_chunk_scan(
        x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1), A,
        B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3), chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_k.transpose(0, 2, 1, 3)),
                               np.asarray(y_ref), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_kernel_state_carries_across_chunks():
    """Zeroing the first chunk's inputs must change later chunks only through
    the carried state (which must then be exactly the remaining recurrence)."""
    from repro.kernels import ssd_chunk_scan
    rng = np.random.default_rng(5)
    b, l, h, p, g, n, chunk = 1, 64, 2, 8, 1, 8, 16
    x = _t(rng, (b, h, l, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.2, (b, h, l)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B = _t(rng, (b, g, l, n), jnp.float32)
    C = _t(rng, (b, g, l, n), jnp.float32)
    y, _ = ssd_chunk_scan(x, dt, A, B, C, chunk=chunk)
    x2 = x.at[:, :, :chunk].set(0.0)
    y2, _ = ssd_chunk_scan(x2, dt, A, B, C, chunk=chunk)
    # first chunk output changed, later chunks differ (state propagated)
    assert float(jnp.abs(y[:, :, :chunk]).max()) > 0
    assert float(jnp.abs(y2[:, :, :chunk]).max()) < 1e-6
    assert float(jnp.abs(y[:, :, chunk:] - y2[:, :, chunk:]).max()) > 1e-6


# (heads per group the kernel sees, p, n, chunk): mamba2-370m (32 heads),
# its local heads under tensor parallelism of 2 and 4, zamba2-1.2b (64
# heads, N 64), a head count with an odd divisor
HEAD_BLOCK_SHAPES = [(32, 64, 128, 128), (16, 64, 128, 128),
                     (8, 64, 128, 128), (64, 64, 64, 128), (24, 64, 128, 128)]


@pytest.mark.parametrize("shape", HEAD_BLOCK_SHAPES)
@pytest.mark.parametrize("backward", [False, True])
def test_ssd_head_block_fits_budget(shape, backward):
    """The head block divides the heads per group, fits the VMEM budget, and
    is the largest divisor that does."""
    from repro.kernels import ssd_scan as S
    hpg, p, n, chunk = shape
    hb = S.ssd_head_block(hpg, p, n, chunk, backward)
    assert hpg % hb == 0
    assert S.vmem_bytes(hb, p, n, chunk, backward) <= S.VMEM_BUDGET
    for bigger in range(hb + 1, hpg + 1):
        if hpg % bigger == 0:
            assert S.vmem_bytes(bigger, p, n, chunk, backward) > S.VMEM_BUDGET


def _ssd_calls(b, h, l, p, n, chunk=128):
    """{kernel name: (grid, output shapes)} of a forward and backward SSD call,
    traced at these shapes (nothing runs)."""
    from repro.kernels.ssd_scan import ssd_chunk_scan
    sds = jax.ShapeDtypeStruct
    args = [sds((b, h, l, p), jnp.bfloat16), sds((b, h, l), jnp.float32),
            sds((h,), jnp.float32), sds((b, 1, l, n), jnp.bfloat16),
            sds((b, 1, l, n), jnp.bfloat16)]

    def fwd_bwd(*a):
        (y, st), vjp = jax.vjp(
            lambda *d: ssd_chunk_scan(*d, chunk=chunk, interpret=True), *a)
        return vjp((jnp.ones_like(y), jnp.ones_like(st)))

    calls = {}
    for eqn in jax.make_jaxpr(fwd_bwd)(*args).jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = (eqn.params["grid_mapping"].grid,
                                         [v.aval.shape for v in eqn.outvars])
    return calls


def test_ssd_grid_at_mamba2_width():
    """At mamba2-370m's shapes (8 x 2048 tokens, 32 heads in one group) each
    kernel call runs one grid step per head block and chunk, and the
    backward writes dB/dC once per head block, not per head."""
    from repro.kernels.ssd_scan import ssd_head_block
    calls = _ssd_calls(8, 32, 2048, 64, 128)
    assert set(calls) == {"ssd_fwd", "ssd_bwd"}
    for name, (grid, _) in calls.items():
        hb = ssd_head_block(32, 64, 128, 128, backward=name == "ssd_bwd")
        assert grid == (8, 32 // hb, 16)
        assert int(np.prod(grid)) <= 256
    grid, shapes = calls["ssd_bwd"]
    assert shapes[3] == shapes[4] == (8, grid[1], 2048, 128)


def test_expert_gemm_expert_isolation():
    """Each expert's output must depend only on its own weight slice."""
    rng = np.random.default_rng(3)
    x = _t(rng, (4, 32, 64), jnp.float32)
    w = _t(rng, (4, 64, 32), jnp.float32)
    base = np.asarray(expert_gemm(x, w, block_c=32, block_f=32, block_d=32))
    w2 = w.at[2].set(0.0)
    out = np.asarray(expert_gemm(x, w2, block_c=32, block_f=32, block_d=32))
    assert np.allclose(out[2], 0.0)
    np.testing.assert_allclose(out[[0, 1, 3]], base[[0, 1, 3]], rtol=1e-6)
