"""Compile every Pallas kernel, forward and backward, for a TPU v5e chip.

Interpret mode (the rest of the kernel tests) cannot see what the TPU's
compiler refuses: block shapes off the (8, 128) tiling, rank-1 blocks, more
fast memory than a kernel may use. The TPU compiler is installed here and
compiles for a chip that is described, not attached, so each kernel is
lowered at a published model width and must come out as a Mosaic custom
call. A train step on a described 2x2 mesh shows which kernels each
parallel path keeps. Nothing runs; results are checked on the chip by
``chip_smoke.py``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import Family, ModelConfig, ParallelPlan, SSMConfig, sharding
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_gemm import expert_gemm
from repro.kernels.ssd_scan import ssd_chunk_scan
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import adamw_init
from repro.train import Hyper, TrainState, make_train_step


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 slice. Described here, in a fixture, and never at
    import: only one process may load the TPU library."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 (any failure means: cannot describe)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip, so the cache stays off here."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _attention(hq, hkv, hd, s, window=0, softcap=0.0, scale=None):
    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               softcap=softcap, scale=scale, interpret=False)
    shapes = [((1, hq, s, hd), jnp.bfloat16), ((1, hkv, s, hd), jnp.bfloat16),
              ((1, hkv, s, hd), jnp.bfloat16)]
    return fwd, shapes, (0, 1, 2), ("flash_fwd", "flash_dq", "flash_dkv")


def _ssd(h=32, l=4096, p=64, n=128, chunk=128):
    def fwd(x, dt, A, B, C):
        return ssd_chunk_scan(x, dt, A, B, C, chunk=chunk, interpret=False)[0]
    shapes = [((1, l, h, p), jnp.bfloat16), ((1, h, l), jnp.float32),
              ((h,), jnp.float32), ((1, 1, l, n), jnp.bfloat16),
              ((1, 1, l, n), jnp.bfloat16)]
    return fwd, shapes, (0, 1, 2, 3, 4), ("ssd_fwd", "ssd_bwd")


def _gemm(e=8, c=512, d=2048, f=1024):
    def fwd(x, w, gs):
        return expert_gemm(x, w, gs, interpret=False)
    shapes = [((e, c, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16),
              ((e,), jnp.int32)]
    return fwd, shapes, (0, 1), ("grouped_gemm_rows", "grouped_gemm_contract")


# published widths: qwen1.5-4b (20 heads, head dim 128), gemma2-9b (GQA 16/8,
# head dim 256, window 4096, softcap 50), mamba2-370m (32 heads, P=64, N=128,
# chunk 128), zamba2-1.2b's Mamba-2 layers (64 heads, P=64, N=64, one group:
# the head block's VMEM budget at twice the heads), olmoe-1b-7b experts
# (d=2048, f=1024; 8 of its 64 experts); zamba2-7b's chip share, one of two
# tensor-parallel ranks: 16 attention heads of 224 (not a multiple of the
# 128 lanes) with its (224/2)^-1/2 scale, and one Mamba-2 group of 56 heads
# (N=64, chunk 256: head blocks of 28 in both passes)
KERNELS = {
    "attention-qwen1.5-4b": lambda: _attention(20, 20, 128, 4096),
    "attention-gemma2-9b": lambda: _attention(16, 8, 256, 4096, window=4096,
                                              softcap=50.0),
    "ssd-mamba2-370m": _ssd,
    "ssd-zamba2-1.2b": lambda: _ssd(h=64, n=64),
    "attention-zamba2-7b": lambda: _attention(16, 16, 224, 4096,
                                              scale=112 ** -0.5),
    "ssd-zamba2-7b": lambda: _ssd(h=56, n=64, chunk=256),
    "grouped-gemm-olmoe-1b-7b": _gemm,
}


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(kernel, direction, one_chip,
                                 no_persistent_cache):
    fwd, shapes, argnums, names = KERNELS[kernel]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if direction == "fwd":
        fn, names = fwd, names[:1]
    else:
        def fn(*a):
            out, vjp = jax.vjp(lambda *d: fwd(*d, *a[len(argnums):]),
                               *a[:len(argnums)])
            return vjp(jnp.ones_like(out))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    for name in names:
        assert name in text, f"{kernel} {direction}: no {name} kernel"


def _dense_cfg():
    # two heads of the kernel-eligible head dim 128
    return ModelConfig(arch_id="dense-128", family=Family.DENSE, n_layers=2,
                       d_model=256, n_heads=2, n_kv_heads=2, d_ff=512,
                       vocab=1024)


def _mamba2_cfg():
    return ModelConfig(arch_id="mamba2-64", family=Family.SSM, n_layers=2,
                       d_model=256, n_heads=0, n_kv_heads=0, d_ff=0,
                       vocab=1024, tie_embeddings=True,
                       ssm=SSMConfig(d_state=128, head_dim=64, expand=2,
                                     n_groups=1, chunk=128))


# (config, tp, tp_impl, kernels the compiled step must hold). GSPMD cannot
# partition a Mosaic kernel, so on a mesh the GSPMD path takes XLA for every
# "auto" kernel; the overlap executor calls the flash kernel per shard.
MESH_STEPS = {
    "dense-gspmd-tp": (_dense_cfg, 2, "gspmd", ()),
    "dense-overlap-tp": (_dense_cfg, 2, "overlap",
                         ("flash_fwd", "flash_dq", "flash_dkv")),
    "mamba2-gspmd-dp": (_mamba2_cfg, 1, "auto", ()),
}


@pytest.mark.parametrize("case", sorted(MESH_STEPS))
def test_train_step_compiles_for_v5e_mesh(case, topo, no_persistent_cache,
                                          monkeypatch):
    """A ZeRO-1 train step on a described 2x2 (data, model) v5e mesh, with
    every kernel choice left at "auto" and resolved as on a TPU host."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    make_cfg, tp, tp_impl, kernels = MESH_STEPS[case]
    cfg = make_cfg()
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    plan = ParallelPlan(tp=tp, tp_impl=tp_impl, zero_stage=1,
                        compute_dtype="bfloat16")
    model = build_model(cfg, plan, mesh, ("data",))
    step = make_train_step(model, plan, Hyper(), mesh=mesh)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = sharding.param_specs(params, cfg, plan, mesh)
    ospecs = sharding.opt_state_specs(pspecs, params, plan, mesh)
    state = jax.eval_shape(lambda p: TrainState(p, adamw_init(p)), params)
    specs = TrainState(pspecs, type(state.opt)(step=P(), mu=ospecs,
                                                nu=ospecs))
    batch = {k: jax.ShapeDtypeStruct((4, 256), jnp.int32)
             for k in ("tokens", "labels")}
    on = lambda s: NamedSharding(mesh, s)  # noqa: E731
    in_shardings = (jax.tree.map(on, specs,
                                 is_leaf=lambda x: isinstance(x, P)),
                    {k: on(P("data")) for k in batch})
    text = jax.jit(step, in_shardings=in_shardings).lower(
        state, batch).compile().as_text()
    assert ('custom_call_target="tpu_custom_call"' in text) == bool(kernels)
    for name in kernels:
        assert name in text, f"{case}: no {name} kernel"
