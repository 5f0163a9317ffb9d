"""Trip-count-aware HLO cost walker: validated against analytic FLOPs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.perf.hlo_cost import analyze_hlo
from repro.perf.roofline import model_flops_for
from repro.core import ModelConfig, ParallelPlan, Family, InputShape
from repro.models import build_model
from repro.train import TrainState, make_train_step
from repro.optim import adamw_init


def test_scan_trip_count_multiplied():
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((256,), jnp.float32)

    def single(w, x):
        return w @ x

    def scanned(w, x):
        def body(c, _):
            return w @ c, None
        out, _ = jax.lax.scan(body, x, None, length=12)
        return out

    f1 = analyze_hlo(jax.jit(single).lower(w, x).compile().as_text(), 1).flops
    f12 = analyze_hlo(jax.jit(scanned).lower(w, x).compile().as_text(), 1).flops
    assert f1 == 2 * 256 * 256
    assert f12 == 12 * f1


def test_dot_flops_with_batch_dims():
    a = jax.ShapeDtypeStruct((4, 64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    comp = jax.jit(lambda a, b: jnp.einsum("bij,bjk->bik", a, b)).lower(a, b).compile()
    flops = analyze_hlo(comp.as_text(), 1).flops
    assert flops == 2 * 4 * 64 * 16 * 32


def test_train_step_flops_near_6nd():
    """hlo_flops must land between 6ND (no remat would be ~6ND + attn/head
    overhead) and ~10ND (full remat re-runs the forward)."""
    cfg = ModelConfig("t", Family.DENSE, n_layers=4, d_model=256, n_heads=4,
                      n_kv_heads=4, d_ff=1024, vocab=1024)
    plan = ParallelPlan(remat="full", compute_dtype="float32")
    model = build_model(cfg, plan)
    step = make_train_step(model, plan)
    b, s = 4, 128
    state = jax.eval_shape(
        lambda r: TrainState(model.init(r), adamw_init(model.init(r))),
        jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    comp = jax.jit(step).lower(state, batch).compile()
    flops = analyze_hlo(comp.as_text(), 1).flops
    nd6 = 6 * cfg.param_count() * b * s
    assert 0.9 * nd6 < flops < 1.8 * nd6, flops / nd6


def test_collectives_parsed_with_group_size(multidevice):
    multidevice("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.perf.hlo_cost import analyze_hlo
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))

def f(w, x):
    return (x @ w).sum()

comp = jax.jit(jax.grad(f), in_shardings=(
    NamedSharding(mesh, P(None, "model")),
    NamedSharding(mesh, P("data", None)))).lower(
    jax.ShapeDtypeStruct((64, 128), jnp.float32),
    jax.ShapeDtypeStruct((32, 64), jnp.float32)).compile()
a = analyze_hlo(comp.as_text(), 8)
assert a.collective_counts["all-reduce"] >= 1, a.collective_counts
assert a.collective_link_bytes > 0
print("collectives:", {k: v for k, v in a.collective_counts.items() if v})
""")


def test_all_to_all_pricing_formula():
    """all-to-all link bytes follow the ring model — (n-1)/n of the result
    bytes, with the async ``-start`` form halved (its tuple result carries
    operand + destination buffers) and the ``-done`` marker free."""
    txt = """
HloModule m

ENTRY %main (p0: f32[4,64]) -> f32[4,64] {
  %p0 = f32[4,64]{1,0} parameter(0)
  %a2a = f32[4,64]{1,0} all-to-all(f32[4,64]{1,0} %p0), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %a2as = (f32[4,64]{1,0}, f32[4,64]{1,0}) all-to-all-start(f32[4,64]{1,0} %a2a), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  ROOT %a2ad = f32[4,64]{1,0} all-to-all-done((f32[4,64]{1,0}, f32[4,64]{1,0}) %a2as)
}
"""
    a = analyze_hlo(txt, 8)
    # f32[4,64] = 1024 B in groups of 4 -> 3/4 * 1024 = 768 per exchange;
    # the -start tuple (2048 B) halves back to one 1024 B payload
    assert a.collective_counts["all-to-all"] == 2, a.collective_counts
    assert a.collective_bytes_by_kind["all-to-all"] == 768.0 * 2
    assert a.collective_link_bytes == 768.0 * 2


def test_all_to_all_priced_from_lowered(multidevice):
    """The EP dispatch exchange as XLA actually lowers it (variadic tuple
    all-to-all under shard_map) is recognized and priced at (n-1)/n of the
    tuple total."""
    multidevice("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.compat import shard_map
from repro.perf.hlo_cost import analyze_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("model",))

def body(x):
    return jax.lax.all_to_all(x, "model", split_axis=0, concat_axis=0,
                              tiled=False)

f = shard_map(body, mesh=mesh, in_specs=P(None, "model"),
              out_specs=P(None, "model"))
x = jax.ShapeDtypeStruct((8, 64, 32), jnp.float32)
a = analyze_hlo(jax.jit(f).lower(x).compile().as_text(), 8)
assert a.collective_counts["all-to-all"] == 1, a.collective_counts
# 8 pieces of f32[1,8,32] (1024 B each) -> 7/8 * 8192 = 7168 link bytes
assert a.collective_bytes_by_kind["all-to-all"] == 7.0 / 8.0 * 8 * 1024, \\
    a.collective_bytes_by_kind
print("a2a priced:", a.collective_bytes_by_kind["all-to-all"])
""")


def test_model_flops_for_shapes():
    cfg = ModelConfig("t", Family.DENSE, n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=4, d_ff=128, vocab=256)
    n = cfg.param_count()
    train = model_flops_for(cfg, InputShape("t", 128, 4, "train"))
    prefill = model_flops_for(cfg, InputShape("p", 128, 4, "prefill"))
    decode = model_flops_for(cfg, InputShape("d", 128, 4, "decode"))
    assert train == 6 * n * 512
    assert prefill == 2 * n * 512
    assert decode == 2 * n * 4
