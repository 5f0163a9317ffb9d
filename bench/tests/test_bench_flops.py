"""The benchmark's FLOP counts agree with the dot FLOPs that perf/hlo_cost
finds in a compiled forward and backward of the trainer's loss at a small
size. The XLA paths compute the full square of every SSD chunk and of the
attention scores where the counts take only causal pairs; adding back those
masked halves, the two agree to within what hlo_cost leaves out."""

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness, reference as R
from bench.tests.tiny import tiny_cell
from repro.core import ParallelPlan
from repro.models import build_model
from repro.perf.hlo_cost import analyze_hlo
from repro.train.step import Hyper, make_loss_fn

SMALL = {"hidden_size": 256, "num_hidden_layers": 2, "state_size": 32,
         "head_dim": 32, "chunk_size": 64, "vocab_size": 512,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "attention_head_dim": 64, "intermediate_size": 1024,
         "shared_attention_every": 1}


@pytest.mark.parametrize("name", ["mamba2-370m.pretrain-2k",
                                  "zamba2-1.2b.pretrain-4k"])
def test_counts_match_compiled_dot_flops(name):
    cell = tiny_cell(name)
    cell.config.update({k: v for k, v in SMALL.items() if k in cell.config})
    c, b, s = cell.config, 2, 512
    model = build_model(harness.program_config(c),
                        ParallelPlan(remat="none", compute_dtype="float32"),
                        None, ())
    spec = cell.model.param_spec(c)
    params = jax.eval_shape(lambda k: R.init_params(spec, k),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    loss = make_loss_fn(model, Hyper())
    grad = jax.value_and_grad(
        lambda p, t, y: loss(p, {"tokens": t, "labels": y})[0])
    hlo = analyze_hlo(jax.jit(grad).lower(params, tok, tok).compile()
                      .as_text(), 1).flops

    counted = flops.train_step_flops(c, b, s)
    _, _, nh, g, n = flops.ssm_dims(c)
    q = c["chunk_size"]
    masked = c["num_hidden_layers"] * 2 * (q - 1) / 2 * (n * g + c["head_dim"] * nh)
    if "shared_attention_every" in c:
        apps = c["num_hidden_layers"] // c["shared_attention_every"]
        masked += apps * 2 * 2 * c["num_attention_heads"] \
            * c["attention_head_dim"] * (s - 1) / 2
    full = counted + 3 * b * s * masked
    assert counted < hlo
    assert abs(hlo - full) / full < 0.01, (hlo, full, counted)
