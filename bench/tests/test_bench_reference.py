"""The plain reference computes what the trainer's model computes: at a tiny
size in float32, on weights drawn from a seed, the two forward passes agree
to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference as R
from bench.tests.tiny import tiny_cell
from repro.core import ParallelPlan
from repro.models import build_model


@pytest.mark.parametrize("name", ["mamba2-370m.pretrain-2k",
                                  "zamba2-1.2b.pretrain-4k"])
def test_reference_forward_matches_trainer(name):
    cell = tiny_cell(name)
    c = cell.config
    model = build_model(harness.program_config(c),
                        ParallelPlan(remat="none", compute_dtype="float32"),
                        None, ())
    params = R.init_params(cell.model.param_spec(c), R.seed_key(2 ** 31 + 9))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert harness.leaf_names(params) == harness.leaf_names(want)
    tok = jnp.asarray(np.random.default_rng(0).integers(
        0, c["vocab_size"], (2, 64)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.forward(params, {"tokens": tok})
        ref = cell.model.forward(params, tok, c, R.CASTS["f32"])
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(got - ref))) < 1e-5 * scale


def test_seed_key_keeps_wide_seeds_apart():
    a, b = R.seed_key(5), R.seed_key(2 ** 32 + 5)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
