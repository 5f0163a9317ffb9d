"""Whole runs of mamba2-370m on the CPU at a tiny size, the chip check
skipped: sound, they come out correct; with the timed path broken
underneath, they do not. The RAM-tier traffic, with its injected fault,
runs too: its recovery is timed and its restore is checked."""

import jax
import jax.numpy as jnp
import pytest

from bench.tests.tiny import FAULTS, HOTCKPT, run, tiny_cell, use_test_cache

CELLS = ["mamba2-370m.pretrain-2k", HOTCKPT]


@pytest.fixture(autouse=True)
def _cache(monkeypatch, tmp_path_factory):
    # one compile cache for the runs of this worker: each compiles once
    use_test_cache(monkeypatch, str(tmp_path_factory.getbasetemp() / "jax"))
    yield
    use_test_cache(None, None)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny_cell(name).end_to_end}


@pytest.mark.parametrize("fault", FAULTS)
def test_broken_step_is_not_correct(fault):
    out = run("mamba2-370m.pretrain-2k", make_step=fault)
    assert not out["correct"], out["checks"]


def test_altered_restore_is_not_correct(monkeypatch):
    """The RAM tier hands back a state that differs from what it saved."""
    from repro.checkpoint import memory
    real = memory.MemoryCheckpointTier.restore

    def altered(self, tree_like, *a, **kw):
        step, tree = real(self, tree_like, *a, **kw)
        leaves, treedef = jax.tree.flatten(tree)
        leaves[0] = leaves[0] + jnp.asarray(1e-3, leaves[0].dtype)
        return step, jax.tree.unflatten(treedef, leaves)

    monkeypatch.setattr(memory.MemoryCheckpointTier, "restore", altered)
    out = run(HOTCKPT)
    assert not out["correct"] and out["checks"]["restore"]["value"] > 0
