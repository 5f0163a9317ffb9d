"""Cells of BENCHMARK.json cut to a size a CPU test run can hold (the same
files and code paths, small widths and batches), and the faults the check
of a training cell must catch, planted in the trainer's step."""

import json
import time

import jax

from bench import harness
from repro.train import make_train_step

TINY_CONFIG = {"num_hidden_layers": 2, "hidden_size": 64, "state_size": 16,
               "head_dim": 16, "vocab_size": 128, "chunk_size": 32,
               "ref_chunk": 16, "ref_rows": 1, "num_attention_heads": 4,
               "num_key_value_heads": 4, "attention_head_dim": 16,
               "intermediate_size": 128, "shared_attention_every": 1,
               "ref_attn_block": 32}
TINY_TRAFFIC = {"batch": 2, "seq": 64, "distinct_batches": 8}

# Cells whose files are in bench/ but which are not in BENCHMARK.json yet
# (PERF.md, Open questions): mamba2-370m under the trainer's default fault
# tolerance with one injected fault, whose runs report recover_s; and the
# zamba2 hybrid, which has no limits yet and so no check.
HOTCKPT = "mamba2-370m.pretrain-2k.hotckpt"
HYBRID = "zamba2-1.2b.pretrain-4k"
PENDING = {HOTCKPT: ("mamba2-370m.json", "pretrain-2k.hotckpt"),
           HYBRID: ("zamba2-1.2b.12l.json", "pretrain-4k")}


def _pending(name: str) -> harness.Cell:
    bm = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    config, traffic = PENDING[name]
    cell = harness.load_cell(name, 1, harness.BENCH / "configs" / config,
                             traffic, bm)
    if name == HOTCKPT:
        cell.end_to_end.append({"name": "recover_s", "unit": "s"})
    return cell


def full_cell(name: str) -> harness.Cell:
    """The cell at its own size."""
    return _pending(name) if name in PENDING else harness.resolve(name)


def tiny_cell(name: str, compute_dtype: str = "float32") -> harness.Cell:
    """The cell at a tiny size. The trainer computes in float32 here: the
    cell's limits are set from its bf16 readings at full size, and at a
    tiny size bf16 rounding alone reads above some of them."""
    cell = full_cell(name)
    cell.config.update({k: v for k, v in TINY_CONFIG.items()
                        if k in cell.config})
    cell.config["plan"] = dict(cell.config["plan"], compute_dtype=compute_dtype)
    cell.traffic.update(TINY_TRAFFIC)
    return cell


def unchanged_state(model, plan, hyper):
    """A step that computes its metrics and returns its state unchanged."""
    real = make_train_step(model, plan, hyper)

    def step(state, batch):
        _, metrics = real(state, batch)
        return state, metrics
    return step


def half_batch(model, plan, hyper):
    """A step that leaves out half of the batch: the mean over the rest."""
    real = make_train_step(model, plan, hyper)

    def step(state, batch):
        return real(state, jax.tree.map(lambda x: x[:x.shape[0] // 2], batch))
    return step


FAULTS = [unchanged_state, half_batch]


def run(name, make_step=None):
    """One run of the tiny cell, the chip check skipped, a 1-s window."""
    return harness.run_cell(tiny_cell(name), 2 ** 31 + 3, 1.0, False,
                            time.perf_counter(), make_step=make_step)


def use_test_cache(monkeypatch, cache_dir):
    """Point the harness's compile cache at ``cache_dir`` (a fixture undoes
    it with ``use_test_cache(None, None)``)."""
    from jax.experimental.compilation_cache import compilation_cache
    import repro.launch.cache as cache

    def use():
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()

    if monkeypatch is None:
        use()
    else:
        monkeypatch.setattr(cache, "use_compile_cache", use)
