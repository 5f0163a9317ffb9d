"""The benchmark's copy of the synthetic token generator reproduces the
trainer's batches for a seed, so the yardstick starts from the trainer's own
traffic."""

import numpy as np
import pytest

from bench.data import MarkovTokens
from repro.core import get_config
from repro.core.config import InputShape
from repro.data import SyntheticDataset


@pytest.mark.parametrize("arch,batch,seq,seed", [
    ("mamba2-370m", 8, 2048, 7),
    ("zamba2-1.2b", 2, 4096, 2 ** 31 + 11),
])
def test_copy_matches_trainer_generator(arch, batch, seq, seed):
    cfg = get_config(arch)
    ds = SyntheticDataset(cfg, InputShape("bench", seq, batch, "train"),
                          seed=seed)
    gen = MarkovTokens(cfg.vocab, batch, seq, seed)
    for step in (0, 1, 37):
        want, got = ds.batch(step), gen.batch_at(step)
        np.testing.assert_array_equal(got["tokens"], want["tokens"])
        np.testing.assert_array_equal(got["labels"], want["labels"])
    assert not np.array_equal(gen.batch_at(0)["tokens"],
                              gen.batch_at(1)["tokens"])
