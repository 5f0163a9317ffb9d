"""The control of each cell's check comes out not correct: the plain
reference computed with fp8 operands (e4m3, gradients e5m2), one precision
below the configuration's bf16, put in the program's place. At a tiny size on
the CPU, against the cell's own limits; the chip readings at the cell's size
are in PERF.md."""

import pytest

from bench import harness, reference as R
from bench.data import MarkovTokens
from bench.tests.tiny import tiny_cell


@pytest.mark.parametrize("name", harness.cell_names())
def test_control_is_not_correct(name):
    seed = 2 ** 31 + 5
    cell = tiny_cell(name)
    c, tr = cell.config, cell.traffic
    gen = MarkovTokens(c["vocab_size"], tr["batch"], tr["seq"], seed)
    batches = [gen.batch_at(s) for s in range(tr["setup_steps"])]
    ref = R.train_reference(cell.model, c, tr["hyper"], seed, batches)
    ctl = R.train_reference(cell.model, c, tr["hyper"], seed, batches,
                            cast="fp8")
    checks = harness.compare(ctl, ref, cell.limits)
    assert any(v["value"] > v["limit"] for v in checks.values()), checks
