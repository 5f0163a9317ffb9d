"""Readings that the limits of a cell's check are set from.

    python3 bench/calibrate.py --workload mamba2-370m.pretrain-2k \\
        --seeds 11 12 13 ... --control-seeds 11 12 13 --witness-seeds 11

For each seed, in one process on the chip: the trainer's compiled step is
driven through the cell's first ``setup_steps`` steps from the seed's
weights and batches, as the harness drives it, and compared with the plain
float32 reference (the lower readings). For each control seed also: the
reference computed with fp8 operands in the program's place (the control)
and with half of every batch left out (a fault the check must catch); for
each witness seed, with bf16 operands (a second witness at the program's
own precision). A state
left unchanged reads 1 on ``change`` by construction and needs no run.
Prints one JSON line per reading; the benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def program_readings(cell, seed, parts):
    """The program's losses, first-gradient and change norms for ``seed``."""
    import jax  # noqa: PLC0415
    from repro.optim import adamw_init  # noqa: PLC0415
    from repro.train import TrainState  # noqa: PLC0415
    from bench import harness, reference as R  # noqa: PLC0415
    from bench.data import MarkovTokens  # noqa: PLC0415

    step_fn, init, names = parts
    c, tr = cell.config, cell.traffic
    gen = MarkovTokens(c["vocab_size"], tr["batch"], tr["seq"], seed)
    batches = [gen.batch_at(s) for s in range(tr["setup_steps"])]
    params = init(R.seed_key(seed))
    state = TrainState(params, adamw_init(params))
    del params
    losses, grad, change = [], None, None
    for i, b in enumerate(batches):
        state, m = step_fn(state, {k: jax.numpy.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
        if i == 0:
            grad = [float(x) / (1.0 - harness.B1) for x in jax.jit(
                lambda t: [jax.numpy.linalg.norm(x.ravel())
                           for x in jax.tree.leaves(t)])(state.opt.mu)]
    change = [float(x) for x in jax.jit(
        lambda a, k: [jax.numpy.linalg.norm((x - y).ravel()) for x, y in
                      zip(jax.tree.leaves(a), jax.tree.leaves(init(k)))])(
        state.params, R.seed_key(seed))]
    del state
    return batches, {"losses": losses, "grad": dict(zip(names, grad)),
                     "change": dict(zip(names, change))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--witness-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax  # noqa: PLC0415
    from repro.launch.cache import use_compile_cache  # noqa: PLC0415
    from repro.core import ParallelPlan  # noqa: PLC0415
    from repro.models import build_model  # noqa: PLC0415
    from repro.train import Hyper, make_train_step  # noqa: PLC0415
    from bench import harness, reference as R  # noqa: PLC0415

    cell = harness.resolve(args.workload)
    harness.require_chip(cell.chips)
    use_compile_cache()
    c, h = cell.config, cell.traffic["hyper"]
    plan = ParallelPlan(remat=c["plan"]["remat"],
                        compute_dtype=c["plan"]["compute_dtype"])
    model = build_model(harness.program_config(c), plan, None, ())
    step_fn = jax.jit(make_train_step(model, plan, Hyper(**h)),
                      donate_argnums=(0,))
    spec = cell.model.param_spec(c)
    init = jax.jit(lambda k: R.init_params(spec, k))
    names = harness.leaf_names(jax.eval_shape(model.init,
                                              jax.random.PRNGKey(0)))
    every = {k: 1.0 for k in ("loss", "grad", "change")}

    def emit(seed, kind, readings):
        vals = {k: v["value"] for k, v in harness.compare(
            readings, ref, every).items()}
        print(json.dumps({"cell": cell.name, "seed": seed, "kind": kind,
                          **vals, "losses": readings["losses"]}), flush=True)

    for seed in args.seeds:
        batches, prog = program_readings(cell, seed, (step_fn, init, names))
        for a in jax.live_arrays():
            a.delete()
        ref = R.train_reference(cell.model, c, h, seed, batches)
        emit(seed, "program", prog)
        kinds = []
        if seed in args.control_seeds:
            kinds += [("control_fp8", {"cast": "fp8"}),
                      ("fault_half_batch", {"rows": "half"})]
        if seed in args.witness_seeds:
            kinds += [("witness_bf16", {"cast": "bf16"})]
        for kind, kw in kinds:
            emit(seed, kind, R.train_reference(cell.model, c, h, seed,
                                               batches, **kw))
    return 0


if __name__ == "__main__":
    sys.exit(main())
