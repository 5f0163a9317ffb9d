#!/usr/bin/env python3
"""Smoke run of the training path on TPU chips, at published model widths.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips of one host, 2x2 mesh

One chip: trains mamba2-370m at full published size (48 layers, 368M
parameters, random weights from a seed) through ``repro.launch.train`` for a
few steps, with bf16 compute and ``auto`` kernel choice, so the SSD scan runs
as the compiled Pallas kernel forward and backward. Then every Pallas kernel
runs forward and backward at a published width against its ``kernels/ref.py``
oracle. Four chips: trains qwen1.5-4b at published widths, cut to 4 layers,
on a (data, model) = (2, 2) mesh with overlap tensor parallelism and compares
its losses with the same steps under GSPMD tensor parallelism, for three
data and init seeds.

Everything runs in this one process, which holds the chips. The script exits
non-zero, printing no result, when JAX finds no TPU or any check fails. Its
last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

# the TPU runtime logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))
SCRATCH = REPO / ".smoke"          # checkpoints of the training phase
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'  # a Pallas kernel

# mamba2-370m training phase: 8 x 2048 tokens with full remat is the batch a
# compile for a v5e chip fits in its 16 GB (selective remat at 8 x 2048 does
# not); 8 steps after the compile, at the Mamba2 paper's 370M learning rate
TRAIN_ARGS = ["--arch", "mamba2-370m", "--full", "--steps", "8",
              "--batch", "8", "--seq", "2048", "--remat", "full",
              "--lr", "1.5e-3"]

# Kernel tolerances, on max|kernel - ref| / max|ref| per output. The kernels
# take bf16 inputs and write bf16 outputs (unit roundoff 2^-9 ~ 2e-3), and
# run under the default matmul precision, which may feed the MXU single-pass
# bf16 operands; the oracle runs in fp32 at "highest". A wrong mask, scale,
# index map or tile skip moves outputs by O(1) of their range, far above
# these bounds. Gradients sum products over the whole sequence (or capacity),
# so their bound is twice the forward's.
FWD_TOL = 1e-2
BWD_TOL = 2e-2
# SSD outputs are fp32, but the decay products exp(cumsum(dt*A)) amplify
# rounding of dt*A across a 128-step chunk: same bounds.

# Published widths. Flash attention: qwen1.5-4b (20 heads, head dim 128) and
# gemma2-9b (GQA 16/8, head dim 256, window 4096, softcap 50), at S = 4096:
# (name, q heads, kv heads, head dim, S, window, softcap)
ATTENTION_CASES = [
    ("attention-qwen1.5-4b", 20, 20, 128, 4096, 0, 0.0),
    ("attention-gemma2-9b", 16, 8, 256, 4096, 4096, 50.0),
]
# SSD: mamba2-370m (32 heads, P=64, one group of N=128, chunk 128), L = 4096:
# (name, L, heads, P, N, chunk)
SSD_CASE = ("ssd-mamba2-370m", 4096, 32, 64, 128, 128)
# grouped GEMM: olmoe-1b-7b experts (d=2048, f=1024), 8 of its 64 experts
# with uneven loads over a 512-row capacity (empty and full experts,
# tile-straddling sizes): (name, capacity, d, f, group sizes)
GEMM_CASE = ("grouped-gemm-olmoe-1b-7b", 512, 2048, 1024,
             [512, 0, 1, 127, 128, 129, 300, 511])

# Four chips: per-step losses of overlap TP vs GSPMD TP, for each data and
# init seed. Both run bf16 compute over the same data and init; they sum the
# same products in another order (ring partial sums vs one all-reduce) and
# attention runs as the flash kernel on one side and as XLA on the other.
# Sound runs on four v5e chips read 5.2e-5, 1.7e-4 and 6.2e-5 (seeds 0, 1,
# 2); the bound sits about 6x above the largest. A fault in the forward
# pass moves the loss far more. A gradient off by a constant factor in one
# leaf may not: AdamW's update is nearly blind to gradient scale, so such
# faults are left to the CPU suite, which compares overlap and GSPMD
# gradients leaf by leaf (tests/test_tensor_parallel.py).
TP_LOSS_RTOL = 1e-3
TP_SEEDS = (0, 1, 2)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def require_tpu(count: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        fail(f"no TPU found: JAX reports platform {d.platform!r}")
    if len(devs) < count:
        fail(f"needs {count} TPU chips, JAX sees {len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def peak_bytes(device) -> int:
    """Peak device memory of this process so far (never reset)."""
    return device.memory_stats()["peak_bytes_in_use"]


def bytes_in_use(device) -> int:
    return device.memory_stats()["bytes_in_use"]


def memory_analysis(compiled) -> str:
    """The compiler's per-device byte counts for one executable."""
    m = compiled.memory_analysis()
    return (f"argument_bytes={m.argument_size_in_bytes} "
            f"output_bytes={m.output_size_in_bytes} "
            f"alias_bytes={m.alias_size_in_bytes} "
            f"temp_bytes={m.temp_size_in_bytes} "
            f"generated_code_bytes={m.generated_code_size_in_bytes}")


# ---------------------------------------------------------------------------
# one chip: the main training path


def train_phase():
    from repro.launch import train

    shutil.rmtree(SCRATCH, ignore_errors=True)
    try:
        res = train.main(TRAIN_ARGS + ["--ckpt-dir", str(SCRATCH / "ckpt")])
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    rep = res.report
    print(f"train: compile_seconds={res.compile_seconds}", flush=True)
    # The checkpoint tiers' own timings, from the trainer's flight recorder:
    # the RAM tier snapshots after every step (its step-s event follows step
    # s - 1), the disk tier saves at step 0. wall_seconds runs from the end
    # of one RAM snapshot to the end of the next: the batch, the device step
    # (seconds=, ending in block_until_ready) and the RAM snapshot.
    saves = [e for e in res.flight.events if e["kind"] == "ckpt.persist"]
    ram = {e["step"]: e for e in saves if e["tier"] == "memory"}
    for e in saves:
        if e["tier"] == "disk":
            print(f"train: disk checkpoint step {e['step']} snapshot_seconds="
                  f"{e['snapshot_seconds']} persist_seconds={e['seconds']}")
    for i, (t, loss) in enumerate(zip(rep.step_seconds, rep.losses)):
        before, after = ram.get(i), ram.get(i + 1)
        extra = "" if before is None or after is None else (
            f" ram_snapshot_seconds={after['seconds']}"
            f" wall_seconds={after['t'] - before['t']}")
        print(f"train: step {i} seconds={t} loss={loss}{extra}")
    dev = jax.devices()[0]
    print(f"train: peak_bytes_in_use={peak_bytes(dev)}")
    print(f"train: memory_stats={dev.memory_stats()}")
    print(f"train: compiled step {memory_analysis(res.compiled)}", flush=True)
    text = res.compiled.as_text()
    n_kernels = text.count(MOSAIC_CALL)
    print(f"train: tpu_custom_call ops in the compiled step={n_kernels} "
          f"(ssd_fwd {'ssd_fwd' in text}, ssd_bwd {'ssd_bwd' in text})",
          flush=True)

    if rep.anomalies:
        fail(f"the run recorded anomalies: {rep.anomalies}")
    if rep.restores:
        fail(f"the run restored {rep.restores} times")
    if len(rep.losses) < 5 or not all(map(math.isfinite, rep.losses)):
        fail(f"expected >= 5 finite losses, got {rep.losses}")
    if not rep.losses[-1] < rep.losses[0]:
        fail(f"loss did not fall: {rep.losses[0]} -> {rep.losses[-1]}")
    if "ssd_fwd" not in text or "ssd_bwd" not in text:
        fail("the compiled step holds no Pallas SSD kernel")


# ---------------------------------------------------------------------------
# one chip: every kernel against its oracle


def _rel_err(got, ref) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    ref = np.asarray(jnp.asarray(ref, jnp.float32))
    return float(np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30))


def _check(name, fn, ref_fn, args, cot_seed):
    """Forward and VJP of ``fn`` (the kernel, bf16 in) against ``ref_fn``
    (the oracle, fp32 in, "highest" precision) on the same values."""
    out, vjp = jax.vjp(fn, *args)
    ref_args = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        ref_out, ref_vjp = jax.vjp(ref_fn, *ref_args)
    leaves, tree = jax.tree.flatten(ref_out)
    keys = jax.random.split(jax.random.PRNGKey(cot_seed), len(leaves))
    cots = [jax.random.normal(k, l.shape, jnp.float32)
            for k, l in zip(keys, leaves)]
    cot = jax.tree.unflatten(tree, cots)
    grads = vjp(jax.tree.map(lambda c, o: c.astype(o.dtype), cot, out))
    with jax.default_matmul_precision("highest"):
        ref_grads = ref_vjp(cot)
    fwd = max(_rel_err(a, b) for a, b in
              zip(jax.tree.leaves(out), jax.tree.leaves(ref_out)))
    bwd = max(_rel_err(a, b) for a, b in zip(grads, ref_grads))
    print(f"kernel {name}: fwd max_rel_err={fwd} (tol {FWD_TOL}), "
          f"bwd max_rel_err={bwd} (tol {BWD_TOL})", flush=True)
    return fwd <= FWD_TOL and bwd <= BWD_TOL


def kernel_phase():
    from repro.kernels import ref
    from repro.kernels.dispatch import dispatch_ssd_scan
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.grouped_gemm import expert_gemm

    rng = np.random.default_rng(0)

    def normal(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    ok = True
    for name, hq, hkv, hd, s, window, cap in ATTENTION_CASES:
        args = [normal((1, hq, s, hd)), normal((1, hkv, s, hd)),
                normal((1, hkv, s, hd))]
        ok &= _check(
            name,
            lambda q, k, v, w=window, c=cap: flash_attention(
                q, k, v, causal=True, window=w, softcap=c),
            lambda q, k, v, w=window, c=cap: ref.flash_attention_ref(
                q, k, v, causal=True, window=w, softcap=c),
            args, cot_seed=1)

    # model layout through the dispatcher; dt and A drawn from the model's
    # init ranges (dt in [1e-3, 0.1], A = -[1, 16])
    name, l, h, p, n, chunk = SSD_CASE
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (1, l, h)), jnp.float32)
    a = jnp.asarray(-rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    args = [normal((1, l, h, p)), dt, a, normal((1, l, 1, n)),
            normal((1, l, 1, n))]
    ok &= _check(
        name,
        lambda x, dt, A, B, C: dispatch_ssd_scan(x, dt, A, B, C, chunk=chunk,
                                                 impl="pallas"),
        lambda x, dt, A, B, C: ref.ssd_chunk_ref(x, dt, A, B, C, chunk),
        args, cot_seed=2)

    name, c, d, f, sizes = GEMM_CASE
    gs = jnp.asarray(sizes, jnp.int32)
    args = [normal((len(sizes), c, d)), normal((len(sizes), d, f)) * 0.02]
    ok &= _check(
        name,
        lambda x, w: expert_gemm(x, w, gs),
        lambda x, w: ref.expert_gemm_ref(x, w, gs),
        args, cot_seed=3)
    if not ok:
        fail("a kernel is outside its tolerance")


# ---------------------------------------------------------------------------
# four chips: overlap TP against GSPMD TP


def four_chip_phase(steps: int = 6, batch: int = 8, seq: int = 2048):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import InputShape, ParallelPlan, get_config
    from repro.data import SyntheticDataset
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train import Hyper, init_train_state, make_train_step

    cfg = dataclasses.replace(get_config("qwen1.5-4b"), n_layers=4)
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    chips = list(mesh.devices.flat)
    on_data = NamedSharding(mesh, P("data", None))
    shape = InputShape("four-chip", seq, batch, "train")
    batches = {}
    for seed in TP_SEEDS:
        ds = SyntheticDataset(cfg, shape, seed=seed)
        batches[seed] = [jax.device_put({k: jnp.asarray(v) for k, v in
                                         ds.batch(s).items()}, on_data)
                         for s in range(steps)]
    hyper = Hyper(peak_lr=3e-4, warmup_steps=2, total_steps=steps)
    losses = {}
    for impl in ("gspmd", "overlap"):
        # every kernel choice left at "auto": on the mesh the GSPMD path
        # takes XLA attention (GSPMD cannot partition a Mosaic kernel), the
        # overlap executor calls the flash kernel per shard
        plan = ParallelPlan(remat="full", compute_dtype="bfloat16", tp=2,
                            zero_stage=1, tp_impl=impl)
        model = build_model(cfg, plan, mesh, ("data",))
        step = jax.jit(make_train_step(model, plan, hyper, mesh=mesh),
                       donate_argnums=(0,))
        for seed in TP_SEEDS:
            state = init_train_state(model, jax.random.PRNGKey(seed),
                                     mesh=mesh, plan=plan)
            if seed == TP_SEEDS[0]:
                # the parameters are drawn whole on chip 0, then placed
                print(f"four-chip {impl}: after init bytes_in_use per chip="
                      f"{[bytes_in_use(d) for d in chips]} "
                      f"peak_bytes_in_use per chip="
                      f"{[peak_bytes(d) for d in chips]}", flush=True)
                t0 = time.perf_counter()
                compiled = step.lower(state, batches[seed][0]).compile()
                print(f"four-chip {impl}: compile_seconds="
                      f"{time.perf_counter() - t0} per chip "
                      f"{memory_analysis(compiled)} tpu_custom_call ops="
                      f"{compiled.as_text().count(MOSAIC_CALL)}",
                      flush=True)
            losses[impl, seed] = []
            for s, b in enumerate(batches[seed]):
                t0 = time.perf_counter()
                state, m = jax.block_until_ready(step(state, b))
                dt = time.perf_counter() - t0
                losses[impl, seed].append(float(m["loss"]))
                print(f"four-chip {impl} seed {seed}: step {s} seconds={dt} "
                      f"loss={losses[impl, seed][-1]}", flush=True)
            del state
        print(f"four-chip {impl}: peak_bytes_in_use per chip="
              f"{[peak_bytes(d) for d in chips]}", flush=True)

    gaps = []
    for seed in TP_SEEDS:
        g = np.asarray(losses["gspmd", seed])
        o = np.asarray(losses["overlap", seed])
        if not (np.isfinite(g).all() and np.isfinite(o).all()):
            fail(f"non-finite losses (seed {seed})")
        if not (g[-1] < g[0] and o[-1] < o[0]):
            fail(f"loss did not fall (seed {seed}): gspmd {g.tolist()} "
                 f"overlap {o.tolist()}")
        gaps.append(float(np.max(np.abs(o - g) / np.abs(g))))
        print(f"four-chip seed {seed}: max per-step |overlap - gspmd| / gspmd"
              f" = {gaps[-1]} (tol {TP_LOSS_RTOL})", flush=True)
    if max(gaps) > TP_LOSS_RTOL:
        fail("overlap TP does not match GSPMD TP")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 2x2 mesh and what "
                         "it is compared with")
    args = ap.parse_args()
    device = require_tpu(4 if args.four_chips else 1)

    from repro.launch.cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.four_chips:
        four_chip_phase()
    else:
        train_phase()
        kernel_phase()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
