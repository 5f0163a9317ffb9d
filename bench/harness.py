"""One run of one cell: set up the trainer's loop, measure a window, check
what the window's loop computed against the plain reference, report.

The entry the window drives is the trainer's own loop,
``repro.ft.run_with_recovery``, wired as ``repro.launch.train`` wires it
(model, train step, disk checkpoints, RAM tier, monitor, flight recorder,
straggler timer, recovery policy). The harness hands it two callables of its
own, ``get_batch`` and the step, which carry its spans and decide the
window: set-up runs the step-0 save and the first ``setup_steps`` steps,
the window runs from there to the end of the first whole step after
``seconds``, and the loop is then stopped. A cell whose traffic names a
fault poisons the first step after the window and times its recovery.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json`` with its reference
``<config>.py``) and traffic (``bench/traffic/<traffic>.json``); the limits
of its check are ``bench/limits/<cell>.json``; each per-layer metric is read
by ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
WORK = BENCH / ".work"          # checkpoints and traces of one run
B1 = 0.9                        # AdamW's first-moment decay in the trainer


class StopLoop(Exception):
    """Raised from a harness callable to end the trainer's loop."""


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# finding a cell's files

def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    model: Any                    # the configuration's reference module
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def cell_names(root: Path = ROOT) -> List[str]:
    bm = json.loads((root / "BENCHMARK.json").read_text())
    return [w["name"] for w in bm["workloads"]]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of BENCHMARK.json."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[w["config"]]
    return load_cell(name, int(w["chips"]), root / conf["file"], w["traffic"],
                     bm, root)


def load_cell(name: str, chips: int, config_file: Path, traffic: str,
              bm: Dict[str, Any], root: Path = ROOT) -> Cell:
    """A cell from its files: the configuration file (and its reference
    beside it), the traffic mix by name, the limits by the cell's name
    (none where the file is not there yet); its metrics as ``bm``
    (BENCHMARK.json) lists them for ``name``."""
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    limits = root / "bench" / "limits" / f"{name}.json"
    return Cell(
        name, chips, json.loads(config_file.read_text()),
        load_module(config_file.with_suffix(".py")),
        json.loads((root / "bench" / "traffic" / f"{traffic}.json").read_text()),
        json.loads(limits.read_text()) if limits.exists() else {},
        e2e, per_layer)


def require_chip(chips: int):
    """The devices the run uses; raises NoChip without a TPU or with fewer
    chips than the cell asks for."""
    import jax  # noqa: PLC0415
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# the program under test

def program_config(c: Dict[str, Any]):
    """The trainer's ModelConfig of ``c["program_arch"]`` with every size
    the configuration file states, so the file is what runs."""
    from repro.core import get_config  # noqa: PLC0415
    base = get_config(c["program_arch"])
    ssm = dataclasses.replace(
        base.ssm, d_state=c["state_size"], head_dim=c["head_dim"],
        expand=c["expand"], n_groups=c["n_groups"], d_conv=c["conv_kernel"],
        chunk=c["chunk_size"])
    kw = dict(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
              vocab=c["vocab_size"], tie_embeddings=c["tie_word_embeddings"],
              rms_eps=c["rms_norm_eps"], ssm=ssm)
    if c.get("shared_attention_every"):
        kw.update(n_heads=c["num_attention_heads"],
                  n_kv_heads=c["num_key_value_heads"],
                  head_dim=c["attention_head_dim"],
                  d_ff=c["intermediate_size"],
                  shared_attn_every=c["shared_attention_every"],
                  rope_theta=c["rope_theta"])
    return dataclasses.replace(base, **kw)


def leaf_names(tree) -> List[str]:
    import jax  # noqa: PLC0415
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------------------
# the run

@dataclasses.dataclass
class RunData:
    """What one run saw; the per-layer metric readers take it."""
    cell: Cell
    setup_s: float = 0.0
    t_start: float = 0.0
    t_end: float = 0.0
    first_step: int = 0
    end_step: int = 0
    fetch_calls: List = dataclasses.field(default_factory=list)  # (step, t)
    step_spans: List = dataclasses.field(default_factory=list)   # (step, t0, t1)
    flight: List[Dict] = dataclasses.field(default_factory=list)
    trace: Optional[Dict] = None
    device_kind: str = ""
    peaks: Optional[Dict[str, float]] = None

    def window_steps(self):
        return [(s, t0, t1) for s, t0, t1 in self.step_spans
                if self.t_start <= t0 and t1 <= self.t_end]


def _peaks(kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, devices=None,
             make_step: Optional[Callable] = None) -> Dict[str, Any]:
    """One run. ``devices`` is what :func:`require_chip` returned (None runs
    on whatever JAX has, for the tests); ``make_step`` replaces the
    trainer's step factory, for the tests that break the timed path.
    Checkpoints and traces live under ``bench/.work`` and are removed when
    the run ends, however it ends."""
    import jax  # noqa: PLC0415
    compiles: List[str] = []

    def listener(event, duration, **kw):
        if "backend_compile" in event:
            compiles.append(event)

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir(parents=True)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        return _run(cell, seed, seconds, trace, t_process, devices,
                    make_step, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        shutil.rmtree(WORK, ignore_errors=True)


def _run(cell, seed, seconds, trace, t_process, devices, make_step,
         compiles) -> Dict[str, Any]:
    import jax  # noqa: PLC0415
    import jax.numpy as jnp  # noqa: PLC0415
    from repro.checkpoint import CheckpointManager, MemoryCheckpointTier  # noqa: PLC0415
    from repro.core import ParallelPlan, RecoveryPolicy  # noqa: PLC0415
    from repro.ft import (FlightRecorder, Monitor, StragglerTimer,  # noqa: PLC0415
                          run_with_recovery)
    from repro.ft.inject import FaultSpec, make_injector  # noqa: PLC0415
    from repro.launch.cache import use_compile_cache  # noqa: PLC0415
    from repro.models import build_model  # noqa: PLC0415
    from repro.optim import adamw_init  # noqa: PLC0415
    from repro.train import Hyper, TrainState, make_train_step  # noqa: PLC0415

    from bench import reference as R  # noqa: PLC0415
    from bench.data import MarkovTokens  # noqa: PLC0415

    if not set(cell.limits) & {"loss", "grad", "change"}:
        raise ValueError(f"no limits for the check of {cell.name}: "
                         f"bench/limits/{cell.name}.json")
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    c, tr = cell.config, cell.traffic
    devs = devices or jax.devices()[:cell.chips]
    run = RunData(cell, device_kind=devs[0].device_kind)
    if trace:
        run.peaks = _peaks(run.device_kind)

    # -- the trainer's pieces, wired as launch/train.py wires them ---------
    cfg = program_config(c)
    plan = ParallelPlan(remat=c["plan"]["remat"],
                        compute_dtype=c["plan"]["compute_dtype"])
    model = build_model(cfg, plan, None, ())
    h = tr["hyper"]
    hyper = Hyper(peak_lr=h["peak_lr"], warmup_steps=h["warmup_steps"],
                  total_steps=h["total_steps"], weight_decay=h["weight_decay"],
                  grad_clip=h["grad_clip"], z_loss=h["z_loss"])
    spec = cell.model.param_spec(c)
    init = jax.jit(lambda k: R.init_params(spec, k))
    params = init(R.seed_key(seed))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if (leaf_names(params) != leaf_names(want) or
            [x.shape for x in jax.tree.leaves(params)]
            != [x.shape for x in jax.tree.leaves(want)]):
        raise ValueError("the configuration's parameter spec differs from "
                         "the trainer's parameter tree")
    state = TrainState(params, adamw_init(params))
    del params
    step_fn = jax.jit((make_step or make_train_step)(model, plan, hyper),
                      donate_argnums=(0,))
    flight = FlightRecorder(maxlen=256, path=str(WORK / "ckpt" / "flight.json"))
    ckpt = CheckpointManager(str(WORK / "ckpt"), keep=tr["ckpt_keep"],
                             flight=flight)
    monitor = Monitor(flight=flight)
    policy = RecoveryPolicy(ckpt_memory_keep=tr["mem_keep"],
                            peer_redundancy=tr["peer_redundancy"])
    mem = None
    if policy.ckpt_memory_keep > 0:
        mem = MemoryCheckpointTier(keep=policy.ckpt_memory_keep,
                                   peer_redundancy=policy.peer_redundancy,
                                   groups=max(2, len(devs)), flight=flight)
    rescue = jax.jit(make_train_step(
        model, plan, hyper._replace(peak_lr=h["peak_lr"]
                                    * policy.rescue_lr_scale)))
    straggler = StragglerTimer(cfg=cfg, plan=plan, policy=policy,
                               flight=flight)

    # -- traffic: every batch made from the seed before the loop -----------
    gen = MarkovTokens(c["vocab_size"], tr["batch"], tr["seq"], seed)
    pool = [gen.batch_at(s) for s in range(tr["distinct_batches"])]
    tokens_per_step = tr["batch"] * tr["seq"]
    n_setup = tr["setup_steps"]

    # -- the harness's callables --------------------------------------------
    st = {"phase": "setup", "step": 0, "compiles_before": 0,
          "window_compiles": 0, "fault_at": None, "poisoned_end": None,
          "replay_end": None, "clean_sum": None, "restored_sum": None,
          "replay_loss": None, "attempted": 0, "losses": {}, "grad": None,
          "change": None}
    checksum = jax.jit(lambda t: [(jnp.sum(x.astype(jnp.float32)),
                                   jnp.sum(jnp.square(x.astype(jnp.float32))))
                                  for x in jax.tree.leaves(t)])
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x)))
                               for x in jax.tree.leaves(t)])
    diff_norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(x - y)))
                                       for x, y in zip(jax.tree.leaves(a),
                                                       jax.tree.leaves(b))])
    names = leaf_names(state.params)
    has_fault = bool(tr["fault"])

    def clock(step: int, now: float) -> None:
        """Open the window at the first step after set-up; close it at the
        start of the first step after ``seconds`` (that is, at the end of
        the step before it, saves included)."""
        if st["phase"] == "setup" and step == n_setup:
            if trace:
                jax.profiler.start_trace(
                    str(WORK / "trace"),
                    profiler_options=_profile_options(jax))
            st["compiles_before"] = len(compiles)
            run.t_start = time.perf_counter()
            run.setup_s = run.t_start - t_process
            run.first_step = step
            st["phase"] = "window"
        elif st["phase"] == "window" and now - run.t_start >= seconds:
            run.t_end, run.end_step = now, step
            st["phase"] = "after"
            st["window_compiles"] = len(compiles) - st["compiles_before"]
            if trace:
                jax.profiler.stop_trace()
            if not has_fault:
                raise StopLoop
            st["fault_at"] = step

    def get_batch(step: int):
        now = time.perf_counter()
        if not has_fault:
            clock(step, now)
        run.fetch_calls.append((step, now))
        st["step"] = step
        with jax.profiler.TraceAnnotation("bench.fetch"):
            return {k: jnp.asarray(v)
                    for k, v in pool[step % len(pool)].items()}

    def fault_injector(step: int, state):
        """Called by the loop first in every step: keeps the window's clock
        and poisons the first step after the window, once."""
        clock(step, time.perf_counter())
        if st["fault_at"] != step:
            return state
        with jax.profiler.TraceAnnotation("bench.inject"):
            sums = [(float(a), float(b)) for a, b in checksum(state)]
            if st["clean_sum"] is not None:      # the replay, after restore
                st["restored_sum"] = sums
                return state
            st["clean_sum"] = sums
            spec = FaultSpec(tr["fault"]["point"], tr["fault"]["kind"],
                             step=step, seed=seed % (2 ** 31))
            return make_injector([spec])(step, state)

    def step(state, batch):
        i = st["step"]
        if st["phase"] == "window":
            st["attempted"] += 1
        with jax.profiler.StepTraceAnnotation("train", step_num=i), \
                jax.profiler.TraceAnnotation("bench.step"):
            t0 = time.perf_counter()
            out = jax.block_until_ready(step_fn(state, batch))
            t1 = time.perf_counter()
        run.step_spans.append((i, t0, t1))
        new_state, metrics = out
        if st["phase"] == "setup" and i < n_setup:
            st["losses"][i] = float(metrics["loss"])
            if i == 0:
                st["grad"] = [float(x) / (1.0 - B1)
                              for x in norms(new_state.opt.mu)]
            if i == n_setup - 1:
                st["change"] = [float(x) for x in diff_norms(
                    new_state.params, init(R.seed_key(seed)))]
        if st["phase"] == "after":
            if st["poisoned_end"] is None:
                st["poisoned_end"] = t1
            else:
                st["replay_end"] = t1
                st["replay_loss"] = float(metrics["loss"])
                raise StopLoop
        return out

    # -- set-up, window, fault ---------------------------------------------
    try:
        run_with_recovery(state, step, get_batch, 10 ** 9, ckpt, monitor,
                          ckpt_every=tr["ckpt_every"], plan=plan, mesh=None,
                          policy=policy, rescue_step=rescue,
                          fault_injector=fault_injector if has_fault else None,
                          mem_ckpt=mem, mem_every=tr["mem_every"],
                          flight=flight, straggler=straggler)
        raise RuntimeError("the trainer's loop ended before the window did")
    except StopLoop:
        pass
    finally:
        ckpt.wait()
    run.flight = list(flight.events)
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)

    result: Dict[str, Any] = {}
    accepted = run.end_step - run.first_step
    if trace:
        from bench import trace as T  # noqa: PLC0415
        run.trace = T.load(str(WORK / "trace"))
        vals = {}
        for m in cell.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                vals[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = vals
        result["breakdown"] = {"device_ops": T.top_ops(run.trace),
                               "idle_gaps": T.idle_gaps(run.trace)}
        busy = T.busy_seconds(run.trace)
    else:
        e2e = {"tokens_per_s": accepted * tokens_per_step
               / (run.t_end - run.t_start),
               "setup_s": run.setup_s}
        if st["replay_end"] is not None:
            e2e["recover_s"] = st["replay_end"] - st["poisoned_end"]
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}

    # -- free the program's state, then the reference -------------------------
    del state, step_fn, rescue, mem, ckpt, monitor, straggler
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    ref = R.train_reference(cell.model, c, h, seed, pool[:n_setup])
    prog = {"losses": [st["losses"][i] for i in range(n_setup)],
            "grad": dict(zip(names, st["grad"])),
            "change": dict(zip(names, st["change"]))}
    checks = compare(prog, ref, cell.limits)
    print(f"[bench] change counts {len(counted_leaves(ref['grad']))} of "
          f"{len(ref['grad'])} leaves", file=sys.stderr)
    if tr["fault"]:
        restored_gap = (math.inf if st["restored_sum"] is None else
                        max(abs(a - b) for x, y in zip(st["clean_sum"],
                                                       st["restored_sum"])
                            for a, b in zip(x, y)))
        checks["restore"] = {"value": restored_gap,
                             "limit": cell.limits["restore"]}
        replay_ok = (st["replay_loss"] is not None
                     and math.isfinite(st["replay_loss"]))
        checks["replay_nonfinite"] = {"value": 0.0 if replay_ok else 1.0,
                                      "limit": 0.0}
    # nothing may compile inside the window: a compile there is a fault of
    # the harness's set-up, and the run's numbers do not stand
    checks["window_compiles"] = {"value": float(st["window_compiles"]),
                                 "limit": 0.0}
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    devinfo = {"platform": devs[0].platform, "kind": run.device_kind,
               "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    if trace:
        devinfo["busy_s"] = busy
        devinfo["window_s"] = run.t_end - run.t_start
    out = {"correct": correct, "attempted": st["attempted"],
           "failed": st["attempted"] - accepted, **result,
           "device": devinfo, "checks": checks}
    # the breakdown and the checks must come after the keys above
    if "breakdown" in out:
        out["breakdown"] = out.pop("breakdown")
    out["checks"] = out.pop("checks")
    return out


def _profile_options(jax):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


# ---------------------------------------------------------------------------
# the comparison that decides `correct`

def compare(prog: Dict[str, Any], ref: Dict[str, Any],
            limits: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """The numbers that decide ``correct``, each the worst case of its kind,
    for those of them that ``limits`` gives a limit:

    - ``loss``: each step's |program - reference| / |reference|;
    - ``grad``: per leaf, the gap between the norms of the first clipped
      gradient, over the larger of the reference leaf's norm and the median
      leaf's;
    - ``change``: the same for the parameters' change over the steps,
      leaving out leaves whose reference gradient is under a thousandth of
      the median leaf's (they move by round-off alone under Adam).
    """
    out = {"loss": lambda: max(abs(a - b) / abs(b) for a, b in
                               zip(prog["losses"], ref["losses"])),
           "grad": lambda: _leaf_gap(prog["grad"], ref["grad"],
                                     list(ref["grad"])),
           "change": lambda: _leaf_gap(prog["change"], ref["change"],
                                       counted_leaves(ref["grad"]))}
    return {k: {"value": f(), "limit": limits[k]} for k, f in out.items()
            if k in limits}


def counted_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return [k for k, g in ref_grad.items() if g >= 1e-3 * med]


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keys: List[str]) -> float:
    med = statistics.median(ref[k] for k in keys)
    worst = 0.0
    for k in keys:
        den = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / den if den > 0 else math.inf
        worst = max(worst, gap if math.isfinite(prog[k]) else math.inf)
    return worst


def result_line(out: Dict[str, Any]) -> str:
    return json.dumps(out, default=_num)


def _num(x):
    if isinstance(x, (np.floating, np.integer)):
        return x.item()
    raise TypeError(type(x))
