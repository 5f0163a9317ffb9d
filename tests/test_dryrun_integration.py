"""End-to-end dry-run path on a forced 8-device host mesh: stepbuilder →
jit(in_shardings) → lower → compile → HLO cost walk, for representative archs
and all three step kinds, using the reduced (smoke) configs."""

import pytest


def _script(arch: str, kind: str) -> str:
    return f"""
import dataclasses, jax, jax.numpy as jnp
from repro.core import ParallelPlan, SHAPES_BY_NAME
from repro.core.config import Family, InputShape
from repro.launch.stepbuilder import build_step, resolve_config
from repro.perf.hlo_cost import analyze_hlo
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
arch = "{arch}"
cfg = resolve_config(arch, "train_4k", smoke=True)
# MoE archs fold the expert ring onto the 4-wide model axis (ep is a
# degree now; the old ep=True/False bool is rejected by validate())
plan = ParallelPlan(remat="full", ep=4 if cfg.family == Family.MOE else 1)

# patch a reduced shape in place of the production ones
import repro.core.config as cc
import repro.launch.stepbuilder as sb
shape = InputShape("{kind}_t", 64, 8, "{kind}")
sb.SHAPES_BY_NAME = dict(sb.SHAPES_BY_NAME)
sb.SHAPES_BY_NAME[shape.name] = shape

fn, args, shardings, meta = build_step(arch, shape.name, mesh, plan, smoke=True)
with mesh:
    compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
hc = analyze_hlo(compiled.as_text(), mesh.size)
assert hc.flops > 0
print(arch, "{kind}", "flops", hc.flops, "coll", hc.collective_link_bytes)
"""


@pytest.mark.parametrize("arch,kind", [
    ("qwen1.5-4b", "train"),
    ("olmoe-1b-7b", "train"),
    ("mamba2-370m", "decode"),
    ("zamba2-1.2b", "decode"),
    ("whisper-small", "prefill"),
    ("pixtral-12b", "prefill"),
])
def test_dryrun_smoke_mesh(multidevice, arch, kind):
    multidevice(_script(arch, kind))
