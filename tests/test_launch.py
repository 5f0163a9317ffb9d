"""Entry points: the trainer's CLI driven in-process, the compile-cache
placement, and chip_smoke.py's refusal to run without a TPU."""

import os
import subprocess
import sys

import jax
import numpy as np

from conftest import REPO
from repro.launch import cache, train
from repro.launch.mesh import cpu_child_env


def test_train_main_reports_compile_and_step_times(tmp_path, monkeypatch):
    # a set variable leaves JAX's cache config alone (JAX read it at import)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    steps = 6
    res = train.main(["--arch", "mamba2-370m", "--steps", str(steps),
                      "--batch", "2", "--seq", "32", "--remat", "full",
                      "--ckpt-dir", str(tmp_path / "ckpt"), "--log-every", "1"])
    rep = res.report
    assert res.compile_seconds > 0
    assert res.compiled.as_text()                 # the step as compiled
    assert rep.steps_done == steps and not rep.anomalies
    assert len(rep.step_seconds) == steps
    assert all(t > 0 for t in rep.step_seconds)
    assert np.isfinite(rep.losses).all() and rep.losses[-1] < rep.losses[0]


def test_compile_cache_placement(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cache.use_compile_cache()
        # one fixed directory inside the checkout: never a temp name or pid
        assert path == str(REPO / ".jax_cache") == cache.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_without_tpu():
    env = dict(os.environ, **cpu_child_env())
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300, env=env,
                          cwd=REPO)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout
