"""The chip smoke script's CPU rehearsal and the entry points' compile
cache helper.

``chip_smoke.py --grid 16x16x16`` runs every phase on the CPU backend,
where the kernels run in the Pallas interpreter: each phase prints its line
but fails for want of ``tpu_custom_call``, and the script must exit
non-zero without the ``"ok": true`` result line.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]
PHASES = ("default/pw_advection/block", "default/pw_advection/stream",
          "default/tracer_advection/block", "default/tracer_advection/stream",
          "chain/pw_advection/stream_t4_p4", "serve/pw_advection/stream")


def test_chip_smoke_cpu_rehearsal_runs_every_phase_and_fails(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--grid", "16x16x16"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    lines = proc.stdout.strip().splitlines()
    assert proc.returncode != 0
    assert lines[0].startswith("device: platform=cpu ")
    for name in PHASES:
        phase = [ln for ln in lines if ln.startswith(f"phase {name}: ")]
        assert len(phase) == 1, (name, proc.stdout, proc.stderr)
        # it ran and matched the reference; only the kernel check fails
        rel_err = float(re.search(r" rel_err=(\S+) ", phase[0]).group(1))
        assert rel_err <= 1e-4, (name, rel_err)
        assert "finite=True" in phase[0] and "tpu_custom_call=False" in phase[0]
        assert phase[0].endswith("-> FAIL")
    assert "dispatch=batched (vmap)" in proc.stdout
    try:
        last = json.loads(lines[-1])
    except ValueError:
        last = None
    assert not (isinstance(last, dict) and last.get("ok") is True)


def test_chip_smoke_without_tpu_or_grid_exits_before_phases(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "phase " not in proc.stdout
    assert "no TPU found" in proc.stderr


def test_compile_cache_leaves_a_configured_directory_alone(monkeypatch,
                                                          tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_ignored_checkout_path(monkeypatch):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(path) == ROOT / ".jax_cache"
    assert "/.jax_cache/" in (ROOT / ".gitignore").read_text().split()
