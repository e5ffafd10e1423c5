"""The on-chip smoke script, driven on the CPU at tiny sizes.

``chip_smoke.py`` refuses to run without a TPU, so these tests call its
phase function directly (engine ``backend="ref"``, the path ``"auto"``
resolves to on a TPU) and check that the program itself exits non-zero,
printing no status line, where it finds no chip or no repository.  The
backend rule is steered inside the tests by monkeypatching the platform.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_tiny(chip_smoke):
    """Serve, node add and node remove at tiny sizes: a ragged final plan
    chunk, the NumPy oracle spanning several chunks, every check run."""
    lines = []
    out = chip_smoke.run_phases(
        n_nodes=16, n_keys=4096, batch=256, k=2, supersteps=2,
        sample_lanes=64, n_objects=5000, chunk=1024, oracle_ids=2048,
        mover_blocks=2, mover_k=2, backend="ref", log=lines.append,
    )
    assert out["backend"] == "ref"
    assert out["serve"]["requests"] == 2 * 2 * 256
    assert out["serve"]["sampled"] == 64
    for label in ("add", "remove"):
        assert out[label]["rows"] > 0
    assert lines[0].startswith("backend: ref")
    assert sum("equal the NumPy engine" in line for line in lines) == 2


def test_phases_refuse_host_numpy_backend(chip_smoke):
    with pytest.raises(chip_smoke.CheckFailed, match="NumPy backend"):
        chip_smoke.run_phases(n_nodes=4, backend="numpy", log=lambda _: None)


def _run_script(cwd, script):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def _prints_no_status(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_script_refuses_cpu_only_process():
    proc = _run_script(ROOT, SCRIPT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert _prints_no_status(proc.stdout)


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run_script(tmp_path, str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert _prints_no_status(proc.stdout)


@pytest.mark.parametrize("platform, want", [("tpu", "ref"), ("cpu", "numpy")])
def test_auto_backend_rule(monkeypatch, platform, want):
    import jax

    from repro.core import PlacementEngine, make_uniform_cluster

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    engine = PlacementEngine(make_uniform_cluster(4))
    assert engine.backend == want
    assert engine.backend == want  # resolved once
    (ev,) = engine.ledger.events("engine.backend")
    assert ev["name"] == want
    assert ev["requested"] == "auto" and ev["platform"] == platform


def test_explicit_backend_is_not_resolved(monkeypatch):
    import jax

    from repro.core import PlacementEngine, make_uniform_cluster

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    engine = PlacementEngine(make_uniform_cluster(4), backend="pallas")
    assert engine.backend == "pallas"
    assert engine.ledger.events("engine.backend") == []


def test_compile_cache_dir(monkeypatch, tmp_path):
    import jax

    from repro.compile_cache import ENV_VAR, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(ENV_VAR, str(tmp_path / "env_cache"))
        assert enable_compile_cache(str(tmp_path)) == str(tmp_path / "env_cache")
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv(ENV_VAR)
        want = str(tmp_path / ".jax_cache")
        assert enable_compile_cache(str(tmp_path)) == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
