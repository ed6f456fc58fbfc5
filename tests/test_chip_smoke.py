"""chip_smoke.py off the chip: it refuses to run without a TPU, and its
phases compute the right answers at small sizes in interpret mode."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run_smoke(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_smoke_refuses_the_cpu_backend(args):
    proc = _run_smoke(ROOT, *args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_smoke_alone_without_the_repo_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run_smoke(tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_phases_pass_at_small_size_in_interpret_mode():
    recs = [chip_smoke.phase_transform(B=16),
            chip_smoke.phase_reference(B=8),
            chip_smoke.phase_serve(bandwidths=(8, 16), n=4)]
    for rec in recs:
        assert 0 <= rec["error"] <= rec["tol"], rec
        assert rec["kernel"] is False       # interpret mode: no Mosaic
    assert recs[2]["recovered"] == "4/4"
    with pytest.raises(chip_smoke.PhaseError, match="tpu_custom_call"):
        chip_smoke.report("transform", recs[0])
    chip_smoke.report("transform", dict(recs[0], kernel=True))


def test_compile_cache_dir(monkeypatch):
    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
