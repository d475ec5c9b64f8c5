"""The chip smoke run's checks, exercised on the CPU at SF 0.01, plus the
compile-cache placement the smoke run and the benchmarks rely on."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

from repro.core.persist import compilation_cache_dir  # noqa: E402


def test_smoke_checks_match_oracle_on_cpu():
    lines = []
    summary = chip_smoke.smoke(0.01, seed=0, out=lines.append)
    assert summary["failures"] == [], "\n".join(lines)
    assert sum(line.startswith("kernel ") for line in lines) == 7
    from repro.relational.queries import QUERIES

    assert set(QUERIES) <= set(summary["timings"])
    assert {f"pallas/{q}" for q in chip_smoke.PALLAS_QUERIES} \
        <= set(summary["timings"])


def test_smoke_sharded_checks_match_oracle_on_cpu():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 simulated devices (jax imported before "
                    "conftest could set XLA_FLAGS)")
    lines = []
    summary = chip_smoke.smoke_sharded(4, sf=0.01, seed=0, out=lines.append)
    assert summary["failures"] == [], "\n".join(lines)


def test_chip_smoke_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "placed-from-outside"])
def test_compilation_cache_dir(monkeypatch, tmp_path, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert os.path.normpath(compilation_cache_dir()) == want
