"""Fixtures for the benchmark's CPU tests: the harness run at SF 0.01 with
the chip check skipped and JAX's persistent compilation cache left off (it
is process-wide, and other test files share the worker), on a checkout
whose BENCHMARK.json also holds `dash-opt` (`_chipbench_path`)."""
import json

import pytest

from _chipbench_path import ROOT, SF, bench_with_dash


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    (root / "BENCHMARK.json").write_text(json.dumps(bench_with_dash()))
    (root / "chipbench").symlink_to(ROOT / "chipbench")
    return root


@pytest.fixture
def run_cell(monkeypatch, tmp_path, bench_root):
    import repro.core.persist as persist
    from chipbench import harness

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setattr(persist, "enable_compilation_cache", lambda: "off")

    def run(workload, seed=3, seconds=1.0, trace=False, root=None, **kw):
        return harness.run_cell(workload, seed, seconds, trace,
                                require_tpu=False, sf=SF,
                                root=bench_root if root is None else root,
                                out=lambda msg: None, **kw)
    return run
