"""The rate sweep of an open-loop cell, on the CPU at SF 0.01."""
import _chipbench_path  # noqa: F401
from _chipbench_path import SF


def test_sweep_reports_each_rate(monkeypatch, tmp_path, bench_root):
    import repro.core.persist as persist
    from chipbench import sweep

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(persist, "enable_compilation_cache", lambda: "off")
    lines = sweep.sweep("dash-opt", 5, 1.0, [5.0, 20.0], require_tpu=False,
                        sf=SF, out=lambda msg: None, root=bench_root)
    assert [ln["rate"] for ln in lines] == [5.0, 20.0]
    for ln in lines:
        assert ln["offered"] == round(ln["rate"] * 1.0)
        assert ln["answered"] == ln["offered"]
        assert ln["rejected"] == 0 and ln["compiles"] == 0
        assert isinstance(ln["sustained"], bool)
