"""The readings that set `correct`'s limits, on the CPU at SF 0.01: on each
seed's window the program reads under its limits and the control, put in
its place on the same requests, over one of them."""
import _chipbench_path  # noqa: F401
from _chipbench_path import SF


def test_program_under_and_control_over_the_limits(monkeypatch, tmp_path):
    import repro.core.persist as persist
    from chipbench import readings

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(persist, "enable_compilation_cache", lambda: "off")
    lines = readings.readings("pallas-scan", [4, 2**31 + 9], 0.5,
                              require_tpu=False, sf=SF,
                              out=lambda msg: None)
    assert [ln["seed"] for ln in lines] == [4, 2**31 + 9]
    for ln in lines:
        assert ln["requests"] > 0
        assert all(v <= ln["limits"][k] for k, v in ln["program"].items())
        assert any(v > ln["limits"][k] for k, v in ln["control"].items())
        assert set(ln["gaps"]) == {"q3", "q6", "q12", "q17"}
