"""Each cell's run, end to end on the CPU at SF 0.01 (the chip check
skipped): untimed numbers only — correctness, the result line's keys and
the counters."""
import json

import pytest

from _chipbench_path import CELLS
from chipbench.spec import Spec


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(run_cell, bench_root, cell):
    spec = Spec(bench_root)
    r = run_cell(cell)
    json.dumps(r)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {m["name"] for m in
                                 spec.metrics("end_to_end", cell)}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(r["device"])


@pytest.mark.parametrize("cell", CELLS)
def test_traced_cell_reports_its_per_layer_metrics(run_cell, bench_root,
                                                  cell):
    spec = Spec(bench_root)
    r = run_cell(cell, trace=True)
    assert r["correct"], r["checks"]
    # the kernel's readers find no TPU events on the CPU, and say nothing
    want = {m["name"] for m in spec.metrics("per_layer", cell)} - {
        "pallas_ms_per_query", "pallas_roofline"}
    assert set(r["metrics"]) == want
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert "window_s" in r["device"] and "busy_s" in r["device"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_chip_no_result(capsys):
    """On a host without a TPU the command exits non-zero and prints no
    result line."""
    from chipbench import run

    assert run.main(["--workload", "power-opt", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert "correct" not in capsys.readouterr().out
