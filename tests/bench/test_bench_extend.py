"""A configuration, a traffic mix, a per-layer metric and a cell added as
files and BENCHMARK.json entries only, in a copy of the benchmark's data in
a temporary directory: the harness finds them with no code changed."""
import json
import shutil

import _chipbench_path  # noqa: F401
from _chipbench_path import ROOT

NEW_METRIC = '''
def read(w):
    return w.n_requests / w.seconds
'''


def test_new_files_and_entries_make_a_new_cell(run_cell, tmp_path):
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(ROOT / "chipbench" / d, tmp_path / "chipbench" / d)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    config = json.loads((ROOT / "chipbench/configs/tpch-sf1-opt.json")
                        .read_text())
    config["name"] = "tpch-sf1-noopt"
    config["preset"] = "naive"
    (tmp_path / "chipbench/configs/tpch-sf1-noopt.json").write_text(
        json.dumps(config))
    (tmp_path / "chipbench/traffic/pair.json").write_text(json.dumps(
        {"loop": "closed", "requests": [{"query": "q6"},
                                        {"query": "q14"}]}))
    (tmp_path / "chipbench/metrics/answers_per_s.py").write_text(NEW_METRIC)
    bench["configs"].append({
        "name": "tpch-sf1-noopt", "source": "TPC-H", "reduced": [],
        "file": "chipbench/configs/tpch-sf1-noopt.json", "why": "test"})
    bench["workloads"].append({"name": "pair-noopt",
                               "config": "tpch-sf1-noopt",
                               "traffic": "pair", "chips": 1, "why": "t"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "power-opt" in m["workloads"]:
            m["workloads"].append("pair-noopt")     # a closed loop's metrics
    bench["per_layer"].append({
        "name": "answers_per_s", "unit": "queries/s", "better": "higher",
        "source": "host_clock", "layer": "server",
        "moves": "queries_per_s", "workloads": ["pair-noopt"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    r = run_cell("pair-noopt", root=tmp_path)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"queries_per_s", "latency_geomean_ms",
                                 "latency_p95_ms", "setup_s"}
    traced = run_cell("pair-noopt", trace=True, root=tmp_path)
    assert traced["correct"]
    assert traced["metrics"]["answers_per_s"]["value"] > 0
    assert "compiles_in_window" not in traced["metrics"]
