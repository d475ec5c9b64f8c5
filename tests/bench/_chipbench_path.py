"""Puts the checkout root on sys.path so the tests import `chipbench`;
`SF` is the scale the CPU tests run the cells at.

The tests run the benchmark's cells and one more, `dash-opt`: the open-loop
dashboard cell, kept out of BENCHMARK.json (its tails swing between runs)
and kept here as data (`data/dash_cell.json`) so the open-loop generator,
the rate sweep and the coalescing fault stay tested."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SF = 0.01


def bench_with_dash() -> dict:
    """BENCHMARK.json with the `dash-opt` cell, its metrics, and the cell
    added to the per-layer metrics it shares with the others."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((ROOT / "tests/bench/data/dash_cell.json").read_text())
    for key in ("workloads", "end_to_end", "per_layer"):
        bench[key] += extra[key]
    cells = [w["name"] for w in extra["workloads"]]
    for m in bench["per_layer"]:
        if m["name"] in extra["per_layer_also"]:
            m["workloads"] += cells
    return bench


CELLS = [w["name"] for w in bench_with_dash()["workloads"]]
