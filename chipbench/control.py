"""The control of `correct`: the reference computed one precision below the
configuration's (bfloat16 for float32), put in the program's place.  Its
answers go through the same comparison a run makes, and must come out as
not correct.

    python3 chipbench/control.py --workload power-opt

prints the numbers a run would compare (`exact_mismatches`, `float_gap`)
for the control's answers to every request kind of the cell's mix, on the
configuration's data (its fixed `data_seed`) at its scale, and the gap of
each request kind.  It needs no chip: the control is numpy.  The readings
that set the limits, the program's and the control's on the requests of
each seed's window, are made on the chip by `readings.py`.
"""
import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import arrivals, reference  # noqa: E402
from chipbench.compare import compare  # noqa: E402
from chipbench.spec import Spec  # noqa: E402
from chipbench.tpch_data import generate  # noqa: E402


def bf16(x):
    """Round to bfloat16, the precision below the configuration's float32:
    every float column, parameter and row-level result of the control."""
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def control_answers(raw: dict, reqs: list) -> dict:
    """The control's answer to each request, as the program would serve
    it (without the reference's `#rows:` columns)."""
    data = reference.Data(raw)
    return {r: reference.rows_of(reference.answer(
        data, r.query, r.binding_dict(), bf16))[0] for r in reqs}


def readings(workload: str, sf: float | None = None, root=ROOT) -> dict:
    """What the comparison reads when the control answers every request
    kind of the cell's mix once."""
    spec = Spec(root)
    wl = spec.workload(workload)
    scale = spec.config(wl["config"])["scale_factor"] if sf is None else sf
    raw = generate(scale, spec.config(wl["config"])["data_seed"])
    reqs = arrivals.kinds(spec.traffic(wl["traffic"]))
    data = reference.Data(raw)
    got = control_answers(raw, reqs)
    mismatches, gaps, per = 0, {}, {}
    for r in reqs:
        ok, gap, why = compare(got[r], reference.answer(
            data, r.query, r.binding_dict()),
            r.query in reference.SORT_INSENSITIVE)
        per[r.label] = gap if ok else why
        if ok:
            gaps[r.label] = gap
        else:
            mismatches += 1
    return {"exact_mismatches": mismatches,
            "float_gap": max(gaps.values(), default=0.0),
            "per_request": per}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    print(json.dumps(readings(args.workload)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
