"""`correct` has to fail: the control (the reference one precision down, in
the program's place) and the served path broken underneath the harness,
each on the CPU at SF 0.01."""
import pytest

from _chipbench_path import CELLS, SF
from chipbench import arrivals, control, harness
from chipbench.spec import Spec
from chipbench.tpch_data import generate


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(bench_root, cell):
    spec = Spec(bench_root)
    wl = spec.workload(cell)
    cfg = spec.config(wl["config"])
    raw = generate(SF, cfg["data_seed"])
    reqs = arrivals.kinds(spec.traffic(wl["traffic"]))
    answers = control.control_answers(raw, reqs)
    recs = [harness.Record(r, 0.0, 0.01, answers[r]) for r in reqs]
    verdict = harness.check(recs, raw, cfg["limits"])
    assert any(c["value"] > c["limit"] for c in verdict["checks"].values())


def _stale(monkeypatch):
    """Each execution returns the previous execution's answer: a step that
    leaves its state unchanged."""
    from repro.core.compile import CompiledQuery

    real, last = CompiledQuery.run, []

    def run(self, params=None):
        res = real(self, params)
        last.append(res)
        return last[-2] if len(last) > 1 else res
    monkeypatch.setattr(CompiledQuery, "run", run)


def _altered(monkeypatch):
    """One number of every answer changed where the answer is decoded."""
    from repro.core import compile as compile_mod

    real = compile_mod._decode_frame

    def decode(out, mask, meta):
        res = real(out, mask, meta)
        for k, v in res.items():
            if v.size and v.dtype.kind in "fi":
                v = v.copy()
                v[0] = v[0] * 1.001 + 1 if v.dtype.kind == "f" else v[0] + 1
                res[k] = v
                break
        return res
    monkeypatch.setattr(compile_mod, "_decode_frame", decode)


def _one_kind(column: str, factor: float = 1.01):
    """The answers of one request kind only, those with `column`, made 1%
    wrong in that column where they are decoded; every other kind's
    answers stay right."""
    def fault(monkeypatch):
        from repro.core import compile as compile_mod

        real = compile_mod._decode_frame

        def decode(out, mask, meta):
            res = real(out, mask, meta)
            if column in res:
                res[column] = res[column] * factor
            return res
        monkeypatch.setattr(compile_mod, "_decode_frame", decode)
    fault.__name__ = f"_one_kind_{column}"
    return fault


def _half_batch(monkeypatch):
    """A coalesced dispatch computes the first half of its bindings and
    hands their answers to the second half too."""
    from repro.core.compile import CompiledQuery

    real = CompiledQuery.run_many

    def run_many(self, bindings_list):
        bindings_list = list(bindings_list)
        half = (len(bindings_list) + 1) // 2
        res = real(self, bindings_list[:half])
        return [res[i % half] for i in range(len(bindings_list))]
    monkeypatch.setattr(CompiledQuery, "run_many", run_many)


FAULTS = [("power-opt", _stale), ("power-opt", _altered),
          ("power-opt", _one_kind("avg_yearly")),
          ("pallas-scan", _stale), ("pallas-scan", _altered),
          ("pallas-scan", _one_kind("avg_yearly")),
          ("dash-opt", _stale), ("dash-opt", _altered),
          ("dash-opt", _one_kind("promo_revenue")),
          ("dash-opt", _half_batch)]


@pytest.mark.parametrize("cell,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, f in FAULTS])
def test_broken_served_path_is_not_correct(run_cell, monkeypatch, cell,
                                           fault):
    fault(monkeypatch)
    # dashboards at 50x their rate, so that windows coalesce on the CPU
    rate = 50 * Spec().traffic("dash")["rate_per_s"] \
        if cell == "dash-opt" else None
    r = run_cell(cell, rate=rate)
    assert not r["correct"]


def test_dash_at_the_fault_rate_is_correct(run_cell):
    """The rate the faults above run dash-opt at is served correctly by the
    unbroken program, so the faults are what the comparison catches."""
    r = run_cell("dash-opt", rate=50 * Spec().traffic("dash")["rate_per_s"])
    assert r["correct"], r["checks"]
