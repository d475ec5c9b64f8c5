"""The benchmark's frozen data generator and its plain reference."""
import numpy as np
import pytest

from _chipbench_path import bench_with_dash
from chipbench import arrivals, reference
from chipbench.spec import Spec
from chipbench.tpch_data import generate


@pytest.mark.parametrize("seed", [0, 1])
def test_generator_copy_equals_program_generator(seed):
    from repro.relational.tpch import generate as program_generate

    ours, theirs = generate(0.01, seed), program_generate(0.01, seed)
    assert set(ours) == set(theirs)
    for name, t in theirs.items():
        mine = ours[name]
        assert mine.nrows == t.nrows
        assert set(mine.data) == set(t.data)
        for col, arr in t.data.items():
            assert mine.data[col].dtype == arr.dtype, (name, col)
            np.testing.assert_array_equal(mine.data[col], arr)
        for vocabs, theirs_v in ((mine.vocabs, t.vocabs),
                                 (mine.word_vocabs, t.word_vocabs)):
            assert set(vocabs) == set(theirs_v)
            for col, v in theirs_v.items():
                np.testing.assert_array_equal(vocabs[col], v)


def _kinds():
    spec = Spec()
    return sorted({r for w in bench_with_dash()["workloads"]
                   for r in arrivals.kinds(spec.traffic(w["traffic"]))},
                  key=lambda r: r.label)


@pytest.mark.parametrize("req", _kinds(), ids=lambda r: r.label)
def test_reference_agrees_with_volcano(db, req):
    """The reference and the program's own interpreter give the same
    answer for every request kind a cell sends (SF 0.01, seed 0: the
    session's `db`)."""
    from repro.core import VolcanoEngine
    from repro.relational.queries import PARAM_QUERIES, QUERIES
    from chipbench.compare import compare

    plan = (PARAM_QUERIES[req.query][0]() if req.template
            else QUERIES[req.query]())
    got = VolcanoEngine(db).execute(plan, params=req.binding_dict())
    want, _ = reference.rows_of(reference.answer(
        reference.Data(generate(0.01, 0)), req.query, req.binding_dict()))
    ok, rel, why = compare(got, want, req.query in reference.SORT_INSENSITIVE)
    assert ok, why
    assert rel < 1e-4


def test_open_schedule_gives_every_seed_the_same_work():
    """Two seeds: the same arrival times and templates in the same order;
    only the bindings dealt to each template's arrivals differ."""
    mix = Spec().traffic("dash")
    a = arrivals.open_schedule(mix, 1, 10.0)
    b = arrivals.open_schedule(mix, 2**31 + 5, 10.0)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 10)
    assert [t for t, _ in a] == [t for t, _ in b]
    assert [r.query for _, r in a] == [r.query for _, r in b]
    assert sorted(r.label for _, r in a) == sorted(r.label for _, r in b)
    assert [r.label for _, r in a] != [r.label for _, r in b]
    due = [t for t, _ in a]
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert 8.0 < due[-1] < 10.0
    counts = [sum(r.query == t["template"] for _, r in a)
              for t in mix["requests"]]
    weights = [t["weight"] for t in mix["requests"]]
    assert counts == sorted(counts, reverse=True)
    assert abs(counts[0] / len(a) - weights[0] / sum(weights)) < 0.01
    pool = [r.label for _, r in a if r.query == "q6"]
    assert max(pool.count(x) for x in pool) - min(
        pool.count(x) for x in pool) <= 1


def test_closed_rounds_send_every_query_once_a_round():
    mix = Spec().traffic("power")
    stream = arrivals.closed_rounds(mix, 2**31 + 11)
    first, second = next(stream), next(stream)
    assert sorted(r.label for r in first) == sorted(r.label for r in second) \
        == sorted(r["query"] for r in mix["requests"])
    assert first != second


def test_reference_counts_the_rows_each_value_sums():
    """The `#rows:` column beside a summed float column holds the rows the
    value sums: q1's are its `count_order`, q6's the rows its filter
    keeps, and a value that sums nothing has none."""
    data = reference.Data(generate(0.01, 0))
    q1, rows = reference.rows_of(reference.answer(data, "q1"))
    assert set(rows) == {k for k in q1 if q1[k].dtype.kind == "f"}
    assert all(np.array_equal(v, q1["count_order"]) for v in rows.values())
    q6, rows = reference.rows_of(reference.answer(data, "q6"))
    assert 0 < rows["revenue"][0] < data.raw["lineitem"].nrows
    q10, rows = reference.rows_of(reference.answer(data, "q10"))
    assert set(rows) == {"revenue"}
