"""The device-resident input store (`Database.device_input`): every input
of a compiled program except its `param/<name>` scalars is placed on the
device once per database, shared by every compiled entry that reads it,
and dropped on `reload()`."""
import sys
import threading

import jax
import numpy as np
import pytest

from repro.core import CompiledQuery, VolcanoEngine, preset
from repro.core.mesh import AXIS
from repro.core.plan_cache import PlanCache
from repro.core.volcano import assert_same
from repro.relational import Database
from repro.relational.queries import PARAM_QUERIES, QUERIES, SORT_INSENSITIVE

from test_sharding import _needs, sharded

# the TPC-H power stream the engine serves (q9full stands for Q9)
POWER = ["q1", "q3", "q4", "q5", "q6", "q7", "q9full", "q10", "q12", "q13",
         "q14", "q17", "q18", "q19"]


def fresh(db) -> Database:
    """A database over the shared test tables with a store of its own, so
    counts start at zero."""
    return Database(db.tables)


def stored(cq) -> dict:
    return {k: v for k, v in cq.inputs.items() if not k.startswith("param/")}


def test_plans_share_one_device_column(db):
    d = fresh(db)
    q7 = CompiledQuery(QUERIES["q7"](), d, preset("opt"))
    q12 = CompiledQuery(QUERIES["q12"](), d, preset("opt"))
    a = q7.inputs["lineitem/col/l_shipdate"]
    assert isinstance(a, jax.Array)
    assert q12.inputs["lineitem/col/l_shipdate"] is a


def test_fallback_twin_shares_inputs(db):
    d = fresh(db)
    cq = CompiledQuery(QUERIES["q3"](), d, preset("opt"))
    assert cq.compaction_points
    twin = cq._fallback_query()
    common = set(stored(cq)) & set(stored(twin))
    assert common
    for k in common:
        assert twin.inputs[k] is cq.inputs[k], k


@pytest.mark.parametrize("qname", sorted(PARAM_QUERIES))
def test_bind_hands_host_arrays_for_params_only(db, qname):
    build, defaults = PARAM_QUERIES[qname]
    cq, _ = PlanCache(db).get(build(), preset("opt"), defaults)
    for inputs in (cq.bind(), cq.bind_many([None, None, None])):
        host = {k for k, v in inputs.items() if isinstance(v, np.ndarray)}
        assert host == {k for k in inputs if k.startswith("param/")}
        assert host == {f"param/{n}" for n in cq.param_spec}
        assert all(isinstance(v, jax.Array) for k, v in inputs.items()
                   if k not in host)


def test_reload_empties_the_store(db):
    d = fresh(db)
    old = CompiledQuery(QUERIES["q6"](), d, preset("opt"))
    n = len(stored(old))
    assert d.device_stats().uploads == n
    d.reload(db.tables)
    assert d.device_stats().resident_bytes == 0
    new = CompiledQuery(QUERIES["q6"](), d, preset("opt"))
    st = d.device_stats()
    assert st.uploads == 2 * n and st.hits == 0
    for k, v in stored(new).items():
        assert v is not old.inputs[k], k
    assert new.run()["revenue"] == pytest.approx(old.run()["revenue"])


def test_device_stats_count_uploads_then_hits(db):
    d = fresh(db)
    assert d.device_stats().uploads == 0
    first = CompiledQuery(QUERIES["q6"](), d, preset("opt"))
    keys = stored(first)
    st = d.device_stats()
    assert (st.uploads, st.hits) == (len(keys), 0)
    assert st.resident_bytes == sum(v.nbytes for v in keys.values())
    CompiledQuery(QUERIES["q6"](), d, preset("opt"))
    again = d.device_stats()
    assert (again.uploads, again.hits) == (len(keys), len(keys))
    assert again.resident_bytes == st.resident_bytes


def test_concurrent_lookups_upload_once(db):
    d = fresh(db)
    cols = {f"lineitem/col/{c}": db.table("lineitem").data[c]
            for c in ("l_quantity", "l_discount", "l_tax")}
    got: list = []
    lock = threading.Lock()

    def worker():
        mine = [d.device_input(k, v) for k, v in cols.items()
                for _ in range(20)]
        with lock:
            got.extend(mine)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    st = d.device_stats()
    assert st.uploads == len(cols)
    assert st.hits == 16 * 20 * len(cols) - len(cols)
    assert len({id(a) for a in got}) == len(cols)


@pytest.fixture(scope="module")
def power_db(db):
    return fresh(db)


@pytest.fixture(scope="module")
def oracle(db):
    eng = VolcanoEngine(db)
    return {name: eng.execute(QUERIES[name]()) for name in POWER}


@pytest.mark.parametrize("qname", POWER)
def test_power_queries_match_oracle_from_the_store(power_db, oracle, qname):
    cq = CompiledQuery(QUERIES[qname](), power_db, preset("opt"))
    for k, v in stored(cq).items():
        assert power_db.device_input(k, None) is v, k
    assert_same(cq.run(), oracle[qname], qname in SORT_INSENSITIVE)


@_needs(2)
def test_two_shard_store_placement(db):
    d = fresh(db)
    cq = CompiledQuery(QUERIES["q12"](), d, sharded(2))
    assert cq.sharded_keys
    devices = set(cq._mesh.devices.flat)
    for k, v in stored(cq).items():
        assert v.sharding.device_set == devices, k
        want = (AXIS,) if k in cq.sharded_keys else ()
        assert tuple(v.sharding.spec) == want, k
    twin = CompiledQuery(QUERIES["q12"](), d, sharded(2))
    assert all(twin.inputs[k] is v for k, v in stored(cq).items())
    want = VolcanoEngine(db).execute(QUERIES["q12"]())
    assert_same(cq.run(), want, False)
