"""End-to-end correctness: every engine configuration must produce the
same result as the interpreted Volcano oracle for every TPC-H query."""
import pytest

from repro.core import CompiledQuery, VolcanoEngine, preset
from repro.core.volcano import assert_same
from repro.relational.queries import QUERIES, SORT_INSENSITIVE

CONFIGS = ["naive", "template", "tpch", "strdict", "opt"]

# The exhaustive 5-config x 15-query sweep takes many minutes; by default
# only the ladder endpoints run (naive = compilation without domain
# knowledge, opt = everything).  `pytest -m slow` (or `-m ""`) restores the
# full matrix.
FAST_CONFIGS = ["naive", "opt"]
CONFIG_PARAMS = [
    pytest.param(c) if c in FAST_CONFIGS
    else pytest.param(c, marks=pytest.mark.slow)
    for c in CONFIGS
]


@pytest.fixture(scope="module")
def oracle(db):
    eng = VolcanoEngine(db)
    return {name: eng.execute(fn()) for name, fn in QUERIES.items()}


@pytest.mark.parametrize("config", CONFIG_PARAMS)
@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_engine_matches_oracle(db, oracle, qname, config):
    cq = CompiledQuery(QUERIES[qname](), db, preset(config))
    res = cq.run()
    assert_same(res, oracle[qname], qname in SORT_INSENSITIVE)


@pytest.mark.parametrize("qname", sorted(QUERIES))
def test_oracle_nonempty(oracle, qname):
    res = oracle[qname]
    n = len(next(iter(res.values())))
    assert n > 0, f"{qname} returned no rows — predicate constants degenerate"
