"""Per-kernel validation: the Pallas kernel (interpret mode on the CPU)
vs pure-jnp oracles, swept over shapes/dtypes/group counts, plus
hypothesis property tests."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings as hsettings, strategies as st
except ImportError:   # degrade gracefully: property tests skip, rest run
    from _hypothesis_stub import given, hsettings, st  # noqa: F401

from repro.kernels import ops, ref


@pytest.mark.parametrize("n", [1, 7, 100, 2048, 5000])
@pytest.mark.parametrize("n_groups", [1, 6, 25, 130])
@pytest.mark.parametrize("n_aggs", [1, 3, 8])
def test_filter_agg_matches_ref(n, n_groups, n_aggs):
    rng = np.random.default_rng(n * 1000 + n_groups + n_aggs)
    mask = jnp.asarray(rng.random(n) < 0.6)
    gidx = jnp.asarray(rng.integers(0, n_groups, n), dtype=jnp.int32)
    vals = jnp.asarray(rng.normal(size=(n, n_aggs)), dtype=jnp.float32)
    out = ops.filter_agg(mask, gidx, vals, n_groups, tile=1024)
    want = ref.filter_agg_ref(mask, gidx, vals, n_groups)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [3, 513, 4096])
@pytest.mark.parametrize("k,c", [(5, 1), (25, 4), (640, 3)])
def test_gather_join_matches_ref(n, k, c):
    rng = np.random.default_rng(n + k + c)
    fk = jnp.asarray(rng.integers(0, k, n), dtype=jnp.int32)
    table = jnp.asarray(rng.normal(size=(k, c)), dtype=jnp.float32)
    out = ops.gather_join(fk, table, tile=1024)
    want = ref.gather_join_ref(fk, table)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [10, 1000, 9001])
@pytest.mark.parametrize("k", [1, 10, 32])
def test_masked_topk_matches_ref(n, k):
    rng = np.random.default_rng(n + k)
    # distinct values so ordering is unambiguous
    vals = jnp.asarray(rng.permutation(n).astype(np.float32))
    mask = jnp.asarray(rng.random(n) < 0.7)
    tv, ti = ops.masked_topk(vals, mask, k, tile=2048)
    wv, wi = ref.masked_topk_ref(vals, mask, k)
    np.testing.assert_allclose(tv, wv, rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(ti), np.asarray(wi))


@pytest.mark.parametrize("tile", [64, 1000, 1536])
def test_tile_must_hold_whole_vreg_tiles(tile):
    """A block is whole (8, 128) tiles: any other tile is refused, never
    silently rounded to a different grid."""
    mask = jnp.ones((4096,), bool)
    with pytest.raises(ValueError, match="multiple of 1024"):
        ops.compact(mask, 16, tile=tile)
    with pytest.raises(ValueError, match="multiple of 1024"):
        ops.gather_join(jnp.zeros((4096,), jnp.int32),
                        jnp.ones((4, 2), jnp.float32), tile=tile)


# ---------------------------------------------------------------------------
# single-pass stream compaction (+ key→slot translation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 37, 2048, 5000])
@pytest.mark.parametrize("cap", [8, 64, 512])
@pytest.mark.parametrize("p", [0.0, 0.05, 0.5, 1.0])
def test_compact_matches_ref(n, cap, p):
    """Sweep crosses the interesting regimes: count == 0 (p=0), heavy
    overflow (p=1 with cap < n), partial tiles (n not a tile multiple)."""
    rng = np.random.default_rng(n * 7 + cap + int(p * 10))
    mask = jnp.asarray(rng.random(n) < p)
    idx, count = ops.compact(mask, cap, tile=1024)
    widx, wcount = ref.compact_ref(mask, cap)
    assert int(count) == int(wcount) == int(np.asarray(mask).sum())
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(widx))


@pytest.mark.parametrize("n,cap", [(100, 16), (2500, 256), (64, 8)])
def test_compact_translate_matches_ref(n, cap):
    rng = np.random.default_rng(n + cap)
    mask = jnp.asarray(rng.random(n) < 0.3)
    idx, count, slot = ops.compact_translate(mask, cap, tile=1024)
    widx, wcount = ref.compact_ref(mask, cap)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(widx))
    np.testing.assert_array_equal(np.asarray(slot),
                                  np.asarray(ref.slot_of_ref(mask)))
    assert int(count) == int(wcount)


@pytest.mark.parametrize("cap", [127, 128, 129])
@pytest.mark.parametrize("pattern", ["alternate", "runs"])
def test_compact_lane_boundaries(pattern, cap):
    """Packed rows land at running offsets that straddle 128-lane output
    rows, and the capacity cuts through a row: both stores of a row (the
    offset's own output row and the next) must place every id."""
    n = 3000
    i = np.arange(n)
    mask = i % 2 == 0 if pattern == "alternate" else (i // 97) % 3 != 1
    idx, count, slot = ops.compact_translate(jnp.asarray(mask), cap)
    widx, wcount = ref.compact_ref(jnp.asarray(mask), cap)
    assert int(count) == int(wcount) == int(mask.sum())
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(widx))
    np.testing.assert_array_equal(np.asarray(slot),
                                  np.asarray(ref.slot_of_ref(mask)))


def test_compact_overflow_keeps_exact_count():
    """count is the cumsum total, NOT clipped at capacity — the excess IS
    the overflow signal and its magnitude drives re-planning."""
    mask = jnp.ones((3000,), dtype=bool)
    idx, count = ops.compact(mask, 16, tile=1024)
    assert int(count) == 3000
    np.testing.assert_array_equal(np.asarray(idx), np.arange(16))


def test_compact_vmapped():
    """vmap over batched masks (the run_many path stages kernels under
    vmap): per-slot results must equal per-slot scalar calls and the
    oracle, with the running count carried over three grid steps."""
    import jax

    rng = np.random.default_rng(0)
    masks = jnp.asarray(rng.random((4, 3000)) < 0.25)
    bidx, bcount = jax.vmap(lambda m: ops.compact(m, 1024, tile=1024))(masks)
    for i in range(4):
        idx, count = ops.compact(masks[i], 1024, tile=1024)
        widx, wcount = ref.compact_ref(masks[i], 1024)
        np.testing.assert_array_equal(np.asarray(bidx[i]), np.asarray(idx))
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(widx))
        assert int(bcount[i]) == int(count) == int(wcount)


def test_compact_pred_matches_ref():
    """In-kernel predicate evaluation from named column blocks + scalars."""
    rng = np.random.default_rng(3)
    n = 2777
    cols = {"a": jnp.asarray(rng.normal(size=n), jnp.float32),
            "b": jnp.asarray(rng.integers(0, 10, n), jnp.int32)}
    scalars = [jnp.float32(0.2)]

    def pred(c, s):
        return (c["a"] < s[0]) & (c["b"] >= 3)

    idx, count, slot = ops.compact_pred(cols, scalars, pred, 1024,
                                        tile=1024, translate=True)
    mask = pred(cols, scalars)
    widx, wcount = ref.compact_ref(mask, 1024)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(widx))
    np.testing.assert_array_equal(np.asarray(slot),
                                  np.asarray(ref.slot_of_ref(mask)))
    assert int(count) == int(wcount)


# ---------------------------------------------------------------------------
# the fused selective pipeline: pred -> compact -> segment-reduce, one pass
# ---------------------------------------------------------------------------

def _pipeline_case(n, n_groups, seed):
    rng = np.random.default_rng(seed)
    cols = {"x": jnp.asarray(rng.normal(size=n), jnp.float32),
            "g": jnp.asarray(rng.integers(0, max(n_groups, 1), n), jnp.int32)}
    scalars = [jnp.float32(0.5)]
    pred = lambda c, s: c["x"] < s[0]
    vals = lambda c, s: [c["x"] * 2.0, jnp.float32(1.0)]
    gidx = None if n_groups == 1 else (lambda c, s: c["g"])
    return cols, scalars, pred, vals, gidx


@pytest.mark.parametrize("n", [1, 20, 1000, 4097])
@pytest.mark.parametrize("n_groups", [1, 7, 64])
@pytest.mark.parametrize("capacity", [0, 64])
def test_selective_filter_agg_matches_ref(n, n_groups, capacity):
    cols, scalars, pred, vals, gidx = _pipeline_case(n, n_groups, n)
    translate = capacity > 0
    got = ops.selective_filter_agg(cols, scalars, pred, vals, gidx, 2,
                                   n_groups, capacity, translate, tile=1024)
    want = ref.selective_filter_agg_ref(cols, scalars, pred, vals, gidx, 2,
                                        n_groups, capacity, translate)
    assert len(got) == len(want)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-4)
    assert int(got[1]) == int(want[1])
    for g, w in zip(got[2:], want[2:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_selective_filter_agg_empty_and_full():
    """count == 0 (no row passes) and all-pass both behave: zero sums /
    identity compaction respectively."""
    n = 2600
    cols = {"x": jnp.asarray(np.arange(n), jnp.float32)}
    scalars = []
    vals = lambda c, s: [c["x"]]
    never = lambda c, s: c["x"] < -1.0
    sums, count, idx = ops.selective_filter_agg(
        cols, scalars, never, vals, None, 1, 1, capacity=16, tile=1024)
    assert int(count) == 0
    assert float(np.asarray(sums).sum()) == 0.0
    np.testing.assert_array_equal(np.asarray(idx), np.zeros(16))
    always = lambda c, s: c["x"] >= 0.0
    sums, count, idx = ops.selective_filter_agg(
        cols, scalars, always, vals, None, 1, 1, capacity=4096, tile=1024)
    assert int(count) == n
    np.testing.assert_allclose(float(np.asarray(sums)[0, 0]),
                               float(np.arange(n).sum()), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(idx)[:n], np.arange(n))


# ---------------------------------------------------------------------------
# property tests (system invariants)
# ---------------------------------------------------------------------------

@hsettings(max_examples=25, deadline=None)
@given(st.integers(1, 4000), st.integers(1, 12), st.integers(0, 2**31 - 1))
def test_filter_agg_total_invariant(n, g, seed):
    """Sum over groups == masked sum over rows (conservation)."""
    rng = np.random.default_rng(seed)
    mask = jnp.asarray(rng.random(n) < 0.5)
    gidx = jnp.asarray(rng.integers(0, g, n), dtype=jnp.int32)
    vals = jnp.asarray(rng.normal(size=(n, 2)), dtype=jnp.float32)
    out = ops.filter_agg(mask, gidx, vals, g, tile=1024)
    total = np.where(np.asarray(mask)[:, None], np.asarray(vals), 0).sum(0)
    np.testing.assert_allclose(np.asarray(out).sum(0), total, rtol=1e-4,
                               atol=1e-4)


@hsettings(max_examples=25, deadline=None)
@given(st.integers(1, 4000), st.integers(3, 2048), st.integers(0, 2**31 - 1))
def test_compact_prefix_invariant(n, cap, seed):
    """The emitted prefix is exactly the first min(count, cap) valid row
    ids in ascending order, and slot_of inverts it (slot_of[idx[i]] == i)."""
    rng = np.random.default_rng(seed)
    mask = rng.random(n) < rng.random()
    idx, count, slot = ops.compact_translate(jnp.asarray(mask), cap,
                                             tile=1024)
    idx, slot = np.asarray(idx), np.asarray(slot)
    valid_ids = np.flatnonzero(mask)
    k = min(int(count), cap)
    np.testing.assert_array_equal(idx[:k], valid_ids[:k])
    np.testing.assert_array_equal(idx[k:], 0)
    np.testing.assert_array_equal(slot[mask], np.arange(len(valid_ids)))
    assert (slot[~mask] == -1).all()


@hsettings(max_examples=25, deadline=None)
@given(st.integers(1, 3000), st.integers(2, 50), st.integers(0, 2**31 - 1))
def test_gather_join_row_identity(n, k, seed):
    """Gathering the identity table returns one-hot rows that sum to 1."""
    rng = np.random.default_rng(seed)
    fk = jnp.asarray(rng.integers(0, k, n), dtype=jnp.int32)
    table = jnp.eye(k, dtype=jnp.float32)
    out = np.asarray(ops.gather_join(fk, table, tile=1024))
    np.testing.assert_allclose(out.sum(1), np.ones(n), atol=1e-6)
    np.testing.assert_array_equal(out.argmax(1), np.asarray(fk))
