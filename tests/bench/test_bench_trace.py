"""The trace reduction on a small trace recorded on one TPU v5e: the
requests of q14 under `opt` and q6, q12 under `opt-pallas` at SF 1, with a
`bench.window` span added around them."""
import json
import pathlib

import pytest

import _chipbench_path  # noqa: F401
from chipbench import trace
from chipbench.harness import Window
from chipbench.spec import Spec

TRACE = pathlib.Path(__file__).parent / "data" / "v5e_trace.json"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(json.loads(TRACE.read_text()))


def test_busy_and_idle(reduced):
    assert reduced.n_devices == 1
    assert reduced.window_s == pytest.approx(0.161620037)
    assert 0 < reduced.busy_s < reduced.window_s
    assert reduced.idle_share == pytest.approx(
        1 - reduced.busy_s / reduced.window_s)
    assert reduced.busy_s == pytest.approx(0.087952939)


def test_kernel_events_and_bytes(reduced):
    kernels = reduced.kernel_ops()
    assert len(kernels) == 3
    # q6's call: three f32[7136,128] operands, an s32[1] and an f32[8,128]
    # result
    assert sorted(trace.kernel_bytes(n) for _, _, n in kernels)[1] == \
        3 * 7136 * 128 * 4 + 4 + 8 * 128 * 4


def test_breakdown(reduced):
    assert len(reduced.top_ops) == 10
    assert reduced.top_ops == sorted(reduced.top_ops, key=lambda kv: -kv[1])
    assert reduced.top_ops[0][0] == "%fusion f32[912404] <- l_discount__.1"
    assert reduced.gaps == sorted(reduced.gaps, key=lambda kv: -kv[1])
    assert all(label.startswith("bench.request ")
               for label, _ in reduced.gaps)


def test_union_merges_overlaps():
    tr = {"host": [[0, 100, "bench.window", "main"]],
          "devices": {"/device:TPU:0": [[10, 20, "a"], [20, 20, "b"],
                                        [50, 10, "c"], [95, 50, "d"]]}}
    r = trace.reduce(tr)
    assert r.busy_s == pytest.approx((30 + 10 + 5) * 1e-9)
    assert [g[1] for g in r.gaps] == pytest.approx(
        [x * 1e-9 for x in (35, 10, 10)])


def test_kernel_readers_stay_under_the_roofline(reduced):
    spec = Spec()
    peaks = json.loads((_chipbench_path.ROOT / "chipbench" / "peaks.json")
                       .read_text())["TPU v5 lite"]
    w = Window(1.0, 2, {}, reduced, [], peaks)
    share = spec.reader("pallas_roofline")(w)
    assert 0 < share < 100
    ms = spec.reader("pallas_ms_per_query")(w)
    assert ms == pytest.approx(sum(d for _, d, _ in reduced.kernel_ops())
                               * 1e-6 / 2)
    assert spec.reader("device_idle_share")(w) == pytest.approx(
        100 * reduced.idle_share)
