"""The exchange readers on synthetic device events: collective time and
received bytes, averaged over the chips and divided over the requests."""
import pytest

import _chipbench_path  # noqa: F401
from chipbench import trace
from chipbench.collectives import collective_ops, received_bytes
from chipbench.harness import Window
from chipbench.spec import Spec

GATHER = ("%all-gather.3 = s32[400]{0:T(1024)} all-gather(s32[100]{0} %x), "
          "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
REDUCE_START = ("%all-reduce-start = f32[8,128]{1,0} all-reduce-start("
                "f32[8,128]{1,0} %a), to_apply=%add")
REDUCE_DONE = ("%all-reduce-done = f32[8,128]{1,0} all-reduce-done("
               "f32[8,128]{1,0} %all-reduce-start)")
ASYNC_START = ("%all-gather-start = ((s32[100]{0}), s32[400]{0}) "
               "async-start(s32[100]{0} %x), calls=%all-gather.4")
ASYNC_DONE = ("%all-gather-done = s32[400]{0:T(1024)S(1)} async-done("
              "((s32[100]{0}), s32[400]{0}) %all-gather-start)")
FUSION = ("%fusion.5 = f32[5917470]{0} fusion(f32[5917470]{0} "
          "%l_tax__.1), kind=kLoop, calls=%fused_computation.5")


def _window(devices: dict, n_requests: int) -> Window:
    tr = {"host": [[0, 10_000, "bench.window", "main"]],
          "devices": devices}
    return Window(1e-5, n_requests, {}, trace.reduce(tr), [], {})


def _read(metric: str, w: Window):
    return Spec().reader(metric)(w)


@pytest.mark.parametrize("name,want", [
    (GATHER, 400 * 4), (REDUCE_START, 0), (REDUCE_DONE, 8 * 128 * 4),
    (ASYNC_START, 0), (ASYNC_DONE, 400 * 4)])
def test_received_bytes_are_the_result_shapes(name, want):
    assert received_bytes(name) == want


def test_only_collectives_are_found():
    ops = [[0, 1, n] for n in (GATHER, REDUCE_START, REDUCE_DONE, FUSION,
                               ASYNC_START, ASYNC_DONE)]
    assert [e[2] for e in collective_ops(ops)] == [
        GATHER, REDUCE_START, REDUCE_DONE, ASYNC_START, ASYNC_DONE]


def test_time_and_bytes_per_chip_and_request():
    """Two chips, three requests: chip 0 spends 300 + 100 + 200 ns in
    collectives, chip 1 500 ns; the fusion is not counted."""
    w = _window({"/device:TPU:0": [[100, 300, GATHER],
                                   [500, 100, REDUCE_START],
                                   [700, 200, REDUCE_DONE],
                                   [1000, 4000, FUSION]],
                 "/device:TPU:1": [[100, 500, GATHER],
                                   [1000, 4000, FUSION]]}, 3)
    assert _read("exchange_ms_per_query", w) == pytest.approx(
        (600 + 500) * 1e-6 / 2 / 3)
    assert _read("exchange_mb_per_query", w) == pytest.approx(
        (1600 + 4096 + 1600) / 1e6 / 2 / 3)


def test_no_collective_reads_zero():
    w = _window({"/device:TPU:0": [[100, 300, FUSION]]}, 2)
    assert _read("exchange_ms_per_query", w) == 0.0
    assert _read("exchange_mb_per_query", w) == 0.0


def test_no_collective_over_several_chips_reads_nothing():
    """A sharded program always runs collectives: a trace over several
    chips without one means their names were not recognised, and a 0.0
    would pass for a reading."""
    w = _window({"/device:TPU:0": [[100, 300, FUSION]],
                 "/device:TPU:1": [[100, 300, FUSION]]}, 2)
    assert _read("exchange_ms_per_query", w) is None
    assert _read("exchange_mb_per_query", w) is None


def test_no_trace_reads_nothing():
    w = Window(1.0, 5, {}, None, [], {})
    assert _read("exchange_ms_per_query", w) is None
    assert _read("exchange_mb_per_query", w) is None
