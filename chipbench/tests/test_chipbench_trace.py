"""The reduction from a trace to the per-layer metrics, on a hand-written trace."""
import json

import pytest

from chipbench_testing import BENCH

import trace_reduce  # noqa: E402

NS = 1e-9


@pytest.fixture
def small():
    return json.loads((BENCH / "tests" / "data" / "small_trace.json").read_text())


def test_busy_union_count_and_window(small):
    red = trace_reduce.reduce(small, "chipbench.steady")
    assert red.window_s == pytest.approx(10000 * NS)
    # ops clipped to [1000, 11000]: [1000,3000] (a and b overlap), [6000,9000], [10500,11000]
    assert red.busy_s == pytest.approx(5500 * NS)
    # b, the second a and c start inside; the first a started before it, d after it
    assert red.device_ops == 3


def test_op_time_by_label(small):
    red = trace_reduce.reduce(small, "chipbench.steady")
    assert red.op_s == pytest.approx({
        "a u32[8] custom-call": 4000 * NS,
        "b u32[8] fusion": 1200 * NS,
        "c u32[8] copy": 500 * NS,
    })
    assert [k for k, _ in red.top_ops(2)] == ["a u32[8] custom-call", "b u32[8] fusion"]


def test_idle_gaps_labelled_by_host_span(small):
    red = trace_reduce.reduce(small, "chipbench.steady")
    # gap [3000, 6000] has its middle in a dispatch, gap [9000, 10500] in a block
    assert red.idle_s == pytest.approx({"dispatch": 3000 * NS, "block": 1500 * NS})
    assert red.busy_s + sum(red.idle_s.values()) == pytest.approx(red.window_s)


def test_gap_outside_every_span_is_none(small):
    small["host"] = [h for h in small["host"] if h[0] == "chipbench.steady"]
    red = trace_reduce.reduce(small, "chipbench.steady")
    assert red.idle_s == pytest.approx({"none": 4500 * NS})


def test_busy_is_averaged_over_chips(small):
    small["device"]["/device:TPU:1"] = [["a u32[8] custom-call", 2000, 4000]]
    red = trace_reduce.reduce(small, "chipbench.steady")
    assert red.busy_s == pytest.approx((5500 + 2000) / 2 * NS)
    assert red.device_ops == 4


def test_no_device_plane_gives_no_busy_time(small):
    small["device"] = {}
    red = trace_reduce.reduce(small, "chipbench.steady")
    assert red.busy_s is None and red.device_ops == 0


def test_steady_span_must_be_there_once(small):
    small["host"] = small["host"][1:]
    with pytest.raises(ValueError, match="one 'chipbench.steady' span"):
        trace_reduce.reduce(small, "chipbench.steady")


@pytest.mark.parametrize("hlo, label", [
    ("%ntt_pallas.7 = u32[64,8,64,128]{3,2,1,0:T(8,128)S(1)} custom-call(u32[64,8,64,128]{3,2,1,0} %b)",
     "ntt_pallas.7 u32[64,8,64,128] custom-call"),
    ("%copy-start = (u32[8,2,13]{2,1,0:T(8,128)S(1)}, u32[8,2,13]{2,1,0}, u32[]{:S(2)}) copy-start(u32[8] %c)",
     "copy-start u32[8,2,13] copy-start"),
    ("%compare_select_fusion = u32[64,65536]{1,0:T(8,128)} fusion(u32[8,8]{1,0} %b), kind=kLoop",
     "compare_select_fusion u32[64,65536] fusion"),
    ("not an hlo op", "not an hlo op"),
])
def test_op_label(hlo, label):
    assert trace_reduce.op_label(hlo) == label
