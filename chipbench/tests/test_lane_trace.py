"""`lane_trace.py`: the lane's spans, kernel names and counters read from a
trace, on a hand-written trace, and one window of each committed cell
rehearsed on the CPU."""
import json
import types

import jax
import pytest

from chipbench_testing import BENCH, tiny

import harness  # noqa: E402
import lane_trace  # noqa: E402
import trace_reduce  # noqa: E402

NS = 1e-9
STEADY = "chipbench.steady"
SEED = 2**31 + 13


@pytest.fixture
def lane():
    return json.loads((BENCH / "tests" / "data" / "lane_trace.json").read_text())


def test_kernel_and_non_kernel_device_time(lane):
    lr = lane_trace.reduce_lane(lane, STEADY, 2)
    # the first kernel started before the window: only its last 100 ns count
    assert lr.kernel_s == pytest.approx({
        "ntt_tile_fwd": 2600 * NS, "ntt_tile_inv": 2000 * NS, "ntt_stage_fwd": 2000 * NS, "modmul": 1000 * NS,
    })
    assert lr.non_kernel_s == pytest.approx(1000 * NS)  # reshape, relayout fusion, copy
    red = trace_reduce.reduce(lane, STEADY)
    assert sum(lr.kernel_s.values()) + lr.non_kernel_s == pytest.approx(sum(red.op_s.values()))


def test_entry_time_per_batch_counts_outermost_spans(lane):
    lr = lane_trace.reduce_lane(lane, STEADY, 2)
    # batch 1: ntt 300 + intt 150; batch 2: the product's 3000 (its three
    # transforms inside it not again) + ntt 200; the third batch is cut
    assert lr.entry_s == pytest.approx([450 * NS, 3200 * NS])


def test_idle_gaps_go_to_the_innermost_span(lane):
    lr = lane_trace.reduce_lane(lane, STEADY, 2)
    assert lr.idle_nested_s == pytest.approx({
        "AllocateRawBuffer": 100 * NS,  # inside lane.ntt, the latest-starting runtime event of any thread
        "block": 500 * NS,  # no lane span: the harness's span
        "lane.intt": 5000 * NS,  # the innermost of lane.polymul_ntt > lane.intt
        "dispatch": 1000 * NS,
        "none": 4800 * NS,
    })
    # the harness's own attribution of the same gaps is as it was
    red = trace_reduce.reduce(lane, STEADY)
    assert red.idle_s == pytest.approx({"dispatch": 6100 * NS, "block": 500 * NS, "none": 4800 * NS})
    assert sum(lr.idle_nested_s.values()) == pytest.approx(sum(red.idle_s.values()))


def test_runtime_events_and_long_entries(lane):
    lr = lane_trace.reduce_lane(lane, STEADY, 2, long_ns=2000)
    assert lr.runtime_s == pytest.approx({
        "ParseArguments": 60 * NS, "AllocateRawBuffer": 40 * NS,
        "PjitFunction(ntt_pallas)": 400 * NS, "ExecuteLaunch": 300 * NS,
    })
    assert lr.long_entries == [
        ["lane.polymul_ntt", pytest.approx(3000e-6), [
            ["PjitFunction(ntt_pallas)", pytest.approx(400e-6)], ["ExecuteLaunch", pytest.approx(300e-6)],
        ]],
    ]
    # the idle gaps over 2000 ns, and how many host events started in each
    assert lr.long_gaps == [["lane.intt", pytest.approx(5e-3), 8], ["none", pytest.approx(4.8e-3), 3]]
    quiet = lane_trace.reduce_lane(lane, STEADY, 2)
    assert quiet.long_entries == [] and quiet.long_gaps == []


def test_metrics_of_the_hand_written_trace(lane):
    lr = lane_trace.reduce_lane(lane, STEADY, 2)
    reading = types.SimpleNamespace(requests=4, batches=2, ideal_bytes=8000, hbm_bytes_per_s=1e12)
    m = lane_trace.lane_metrics(lr, reading)
    # ideal 8 ns a request over 7600 ns of kernels / 4 requests
    assert m["kernel_hbm_roofline"] == pytest.approx(100 * 8e-9 / 1900e-9)
    assert m["non_kernel_us_per_batch"] == pytest.approx(0.5)
    assert m["entry_host_us"] == pytest.approx(1.825)


def test_no_device_plane_reads_only_the_host(lane):
    lane["device"] = {}
    lr = lane_trace.reduce_lane(lane, STEADY, 2)
    reading = types.SimpleNamespace(requests=4, batches=2, ideal_bytes=8000, hbm_bytes_per_s=1e12)
    m = lane_trace.lane_metrics(lr, reading)
    assert m["kernel_hbm_roofline"] is None and m["non_kernel_us_per_batch"] is None
    assert m["entry_host_us"] == pytest.approx(1.825)


def test_split_host_keeps_what_lies_inside_lane_spans_on_any_thread():
    lines = {
        "python3": [
            ["chipbench.dispatch", 0, 100],
            ["lane.ntt", 10, 50],
            ["PjitFunction(ntt_pallas)", 12, 48],
            ["Crosses the span's end", 40, 60],
            ["After", 60, 70],
            ["lane.intt", 80, 90],
        ],
        "main": [["ExecuteLaunch", 20, 30], ["Between calls", 55, 75], ["DoEnqueueProgram", 82, 88]],
    }
    got = lane_trace.split_host(lines)
    assert [e[0] for e in got["host"]] == ["chipbench.dispatch"]
    assert [e[0] for e in got["lane"]] == ["lane.ntt", "lane.intt"]
    assert sorted((e[0], e[3]) for e in got["runtime"]) == [
        ("DoEnqueueProgram", "main"), ("ExecuteLaunch", "main"), ("PjitFunction(ntt_pallas)", "python3"),
    ]
    assert got["starts"] == sorted(s for events in lines.values() for _, s, _ in events)


@pytest.mark.parametrize("label, kernel", [
    ("ntt_tile_fwd.1 u32[6144,1,2,128] custom-call", "ntt_tile_fwd"),
    ("ntt_stage_inv.14 u32[64,1,2,4,64,128] custom-call", "ntt_stage_inv"),
    ("modmul u32[8192] custom-call", "modmul"),
    ("ntt_pallas.1 u32[6144,1,2,128] custom-call", None),
    ("reshape.2 u32[6144,1,2,128] reshape", None),
])
def test_kernel_of(label, kernel):
    assert lane_trace.kernel_of(label) == kernel


@pytest.mark.parametrize("name", harness.list_cells())
def test_committed_cell_window_reports_lane_counters(name):
    cell = tiny(harness.load_cell(name), rows=16)
    out = lane_trace.measure(cell, SEED, 0.3, False, None)
    info = out["info"]
    assert info["lane_traces_in_window"] == 0  # warm: nothing traced in the window
    assert set(info["lane_entry_max_ms"]) == {op.name for op in cell.ops}
    assert all(v == 0 for v in info["lane_entry_over_50ms"].values())
    assert sum(info["lane_calls"].values()) == info["batches"] * len(cell.ops)
    assert set(out["end_to_end"]) == set(cell.reports)


def test_traced_window_reads_the_lane_spans(monkeypatch):
    cell = tiny(harness.load_cell("mldsa65.verify_steady"), rate=200.0)
    out = lane_trace.measure(cell, SEED, 0.4, True, {"hbm_bytes_per_s": 1e12})
    m = out["metrics"]
    # the CPU trace has the host spans but no TPU plane
    assert m["entry_host_us"] > 0 and m["dispatch_us"] > 0
    assert m["kernel_hbm_roofline"] is None and m["non_kernel_us_per_batch"] is None
    assert out["breakdown"]["runtime_in_lane"]  # the runtime's host events inside the entry calls
    assert out["info"]["lane_traces_in_window"] == 0


def test_overhead_times_both_sides_alike():
    cell = tiny(harness.load_cell("mldsa65.verify_overload"))
    out = lane_trace.entry_overhead(cell, SEED, calls=8, block=2)
    assert out["op"] == "ntt" and out["shape"] == [cell.ops[0].rows * cell.max_batch, cell.n]
    assert out["calls_per_side"] == 8
    assert set(out["median_us"]) == set(out["mean_us"]) == {"entry", "bare"}
    assert out["off_cost_us_median"] == pytest.approx(out["median_us"]["entry"] - out["median_us"]["bare"])


def test_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert lane_trace.main(["--workload", "mldsa65.verify_steady", "--seed", "1", "--seconds", "1"]) == 1
    assert "needs a TPU" in capsys.readouterr().err
