"""Every committed cell, cut to a size interpret mode runs in seconds, built
from its files and run through the harness on the CPU: the lane's outputs
must match `reference.py` word for word.  The ML-DSA cells run the lane at
q = 8380417, N = 256 at their own widths."""
import dataclasses
import json
import time

import jax
import numpy as np
import pytest

from chipbench_testing import tiny

import harness  # noqa: E402

SEED = 2**31 + 11


def _rehearse(cell, trace=False, seconds=0.3):
    return harness.run(cell, SEED, seconds, trace, 0.0, jax.devices()[0])


@pytest.mark.parametrize("name", harness.list_cells())
def test_committed_cell_runs_correct(name):
    cell = harness.load_cell(name)
    res = _rehearse(tiny(cell, rows=16))
    assert res["correct"], res["checks"]
    assert 0 < res["attempted"] <= res["info"]["arrived"] and res["failed"] == 0
    if cell.drain:
        assert res["attempted"] == res["info"]["arrived"]
    assert res["checks"]["batches_unchecked"]["value"] == 0
    assert set(res["metrics"]) == {*cell.reports, "setup_s"}
    assert res["info"]["compiles_in_window"] == 0


def test_dropped_in_product_cell_runs_correct(tmp_path):
    root = tmp_path / "chipbench"
    for sub in ("configs", "workloads"):
        (root / sub).mkdir(parents=True)
    (root / "configs" / "toy.json").write_text(json.dumps({"name": "toy", "n": 512, "moduli": [12289, 40961]}))
    (root / "workloads" / "toy.product.json").write_text(json.dumps({
        "config": "toy", "chips": 1, "why": "a product", "rate_per_s": 40, "max_batch": 2, "inflight": 2,
        "reports": ["transform_rate", "latency_p95_ms"],
        "request": [{"op": "polymul_ntt", "rows": 3, "inputs": ["pool", "pool"]},
                    {"op": "ntt", "rows": 3, "inputs": ["prev"]}],
    }))
    res = _rehearse(harness.load_cell("toy.product", root))
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"op0.polymul_ntt.words_wrong", "op1.ntt.words_wrong", "batches_unchecked"}
    assert set(res["metrics"]) == {"transform_rate", "latency_p95_ms", "setup_s"}


def test_traced_run_reads_host_spans(monkeypatch):
    monkeypatch.setattr(harness, "load_peaks", lambda kind: {"hbm_bytes_per_s": 1e12})
    cell = tiny(harness.load_cell("mldsa65.verify_steady"), rate=200.0)
    res = _rehearse(cell, trace=True, seconds=0.4)
    assert res["correct"], res["checks"]
    # the CPU trace has host spans but no TPU plane: only the host metric
    # reads, under the name of the end-to-end metric the cell reports
    assert set(res["metrics"]) == {"dispatch_us.latency_p95_ms"}
    assert res["metrics"]["dispatch_us.latency_p95_ms"]["value"] > 0
    assert res["device_extra"]["busy_s"] is None
    assert res["device_extra"]["window_s"] == pytest.approx(0.2, rel=0.5)


def test_result_line_keeps_checks_last():
    cell = tiny(harness.load_cell("mldsa65.verify_overload"))
    res = _rehearse(cell)
    line = harness.result_line(res, jax.devices())
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 2**33 + 7])
def test_every_seed_offers_the_same_work(seed):
    cell = harness.load_cell("mldsa65.verify_steady")
    arrive = harness.arrivals(cell, seed, 10.0)
    assert len(arrive) == round(cell.rate_per_s * 10.0)
    assert np.all(np.diff(arrive) >= 0) and 0.0 <= arrive[0] and arrive[-1] < 10.0
    assert not np.array_equal(arrive[:100], harness.arrivals(cell, seed + 1, 10.0)[:100])


def test_batcher_takes_what_waits_and_pads_to_max_batch():
    cell = dataclasses.replace(tiny(harness.load_cell("mldsa65.verify_overload")), max_batch=4)
    runner = harness.Runner(cell, SEED)
    runner.warm()
    taken = []
    real = runner.offer

    def offer(i, requests, outs):
        taken.append((requests, [o.shape[0] for o in outs]))
        return real(i, requests, outs)

    runner.offer = offer
    arrive = np.concatenate([np.zeros(7), np.full(3, 0.05)])  # a burst of 7, then 3 more
    w = harness.run_window(runner, arrive, 0.1)
    assert len(w.latency) == 10 and np.all(w.latency > 0)
    assert [r for r, _ in taken] == [4, 3, 3]  # 4 of the burst, the 3 left, the 3 later
    rows = [op.rows * cell.max_batch for op in cell.ops]
    assert all(shapes == rows for _, shapes in taken)  # every batch padded to max_batch


def test_without_drain_no_batch_is_issued_after_the_window():
    cell = tiny(harness.load_cell("mldsa65.verify_overload"), max_batch=2)
    runner = harness.Runner(cell, SEED)
    runner.warm()
    arrive = np.linspace(0.0, 0.05, 50)
    w = harness.run_window(runner, arrive, 0.002, drain=False)
    assert 1 <= len(w.latency) < 50
    assert len(w.dispatch) == len(w.batch_done) and max(t for t, _ in w.dispatch) < 0.002


def test_window_records_host_stalls():
    cell = tiny(harness.load_cell("mldsa65.verify_overload"), max_batch=2)
    runner = harness.Runner(cell, SEED)
    runner.warm()
    real = runner.dispatch

    def dispatch(i, span):
        if i == 1:
            time.sleep(2 * harness.STALL_S)  # the host stands still inside the entry call
        return real(i, span)

    runner.dispatch = dispatch
    w = harness.run_window(runner, np.linspace(0.0, 0.05, 10), 0.1)
    assert any(b == "dispatch" and d >= 2 * harness.STALL_S for _, d, b in w.stalls)
    assert all(d > harness.STALL_S for _, d, _ in w.stalls)
