"""BENCHMARK.json agrees with the benchmark's files; new cells, configurations
and metrics are found as files, with no edit; the command refuses to run
without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_testing import BENCH, ROOT

import harness  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_configs_match_their_files():
    for entry in SPEC["configs"]:
        assert entry["file"] == f"chipbench/configs/{entry['name']}.json"
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])


def test_workloads_match_their_files():
    names = [w["name"] for w in SPEC["workloads"]]
    assert sorted(names) == harness.list_cells()
    for entry in SPEC["workloads"]:
        wl = json.loads((BENCH / "workloads" / f"{entry['name']}.json").read_text())
        assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
        assert (wl["config"], wl["chips"], wl["why"]) == (entry["config"], entry["chips"], entry["why"])
        cell = harness.load_cell(entry["name"])
        assert cell.transforms > 0 and cell.ideal_bytes > 0


def test_metrics_match_their_readers():
    assert {m["name"] for m in SPEC["end_to_end"]} == {*harness.END_TO_END, "setup_s"}
    reports = {w["name"]: harness.load_cell(w["name"]).reports for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        cells = m.get("workloads", list(reports))
        assert sorted(cells) == sorted(c for c, r in reports.items() if m["name"] == "setup_s" or m["name"] in r)
    readers = harness.load_metrics()
    # a reader's metric is named `<reader>.<end-to-end metric it moves>`, in
    # every cell that reports that metric
    want = {f"{r}.{moved}" for r in readers for moved in harness.END_TO_END if any(moved in v for v in reports.values())}
    assert {m["name"] for m in SPEC["per_layer"]} == want
    for m in SPEC["per_layer"]:
        reader, moved = m["name"].split(".", 1)
        assert m["moves"] == moved and readers[reader].UNIT == m["unit"]
        assert sorted(m["workloads"]) == sorted(c for c, r in reports.items() if moved in r)


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        harness.load_peaks("cpu")


def _copy_bench(tmp_path):
    root = tmp_path / "chipbench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return root


def test_new_files_are_found_without_an_edit(tmp_path):
    root = _copy_bench(tmp_path)
    (root / "configs" / "toy.json").write_text(json.dumps({"name": "toy", "n": 512, "moduli": [12289]}))
    (root / "workloads" / "toy.product.json").write_text(json.dumps({
        "config": "toy", "chips": 1, "why": "a product", "rate_per_s": 10, "max_batch": 4, "inflight": 1,
        "reports": ["transform_rate"],
        "request": [{"op": "polymul_ntt", "rows": 2, "inputs": ["pool", "pool"]}],
    }))
    (root / "metrics" / "requests_seen.py").write_text("UNIT = 'requests'\n\ndef read(r):\n    return r.requests\n")
    assert "toy.product" in harness.list_cells(root)
    cell = harness.load_cell("toy.product", root)
    assert (cell.n, cell.moduli, cell.transforms) == (512, (12289,), 6)
    assert "requests_seen" in harness.load_metrics(root)


@pytest.mark.parametrize("bad, match", [
    ({"reports": ["requests_per_hour"]}, "reports must name"),
    ({"max_batch": 0}, "must be positive"),
    ({"request": [{"op": "fft", "rows": 2, "inputs": ["pool"]}]}, "unknown op"),
    ({"request": [{"op": "ntt", "rows": 2, "inputs": ["prev"]}]}, "reads 'prev'"),
    ({"request": [{"op": "polymul_ntt", "rows": 2, "inputs": ["pool"]}]}, "takes 2 inputs"),
])
def test_malformed_cells_are_refused(tmp_path, bad, match):
    root = _copy_bench(tmp_path)
    wl = json.loads((root / "workloads" / "mldsa65.verify_steady.json").read_text())
    wl.update(bad)
    (root / "workloads" / "bad.json").write_text(json.dumps(wl))
    with pytest.raises(ValueError, match=match):
        harness.load_cell("bad", root)


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mldsa65.verify_steady",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_run_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = _run(ROOT, env)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "needs 1 TPU chip" in proc.stderr
    assert "{" not in proc.stdout


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    _copy_bench(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = _run(tmp_path, env)
    assert proc.returncode != 0
    assert proc.stdout == ""
