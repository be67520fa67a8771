"""Chip benchmark of the kernel lane: one cell, one process, one chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is `chipbench/workloads/<cell>.json`
over its `chipbench/configs/<config>.json`.  The run builds its inputs on the
device from the seed, warms every program the cell uses, then serves the
requests that arrive at the cell's rate for `--seconds`, checks the outputs
against `chipbench/reference.py`, and prints one JSON object as the last
line of standard output.  With `--trace 0` its metrics are the end-to-end
ones the cell reports (transform_rate or latency_p95_ms) and setup_s; with
`--trace 1` the middle half of the window is traced and its metrics are the
per-layer readers of `chipbench/metrics/`, each named after the end-to-end
metric it moves, with the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 1.  The compile cache goes where JAX_COMPILATION_CACHE_DIR
says, or else to `.jax_cache/` at the root of the checkout.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name: a file of chipbench/workloads/")
    ap.add_argument("--seed", type=int, required=True, help="seed of every input")
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0, help="1: per-layer metrics from a trace")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"chipbench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s)",
            file=sys.stderr,
        )
        return 1
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START, devices[0])
    harness.print_checks(result)
    print(json.dumps(harness.result_line(result, devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
