"""Find a cell's capacity on the chip, once, when the cell is defined.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds 3 \
        --max-batch 64 128 256 512 1024 --inflight 1 2 3 --rates 300000 400000

One process sets up the cell at each `--max-batch` in turn and runs short
windows: first at saturation (arrivals far above any capacity, no batch
issued after the window) for each in-flight count, then, at the cell's own
max batch and in-flight count, at each of `--rates` with every request
served.  One JSON line per window: the rate completed in the window, the
latency quantiles and the host's stalls.  The benchmark's own runs never
run this.
"""
import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent

SATURATE_PER_S = 4e6


def point(runner, harness, np, seconds, seed, **over) -> dict:
    runner.cell = dataclasses.replace(runner.cell, **over)
    drain = over["rate_per_s"] < SATURATE_PER_S
    arrive = harness.arrivals(runner.cell, seed, seconds)
    w = harness.run_window(runner, arrive, seconds, drain=drain)
    lat = w.latency * 1e3
    return {
        **over,
        "drain": drain,
        "requests_per_s": w.completed_in_window / seconds,
        "transform_rate": runner.cell.transforms * w.completed_in_window / seconds,
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p99_ms": float(np.percentile(lat, 99)),
        "mean_batch": len(w.latency) / max(1, len(w.batch_done)),
        "last_done_s": float(w.done.max()),
        "stalls": [[a, d, b] for a, d, b in w.stalls],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--max-batch", type=int, nargs="+", required=True)
    ap.add_argument("--inflight", type=int, nargs="+", default=[2])
    ap.add_argument("--rates", type=float, nargs="*", default=[])
    ap.add_argument("--out", default=None, help="also append the lines to this file")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    import os

    import jax
    import numpy as np

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(HERE.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import harness

    cell = harness.load_cell(args.workload)
    out = open(args.out, "a") if args.out else None
    points = {b: [dict(inflight=i, rate_per_s=SATURATE_PER_S) for i in args.inflight] for b in args.max_batch}
    points.setdefault(cell.max_batch, []).extend(dict(inflight=cell.inflight, rate_per_s=r) for r in args.rates)
    for batch, overs in points.items():
        t0 = time.perf_counter()
        runner = harness.Runner(dataclasses.replace(cell, max_batch=batch), args.seed)
        runner.warm()
        print(json.dumps({"workload": cell.name, "max_batch": batch, "setup_s": time.perf_counter() - t0,
                          "device": jax.devices()[0].device_kind}), flush=True)
        for over in overs:
            line = json.dumps({"workload": cell.name, "max_batch": batch,
                               **point(runner, harness, np, args.seconds, args.seed, **over)})
            print(line, flush=True)
            if out:
                print(line, file=out, flush=True)
        del runner
    return 0


if __name__ == "__main__":
    sys.exit(main())
