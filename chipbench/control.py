"""The control of the benchmark's check, read at a cell's own size.

    python3 chipbench/control.py --workload <cell> --seeds 11 12 13

The control is the reference put in the program's place with one guarantee
of the configurations broken: its last reduction is left out, so its words
are congruent but lie in [0, 2q) (`reference.py`, `lazy=True`), the step a
faster transform is tempted to drop.  For each seed this builds the cell's
inputs on the device as a run does, computes the control's outputs for as
many batches as a run checks, each at the cell's largest batch, and judges
them as a run judges the program.  It prints one JSON line per seed; each has to
come out not correct.  The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def control_checks(cell, seed: int) -> dict:
    import harness

    pool = harness.make_pool(cell, seed)
    kept = []
    for i in range(cell.check_batches):
        t, s = harness.slot_of(cell, i)
        inputs = harness.pool_rows_at(cell, pool, t, s)
        kept.append((t, cell.max_batch, harness.expected(cell, inputs, t, lazy=True), inputs))
    checks, failed = harness.judge(cell, kept)
    return {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "failed": failed,
        "checks": checks,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
    import jax

    import harness

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    for seed in args.seeds:
        line = {"workload": cell.name, "seed": seed, "device": [dev.platform, dev.device_kind]}
        line.update(control_checks(cell, seed))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
