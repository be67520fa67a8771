"""Helpers the chip benchmark's tests share: paths, and cells cut to a size
that interpret mode on the CPU runs in seconds."""
import dataclasses
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny(cell, n=None, towers=2, rows=4, max_batch=4, rate=40.0):
    """The cell with fewer towers, rows per request, pool slots and checked
    batches, a smaller largest batch and a slow arrival rate."""
    ops = tuple(dataclasses.replace(op, rows=min(op.rows, rows)) for op in cell.ops)
    return dataclasses.replace(
        cell,
        n=n or cell.n,
        moduli=cell.moduli[:towers],
        ops=ops,
        pool=min(cell.pool, 2),
        max_batch=min(cell.max_batch, max_batch),
        rate_per_s=rate,
        check_batches=min(cell.check_batches, 2),
    )
