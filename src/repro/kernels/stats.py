"""Host spans and counters of the kernel lane's public entries.

Each entry of `repro.kernels.ops` (`ntt`, `intt`, `polymul_ntt`) makes its
call through `call(name, fn, x, n, ...)`, which gives it

  * a host span `lane.<name>` (`jax.profiler.TraceAnnotation`, with the
    call's `rows` and `n`).  It lands in the profiler's trace beside the
    device's ops, on the same clock, and costs next to nothing when no
    profiler is tracing.  `polymul_ntt` calls `ntt` and `intt`, so their
    spans nest inside its own;
  * counters: calls, rows, host nanoseconds inside the call (sum and max)
    and calls longer than `SLOW_NS`.

The jitted bodies run inside `scope(name)`: a `jax.named_scope` of the same
`lane.<name>`, so every device op of the entry, XLA's relayouts included,
carries it in its `op_name`, and one more count of `traces`.  A jitted body
runs only while JAX traces it, so `traces` counts the times the entry was
traced anew (a new shape or static argument); in a warm loop it stays put.
`batch_major_traces` counts those of the traces that built a whole small-n
transform (n < 1024, one tile) as the batch-major fused pass
(`kernels.ntt._fused_pass`), and `batch_major_tile_traces` those that built
it for a tile of a longer transform or for n >= 1024: every other trace.

A context's tables (its modulus and twiddles, the operands of the compiled
transform: `kernels.ntt.device_tables`) are built and placed on the device
once, through `tables(name, q, n, build)`: a host span `lane.tables` (args
`q`, `n`, `bytes`) around the build, and counts `tables` and `table_bytes`
under the entry of its direction.  Like `traces`, they stay put in a warm
loop, whatever the number of moduli.

A call made while an outer `jax.jit` traces counts once per trace of the
outer function, with the tracing's host time.  `counters()` returns a
snapshot of every entry called or traced so far; `reset_counters()` zeroes
them, for instance as a measured window opens.  The hot path is one Python
frame, a span and a few integer adds under a lock: each extra frame costs
microseconds on a host that has just woken from waiting on the device.
"""
from __future__ import annotations

import threading
from time import perf_counter_ns

import jax
from jax.profiler import TraceAnnotation

PREFIX = "lane."
#: A call longer than this is counted in `over_50ms`: a stall of the host.
SLOW_NS = 50_000_000
FIELDS = (
    "calls", "rows", "host_ns", "host_ns_max", "over_50ms", "traces", "batch_major_traces", "tables", "table_bytes",
    "batch_major_tile_traces",
)
_CALLS, _ROWS, _NS, _MAX, _SLOW, _TRACES, _BATCH_MAJOR, _TABLES, _TABLE_BYTES, _BATCH_MAJOR_TILE = range(len(FIELDS))

_lock = threading.Lock()
_counts: dict[str, list[int]] = {}


def call(name: str, fn, x, n: int, *args, **kw):
    """`fn(x, *args, **kw)` as a call of entry `name` on `x`, rows of `n` words."""
    rows = x.size // n
    with TraceAnnotation(PREFIX + name, rows=rows, n=n):
        t0 = perf_counter_ns()
        out = fn(x, *args, **kw)
        dt = perf_counter_ns() - t0
    with _lock:
        c = _counts.get(name) or _counts.setdefault(name, [0] * len(FIELDS))
        c[_CALLS] += 1
        c[_ROWS] += rows
        c[_NS] += dt
        if dt > c[_MAX]:
            c[_MAX] = dt
        if dt > SLOW_NS:
            c[_SLOW] += 1
    return out


def scope(name: str):
    """The named scope of entry `name`'s jitted body; counts one trace."""
    with _lock:
        _counts.setdefault(name, [0] * len(FIELDS))[_TRACES] += 1
    return jax.named_scope(PREFIX + name)


def batch_major_trace(name: str, tile: bool = False) -> None:
    """Counts one program of entry `name` whose fused pass was built with the
    batch-major layout: in `batch_major_tile_traces` with `tile`, else (a
    whole small-n transform) in `batch_major_traces`."""
    with _lock:
        _counts.setdefault(name, [0] * len(FIELDS))[_BATCH_MAJOR_TILE if tile else _BATCH_MAJOR] += 1


def tables(name: str, q: int, n: int, build):
    """`build()`, the device tables of one context of entry `name`, under the
    host span `lane.tables`; counts one build and the bytes it placed."""
    with TraceAnnotation(PREFIX + "tables", q=q, n=n) as span:
        out = build()
        nbytes = sum(a.nbytes for a in jax.tree.leaves(out))
        span.set_metadata(bytes=nbytes)
    with _lock:
        c = _counts.setdefault(name, [0] * len(FIELDS))
        c[_TABLES] += 1
        c[_TABLE_BYTES] += nbytes
    return out


def counters() -> dict[str, dict[str, int]]:
    """A snapshot: entry name -> {field: count} for every field of FIELDS."""
    with _lock:
        return {name: dict(zip(FIELDS, c)) for name, c in _counts.items()}


def reset_counters() -> None:
    with _lock:
        _counts.clear()
