"""One cell of the chip benchmark: built from its files, timed, checked.

A cell is `workloads/<cell>.json` over `configs/<config>.json` (the ring:
n and the moduli).  Both are found by name, so a new cell or configuration
is a new file and no edit here.

Traffic is open loop: independent clients send requests at `rate_per_s`,
whether or not earlier ones have finished.  A run of `seconds` draws
round(rate * seconds) arrival times uniformly over the window from the seed
(a Poisson process given its count), so every seed offers the same work in
another order.  A request is a list of calls on the kernel lane's public
entries (`repro.kernels.ops.ntt`, `intt`, `polymul_ntt`), each on `rows`
rows per request; an input is a seeded pool array (`"pool"`) or the
previous call's output (`"prev"`).

The batcher: whenever fewer than `inflight` batches are outstanding and
requests wait, it takes up to `max_batch` of them, in arrival order, pads
the batch to `max_batch` and issues each call once on the whole batch, so
each op runs one compiled program.  Batch i runs under modulus i mod T
(the towers, round robin) on pool slot (i div T) mod POOL_SLOTS.  In a
cell that reports a latency, every request that arrived in the window is
served, those still waiting when it closes included; a request's latency
runs from its arrival to the return of its batch's `block_until_ready`.
A cell that reports only a rate issues no batch after the window closes
and counts the requests completed within it.

Correctness: CHECK_BATCHES batches drawn from the seed over the whole run
are kept; once the window has closed, every word of each of their calls'
outputs is compared with `reference.py` run on the same seeded inputs.
Any word that differs fails the run.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import random
import statistics
import sys
import tempfile
import time
from collections import deque
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

import reference
import trace_reduce
from repro.core.ntt import make_context
from repro.kernels import ops as lane

HERE = Path(__file__).resolve().parent

#: The program under test: the lane's public entries by the names cells use.
OPS = {"ntt": lane.ntt, "intt": lane.intt, "polymul_ntt": lane.polymul_ntt}
ARITY = {"ntt": 1, "intt": 1, "polymul_ntt": 2}
#: Polynomial transforms per row: a product is two forward and one inverse.
TRANSFORMS_PER_ROW = {"ntt": 1, "intt": 1, "polymul_ntt": 3}
#: Row-sized HBM transfers per row of the op done ideally: read each input
#: once, write the output once.
IDEAL_ROW_TRANSFERS = {"ntt": 2, "intt": 2, "polymul_ntt": 3}
#: The end-to-end metrics a cell may report.
END_TO_END = ("transform_rate", "latency_p95_ms")
#: Pool slots per pooled input: batches cycle through them.
POOL_SLOTS = 4
#: Batches kept for the check, drawn from the seed over the whole run.
CHECK_BATCHES = 4
#: A turn of the window's loop longer than this is a stall of the host: a
#: turn takes about a millisecond at the most otherwise.
STALL_S = 0.05
WORD_BYTES = 4
SPAN = "chipbench."


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    rows: int  # per request
    inputs: tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    n: int
    moduli: tuple[int, ...]
    ops: tuple[Op, ...]
    rate_per_s: float
    max_batch: int
    inflight: int
    reports: tuple[str, ...]
    chips: int
    pool: int = POOL_SLOTS
    check_batches: int = CHECK_BATCHES

    @property
    def transforms(self) -> int:
        """Polynomial transforms in one request."""
        return sum(op.rows * TRANSFORMS_PER_ROW[op.name] for op in self.ops)

    @property
    def ideal_bytes(self) -> int:
        """HBM bytes one request needs at least: each row read and written once."""
        return sum(op.rows * self.n * WORD_BYTES * IDEAL_ROW_TRANSFERS[op.name] for op in self.ops)

    @property
    def drain(self) -> bool:
        """Serve every arrival, late ones too: a tail is over all requests."""
        return "latency_p95_ms" in self.reports


def list_cells(root: Path = HERE) -> list[str]:
    return sorted(p.stem for p in (root / "workloads").glob("*.json"))


def load_cell(name: str, root: Path = HERE) -> Cell:
    """Read a cell and its configuration by name; raise on anything malformed."""
    wl = json.loads((root / "workloads" / f"{name}.json").read_text())
    cfg = json.loads((root / "configs" / f"{wl['config']}.json").read_text())
    ops = tuple(Op(o["op"], int(o["rows"]), tuple(o["inputs"])) for o in wl["request"])
    for k, op in enumerate(ops):
        if op.name not in OPS:
            raise ValueError(f"{name}: unknown op {op.name!r}; known: {sorted(OPS)}")
        if len(op.inputs) != ARITY[op.name] or not set(op.inputs) <= {"pool", "prev"}:
            raise ValueError(f"{name}: {op.name} takes {ARITY[op.name]} inputs of 'pool' or 'prev'")
        if "prev" in op.inputs and (k == 0 or ops[k - 1].rows != op.rows):
            raise ValueError(f"{name}: op {k} reads 'prev' but no earlier op has {op.rows} rows")
        if op.rows < 1:
            raise ValueError(f"{name}: op {k} has no rows")
    cell = Cell(
        name=name,
        config=wl["config"],
        n=int(cfg["n"]),
        moduli=tuple(int(q) for q in cfg["moduli"]),
        ops=ops,
        rate_per_s=float(wl["rate_per_s"]),
        max_batch=int(wl["max_batch"]),
        inflight=int(wl["inflight"]),
        reports=tuple(wl["reports"]),
        chips=int(wl["chips"]),
    )
    if cell.n & (cell.n - 1):
        raise ValueError(f"{name}: n must be a power of two")
    if not cell.moduli or min(cell.rate_per_s, cell.max_batch, cell.inflight) <= 0:
        raise ValueError(f"{name}: moduli, rate_per_s, max_batch, inflight must be positive")
    if not cell.reports or not set(cell.reports) <= set(END_TO_END):
        raise ValueError(f"{name}: reports must name some of {END_TO_END}")
    return cell


def load_metrics(root: Path = HERE) -> dict:
    """Per-layer metric readers, one module per file of `metrics/`, by file name."""
    found = {}
    for path in sorted((root / "metrics").glob("*.py")):
        spec = importlib.util.spec_from_file_location(f"chipbench_metric_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        found[path.stem] = mod
    return found


def load_peaks(kind: str, root: Path = HERE) -> dict:
    peaks = json.loads((root / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; known: {sorted(peaks)}")
    return peaks[kind]


# ---------------------------------------------------------------------------
# the pool, the arrivals and one batch
# ---------------------------------------------------------------------------


def pool_keys(cell: Cell) -> list[tuple[int, int, int]]:
    """(op, input, tower) of every pooled input; each has `pool` slots."""
    return [
        (k, j, t)
        for k, op in enumerate(cell.ops)
        for j, src in enumerate(op.inputs)
        if src == "pool"
        for t in range(len(cell.moduli))
    ]


def make_pool(cell: Cell, seed: int) -> dict:
    """Every pooled input, uniform in [0, q) of its tower, made on the device
    in one jitted call from the seed: `pool[k, j, t, s]` is slot s of input
    j of op k under tower t, rows for `max_batch` requests."""
    keys = pool_keys(cell)

    def gen(key):
        out = []
        for i, (k, _, t) in enumerate(keys):
            rows = cell.ops[k].rows
            full = jax.random.randint(
                jax.random.fold_in(key, i), (cell.pool, rows * cell.max_batch, cell.n), 0, cell.moduli[t], jnp.int32
            ).astype(jnp.uint32)
            out.append([full[s] for s in range(cell.pool)])
        return out

    slots = jax.jit(gen)(jax.random.key(seed))
    jax.block_until_ready(slots)
    return {(*key, s): a for key, per_slot in zip(keys, slots) for s, a in enumerate(per_slot)}


def arrivals(cell: Cell, seed: int, seconds: float) -> np.ndarray:
    """Sorted arrival times in [0, seconds): round(rate * seconds) of them,
    uniform over the window."""
    count = max(1, round(cell.rate_per_s * seconds))
    return np.sort(np.random.default_rng([seed, 1]).uniform(0.0, seconds, count))


def slot_of(cell: Cell, i: int) -> tuple[int, int]:
    """(tower, pool slot) of batch i."""
    towers = len(cell.moduli)
    return i % towers, (i // towers) % cell.pool


class Runner:
    """Dispatches batches of one cell and keeps what the check needs."""

    def __init__(self, cell: Cell, seed: int):
        self.cell = cell
        self.ctxs = [make_context(q, cell.n) for q in cell.moduli]
        self.pool = make_pool(cell, seed)
        self.rng = random.Random(seed)
        self.batches = 0
        self.kept: list = []  # (tower, slot, requests, outputs)

    def dispatch(self, i: int, span) -> tuple[list, float]:
        """Issue batch i's calls on its pool slot; return their outputs and
        the host seconds spent inside the entry calls."""
        t, s = slot_of(self.cell, i)
        ctx = self.ctxs[t]
        outs, prev, inside = [], None, 0.0
        for k, op in enumerate(self.cell.ops):
            with span("rotate"):
                args = [prev if src == "prev" else self.pool[k, j, t, s] for j, src in enumerate(op.inputs)]
            t0 = time.perf_counter()
            with span("dispatch"):
                prev = OPS[op.name](*args, ctx)
            inside += time.perf_counter() - t0
            outs.append(prev)
        return outs, inside

    def offer(self, i: int, requests: int, outs: list) -> None:
        """Reservoir of `check_batches` batches drawn uniformly from the run."""
        self.batches += 1
        item = (*slot_of(self.cell, i), requests, outs)
        if len(self.kept) < self.cell.check_batches:
            self.kept.append(item)
        else:
            r = self.rng.randrange(self.batches)
            if r < self.cell.check_batches:
                self.kept[r] = item

    def warm(self) -> None:
        """Run every program the window can run: each op under each tower."""
        for t in range(len(self.cell.moduli)):
            jax.block_until_ready(self.dispatch(t, _no_span)[0])


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Window:
    start: float
    seconds: float
    latency: np.ndarray  # per request, seconds
    done: np.ndarray  # per request, completion time since start
    dispatch: list  # per batch: (dispatch time since start, host seconds inside the entry calls)
    batch_done: list  # per batch: completion time since start
    steady: tuple[float, float] | None = None  # since start
    stalls: list = dataclasses.field(default_factory=list)  # (start since start, seconds, branch) over STALL_S

    @property
    def completed_in_window(self) -> int:
        return int(np.sum(self.done <= self.seconds))


def _no_span(_name):
    return contextlib.nullcontext()


def _trace_span(name):
    return jax.profiler.TraceAnnotation(SPAN + name)


def run_window(runner: Runner, arrive: np.ndarray, seconds: float, steady=None, drain: bool = True) -> Window:
    """Serve the requests arriving at `arrive` (seconds since the start).

    With `drain`, every request is served, those still waiting at `seconds`
    included; without it, no batch is issued after `seconds`.  With `steady`
    = (a, b), the part from a to b seconds in is marked by a host span for
    the trace, and every call gets host spans.
    """
    cell = runner.cell
    span = _trace_span if steady else _no_span
    total = len(arrive)
    latency = np.full(total, np.nan)
    done = np.full(total, np.inf)
    disp, batch_done, stalls = [], [], []
    outstanding: deque = deque()
    marker, marks = None, None
    issued = batch = 0
    start = time.perf_counter()
    now = 0.0
    while True:
        if steady and marks is None and now >= steady[0]:
            marker = _trace_span("steady")
            marker.__enter__()
            marks = [now, None]
        if marker is not None and now >= steady[1]:
            marker.__exit__(None, None, None)
            marker, marks[1] = None, now
        waiting = int(np.searchsorted(arrive, now, side="right")) - issued
        open_ = drain or now < seconds
        if waiting > 0 and open_ and len(outstanding) < cell.inflight:
            take = min(waiting, cell.max_batch)
            outs, inside = runner.dispatch(batch, span)
            disp.append((now, inside))
            outstanding.append((batch, issued, take, outs))
            issued += take
            batch += 1
            branch = "dispatch"
        elif outstanding:
            i, first, take, outs = outstanding.popleft()
            with span("block"):
                jax.block_until_ready(outs)
            t_done = time.perf_counter() - start
            latency[first : first + take] = t_done - arrive[first : first + take]
            done[first : first + take] = t_done
            batch_done.append(t_done)
            runner.offer(i, take, outs)
            branch = "block"
        elif issued == total or not open_:
            break
        else:  # nothing outstanding and nobody waiting: spin to the next arrival
            with span("wait"):
                while time.perf_counter() - start < arrive[issued]:
                    pass
            branch, now = "wait", max(now, arrive[issued])  # the spin itself is no stall
        last, now = now, time.perf_counter() - start
        if now - last > STALL_S:
            stalls.append((last, now - last, branch))
    if marker is not None:
        marker.__exit__(None, None, None)
        marks[1] = now
    served = np.isfinite(done)
    return Window(
        start=start,
        seconds=seconds,
        latency=latency[served],
        done=done[served],
        dispatch=disp,
        batch_done=batch_done,
        steady=tuple(marks) if marks else None,
        stalls=stalls,
    )


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------


def expected(cell: Cell, pool_rows: dict, t: int, lazy: bool = False) -> list:
    """Reference outputs of each op of one batch.

    `lazy` gives the control: each op's output with the reference's last
    reduction left out, computed from the exact outputs of the ops before it.
    """
    q = cell.moduli[t]
    outs, prev = [], None
    for k, op in enumerate(cell.ops):
        args = [prev if src == "prev" else pool_rows[k, j] for j, src in enumerate(op.inputs)]
        transform = reference.TRANSFORMS[op.name]
        outs.append(transform(*args, q, lazy=lazy))
        prev = transform(*args, q) if lazy else outs[-1]
    return outs


def pool_rows_at(cell: Cell, pool: dict, t: int, s: int) -> dict:
    """Host copies of the pooled inputs of a batch, by (op, input)."""
    return {
        (k, j): np.asarray(pool[k, j, t, s])
        for k, op in enumerate(cell.ops)
        for j, src in enumerate(op.inputs)
        if src == "pool"
    }


def gather(cell: Cell, runner: Runner) -> list:
    """Host copies of every kept batch: (tower, requests, outputs, inputs)."""
    return [
        (t, requests, [np.asarray(o) for o in outs], pool_rows_at(cell, runner.pool, t, s))
        for t, s, requests, outs in runner.kept
    ]


def judge(cell: Cell, kept: list) -> tuple[dict, int]:
    """Each compared number with its limit, and how many checked requests failed."""
    checks = {f"op{k}.{op.name}.words_wrong": {"value": 0, "limit": 0} for k, op in enumerate(cell.ops)}
    failed = 0
    for t, requests, got, inputs in kept:
        wrong = np.zeros(requests, bool)
        for k, (g, e) in enumerate(zip(got, expected(cell, inputs, t))):
            bad = np.asarray(g) != e
            checks[f"op{k}.{cell.ops[k].name}.words_wrong"]["value"] += int(bad.sum())
            rows = bad.any(axis=1)[: requests * cell.ops[k].rows]
            wrong |= rows.reshape(requests, cell.ops[k].rows).any(axis=1)
        failed += int(wrong.sum())
    checks["batches_unchecked"] = {"value": cell.check_batches - len(kept), "limit": 0}
    return checks, failed


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Reading:
    """Input of every per-layer reader (`metrics/<name>.py: read(Reading)`)."""

    window_s: float  # length of the traced steady window
    busy_s: float | None  # device busy time in it, averaged over chips; None: no device plane
    device_ops: int  # device operations that started in it, over all chips
    requests: int  # requests completed in it
    batches: int  # batches completed in it
    ideal_bytes: int  # HBM bytes one request needs at least
    hbm_bytes_per_s: float  # the device's peak
    dispatch_s: list  # host seconds inside the entry calls, per batch dispatched in it


def steady_reading(cell: Cell, window: Window, reduced, peaks: dict):
    """What the per-layer readers see: the traced steady part of the window."""
    a, b = window.steady
    return Reading(
        window_s=reduced.window_s,
        busy_s=reduced.busy_s,
        device_ops=reduced.device_ops,
        requests=int(np.sum((window.done >= a) & (window.done <= b))),
        batches=sum(a <= t <= b for t in window.batch_done),
        ideal_bytes=cell.ideal_bytes,
        hbm_bytes_per_s=float(peaks["hbm_bytes_per_s"]),
        dispatch_s=[inside for t, inside in window.dispatch if a <= t <= b],
    )


def end_to_end(cell: Cell, window: Window) -> dict:
    """The cell's end-to-end metrics but set-up: all work over all the window."""
    found = {
        "transform_rate": (cell.transforms * window.completed_in_window / window.seconds, "transforms/s"),
        "latency_p95_ms": (float(np.percentile(window.latency, 95)) * 1e3, "ms"),
    }
    return {m: {"value": found[m][0], "unit": found[m][1]} for m in cell.reports}


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float, device) -> dict:
    """Set up, warm, time the window, check; return the result's fields."""
    compiles: list[float] = []

    def on_event(event, _duration, **_kw):
        if event in (
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/backend_compile_duration",
        ):
            compiles.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        return _run(cell, seed, seconds, trace, t_start, device, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


def _run(cell, seed, seconds, trace, t_start, device, compiles) -> dict:
    peaks = load_peaks(device.device_kind) if trace else None
    runner = Runner(cell, seed)
    runner.warm()
    arrive = arrivals(cell, seed, seconds)
    gc.collect()
    gc.freeze()  # set-up's objects stay out of the window's collections
    result: dict = {}
    if trace:
        with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as tdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            setup_s = time.perf_counter() - t_start
            window = run_window(runner, arrive, seconds, steady=(0.25 * seconds, 0.75 * seconds), drain=cell.drain)
            jax.profiler.stop_trace()
            reduced = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(tdir)), SPAN + "steady")
        reading = steady_reading(cell, window, reduced, peaks)
        metrics = {}
        for name, mod in load_metrics().items():
            value = mod.read(reading)
            if value is not None:
                for moved in cell.reports:
                    metrics[f"{name}.{moved}"] = {"value": value, "unit": mod.UNIT}
        result["device_extra"] = {"busy_s": reduced.busy_s, "window_s": reduced.window_s}
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in reduced.top_idle(10)],
        }
    else:
        setup_s = time.perf_counter() - t_start
        window = run_window(runner, arrive, seconds, drain=cell.drain)
        metrics = {**end_to_end(cell, window), "setup_s": {"value": setup_s, "unit": "s"}}
    gc.unfreeze()
    end = window.start + float(window.done.max())
    in_window = sum(window.start <= c <= end for c in compiles)
    memory_peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    kept = gather(cell, runner)
    del runner  # the program's state goes before the reference runs
    checks, failed = judge(cell, kept)
    requests = len(window.latency)
    result.update(
        correct=all(c["value"] <= c["limit"] for c in checks.values()),
        attempted=requests,
        failed=failed,
        metrics=metrics,
        memory_peak_bytes=memory_peak,
        checks=checks,
        info={
            "setup_s": setup_s,
            "arrived": len(arrive),
            "served": requests,
            "served_in_window": window.completed_in_window,
            "batches": len(window.batch_done),
            "mean_batch": requests / max(1, len(window.batch_done)),
            "last_done_s": float(window.done.max()),
            "compiles_in_window": in_window,
            "stalls_over_50ms": len(window.stalls),
            "stall_total_s": sum(d for _, d, _ in window.stalls),
            "stall_max": max(((round(d, 4), round(a, 3), b) for a, d, b in window.stalls), default=None),
            "median_latency_ms": statistics.median(window.latency) * 1e3,
        },
    )
    return result


def result_line(result: dict, devices: list) -> dict:
    """The last line of standard output, `checks` last; the devices as JAX
    reports them."""
    dev = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": result["memory_peak_bytes"],
        **result.get("device_extra", {}),
    }
    line = {
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": result["metrics"],
        "device": dev,
    }
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = result["checks"]
    return line


def print_checks(result: dict, out=sys.stderr) -> None:
    for k, v in result["info"].items():
        print(f"info {k}: {v}", file=out)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=out, flush=True)
