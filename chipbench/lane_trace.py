"""The kernel lane's own spans, kernel names and counters, read from a cell's
window on the chip.

    python3 chipbench/lane_trace.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 chipbench/lane_trace.py --workload <cell> --seed <n> --overhead <calls>

The lane (`repro.kernels`) writes a host span `lane.<entry>` around each
call of `ops.ntt`, `intt` and `polymul_ntt` (nested for the transforms a
product calls), names its kernels (`KERNELS`) and counts each entry's
calls, host time and traces (`ops.counters()`).  The benchmark's harness
does not read these yet; this tool runs one window of a cell through the
harness's own functions and reads them.  In both modes it resets the
lane's counters as the window opens and prints the cell's end-to-end
metrics, the harness's `dispatch_us` quantity and the counters, so a traced
and an untraced run of one seed give the cost of tracing.  With `--trace 1`
the window is traced as in the benchmark's traced run, and from the middle
half it reads:

  kernel_hbm_roofline      ideal HBM time of a request over the device
                           time of the lane's kernels per request, in %
  non_kernel_us_per_batch  device time of every other op (relayouts,
                           copies, XLA fusions) per completed batch
  entry_host_us            per batch, the summed duration of the outermost
                           `lane.*` spans of its calls; median over batches
  idle_gaps_nested         each idle gap of the device put down to the
                           innermost span over its middle: a runtime host
                           event inside a lane span, else a lane span, else
                           a harness span, else "none"
  long_gaps, long_entries  each idle gap and each entry call over 50 ms:
                           its innermost span and how many host events
                           started in it (none: the whole process stood
                           still), or the runtime events inside the call

`--overhead` times `calls` calls of the cell's first transform through its
entry against as many calls of the jitted transform without the entry's
span and counters, in alternating blocks, each call waited for before the
next: the instrumentation's cost with no profiler running.

Prints one JSON object as the last line.  Without a TPU it prints nothing
and exits 1.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import re
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace_reduce  # noqa: E402

LANE = "lane."
#: The lane's kernels by their `pallas_call` names; the compiler adds `.N`.
KERNELS = ("ntt_tile_fwd", "ntt_tile_inv", "ntt_stage_fwd", "ntt_stage_inv", "modmul")
#: An entry call longer than this is listed with what ran inside it.
LONG_NS = 50_000_000
_SUFFIX = re.compile(r"\.\d+$")


def load(path) -> dict:
    """`trace_reduce.load`'s device ops and harness spans, and besides:
    "lane", every `lane.*` host span, [[name, start_ns, end_ns], ...], and
    "runtime", every other host event that lies inside a lane span, on any
    host thread (the TPU runtime launches on a line of its own):
    [[name, start_ns, end_ns, line], ...], and "starts", the sorted start
    of every host event, of any name on any thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, lines = {}, {}
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops.extend([trace_reduce.op_label(e.name), e.start_ns, e.end_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.setdefault(line.name, []).extend([e.name, e.start_ns, e.end_ns] for e in line.events)
    return {"device": device, **split_host(lines)}


def split_host(lines: dict) -> dict:
    """The host's events, by thread, sorted into the harness's spans
    ("host"), the lane's ("lane"), the others that lie inside a lane span
    ("runtime", with their thread), and the start of each ("starts")."""
    ours = (LANE, trace_reduce.SPAN_PREFIX)
    every = [ev for events in lines.values() for ev in events]
    lane = [ev for ev in every if ev[0].startswith(LANE)]
    outer = trace_reduce._union((s, e) for _, s, e in lane)
    starts = [s for s, _ in outer]
    runtime = []
    for line, events in lines.items():
        for name, s, e in events:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= outer[i][1] and not name.startswith(ours):
                runtime.append([name, s, e, line])
    host = [ev for ev in every if ev[0].startswith(trace_reduce.SPAN_PREFIX)]
    return {"host": host, "lane": lane, "runtime": runtime, "starts": sorted(ev[1] for ev in every)}


def kernel_of(label: str) -> str | None:
    """The lane kernel a device op label names, or None."""
    name = _SUFFIX.sub("", label.split(" ", 1)[0])
    return name if name in KERNELS else None


@dataclasses.dataclass
class LaneReduced:
    kernel_s: dict  # lane kernel -> device seconds in the window, averaged over device planes
    non_kernel_s: float  # device seconds of every other op, summed over planes
    entry_s: list  # per batch dispatched in the window: its outermost lane spans' seconds, summed
    idle_nested_s: dict  # innermost span over each idle gap's middle -> seconds, summed over planes
    runtime_s: dict  # runtime event -> seconds inside lane spans in the window (nested ones each)
    long_entries: list  # [span, ms, [[runtime event, ms], ...]] of each outermost entry call over long_ns, anywhere
    long_gaps: list  # [label, ms, host events that started inside] of each idle gap over long_ns in the window


def _outermost(spans) -> list:
    out: list = []
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        if not out or s >= out[-1][2]:
            out.append((name, s, e))
    return out


def _innermost(spans, times) -> list:
    """For each of the sorted `times`, the latest-starting of the nested
    `spans` ([name, start, end, ...]) that covers it, as (start, name), or None."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    found, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        found.append((stack[-1][1], stack[-1][0]) if stack else None)
    return found


def _innermost_of_threads(spans, times) -> list:
    """`_innermost` over spans from several threads, each nested on its own
    thread (the last field of a span): the latest start among the threads."""
    threads: dict = {}
    for sp in spans:
        threads.setdefault(sp[-1], []).append(sp)
    per_thread = [_innermost(group, times) for group in threads.values()]
    return [max((f for f in found if f), default=None) for found in zip(*per_thread)] if per_thread else [None] * len(times)


def reduce_lane(trace: dict, steady: str, calls_per_batch: int, long_ns: int = LONG_NS) -> LaneReduced:
    """What the lane's names and spans say about the host span `steady`.

    The harness opens and closes `steady` between batches, so the outermost
    lane spans that start in it fall into whole batches of `calls_per_batch`
    calls, one per op of the cell; an incomplete last batch is dropped."""
    (w0, w1), = [(s, e) for name, s, e in trace["host"] if name == steady]
    kernel_s: dict = {}
    non_kernel_s = 0.0
    idle: dict = {}
    long_gaps = []
    harness = [(name[len(trace_reduce.SPAN_PREFIX):], s, e) for name, s, e in trace["host"] if name != steady]
    for ops in trace["device"].values():
        inside = [(max(s, w0), min(e, w1), label) for label, s, e in ops if s < w1 and e > w0]
        for s, e, label in inside:
            k = kernel_of(label)
            if k:
                kernel_s[k] = kernel_s.get(k, 0.0) + (e - s) * 1e-9 / len(trace["device"])
            else:
                non_kernel_s += (e - s) * 1e-9
        merged = trace_reduce._union((s, e) for s, e, _ in inside)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(lo, hi) for lo, hi in zip(edges[0::2], edges[1::2]) if hi > lo]
        mids = [(lo + hi) / 2 for lo, hi in gaps]
        by_kind = [_innermost_of_threads(trace["runtime"], mids), _innermost(trace["lane"], mids), _innermost(harness, mids)]
        for (lo, hi), *found in zip(gaps, *by_kind):
            label = next((f[1] for f in found if f), "none")
            idle[label] = idle.get(label, 0.0) + (hi - lo) * 1e-9
            if hi - lo > long_ns:  # did any thread of the host do anything in it?
                events = bisect.bisect_left(trace["starts"], hi) - bisect.bisect_right(trace["starts"], lo)
                long_gaps.append([label, (hi - lo) * 1e-6, events])
    outer = _outermost(trace["lane"])
    calls = [e - s for _, s, e in outer if w0 <= s < w1]
    whole = len(calls) - len(calls) % calls_per_batch
    entry_s = [sum(calls[i : i + calls_per_batch]) * 1e-9 for i in range(0, whole, calls_per_batch)]
    runtime_s: dict = {}
    for name, s, e, _ in trace["runtime"]:
        if w0 <= s < w1:
            runtime_s[name] = runtime_s.get(name, 0.0) + (e - s) * 1e-9
    long_entries = []
    for name, s, e in outer:
        if e - s > long_ns:
            inner = sorted(((n, (b - a) * 1e-6) for n, a, b, _ in trace["runtime"] if s <= a and b <= e), key=lambda x: -x[1])
            long_entries.append([name, (e - s) * 1e-6, [[n, ms] for n, ms in inner[:5]]])
    return LaneReduced(kernel_s, non_kernel_s, entry_s, idle, runtime_s, long_entries, long_gaps)


def lane_metrics(lr: LaneReduced, reading) -> dict:
    """The three quantities, each None where the trace holds nothing to read;
    `reading` is the harness's `Reading` of the same window."""
    kernel = sum(lr.kernel_s.values())
    return {
        "kernel_hbm_roofline": (
            100.0 * (reading.ideal_bytes / reading.hbm_bytes_per_s) / (kernel / reading.requests)
            if kernel and reading.requests else None
        ),
        "non_kernel_us_per_batch": (
            lr.non_kernel_s / reading.batches * 1e6 if reading.batches and (kernel or lr.non_kernel_s) else None
        ),
        "entry_host_us": statistics.median(lr.entry_s) * 1e6 if lr.entry_s else None,
    }


def _top(d: dict, k: int = 10) -> list:
    return [[name, v] for name, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def counter_info(counts: dict) -> dict:
    """The counters of a window as the harness would print them."""
    return {
        "lane_traces_in_window": sum(c["traces"] for c in counts.values()),
        "lane_entry_max_ms": {e: c["host_ns_max"] * 1e-6 for e, c in counts.items() if c["calls"]},
        "lane_entry_over_50ms": {e: c["over_50ms"] for e, c in counts.items() if c["calls"]},
        "lane_entry_mean_us": {e: c["host_ns"] / c["calls"] * 1e-3 for e, c in counts.items() if c["calls"]},
        "lane_calls": {e: c["calls"] for e, c in counts.items()},
    }


def measure(cell, seed: int, seconds: float, trace: bool, peaks: dict | None) -> dict:
    """One window of `cell`, as the benchmark's run of it, with the lane's
    counters reset as it opens; `peaks` is the device's row of peaks.json."""
    import gc
    import tempfile

    import harness
    import jax

    from repro.kernels import ops as lane

    runner = harness.Runner(cell, seed)
    runner.warm()
    arrive = harness.arrivals(cell, seed, seconds)
    middle = (0.25 * seconds, 0.75 * seconds)
    gc.collect()
    gc.freeze()
    lane.reset_counters()
    if trace:
        with tempfile.TemporaryDirectory(prefix="lane_trace_") as tdir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            window = harness.run_window(runner, arrive, seconds, steady=middle, drain=cell.drain)
            jax.profiler.stop_trace()
            data = load(trace_reduce.find_xplane(tdir))
    else:
        window = harness.run_window(runner, arrive, seconds, drain=cell.drain)
    counts = lane.counters()
    gc.unfreeze()
    a, b = window.steady or middle
    inside = [s for t, s in window.dispatch if a <= t <= b]
    out = {
        "workload": cell.name,
        "seed": seed,
        "trace": int(trace),
        "end_to_end": {k: v["value"] for k, v in harness.end_to_end(cell, window).items()},
        "dispatch_us": statistics.median(inside) * 1e6 if inside else None,
        "info": {
            **counter_info(counts),
            "stalls_over_50ms": len(window.stalls),
            "stall_max": max(((round(d, 4), b) for _, d, b in window.stalls), default=None),
            "batches": len(window.batch_done),
            "requests": len(window.latency),
        },
    }
    if trace:
        steady = harness.SPAN + "steady"
        reduced = trace_reduce.reduce(data, steady)
        reading = harness.steady_reading(cell, window, reduced, peaks)
        lr = reduce_lane(data, steady, len(cell.ops))
        out["metrics"] = {
            **{name: mod.read(reading) for name, mod in harness.load_metrics().items()},
            **lane_metrics(lr, reading),
        }
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced.top_ops(10)],
            "kernels": _top(lr.kernel_s),
            "non_kernel_s": lr.non_kernel_s,
            "idle_gaps": [[k, v] for k, v in reduced.top_idle(10)],
            "idle_gaps_nested": _top(lr.idle_nested_s),
            "runtime_in_lane": _top(lr.runtime_s, 20),
            "long_entries": lr.long_entries,
            "long_gaps": lr.long_gaps,
        }
        out["busy_s"], out["window_s"] = reduced.busy_s, reduced.window_s
    return out


def entry_overhead(cell, seed: int, calls: int, block: int = 100) -> dict:
    """Host time of a call through the lane's entry and of the same jitted
    transform called bare, on the cell's first transform at a full batch."""
    import jax
    import numpy as np

    import harness
    from repro.core.ntt import make_context
    from repro.kernels.ntt import ntt_pallas

    op = next(op for op in cell.ops if op.name in ("ntt", "intt"))
    q = cell.moduli[0]
    ctx = make_context(q, cell.n)
    x = jax.random.randint(jax.random.key(seed), (op.rows * cell.max_batch, cell.n), 0, q, "int32").astype("uint32")
    entry = harness.OPS[op.name]
    forward = op.name == "ntt"
    sides = {"entry": lambda: entry(x, ctx), "bare": lambda: ntt_pallas(x, ctx, forward=forward)}
    for f in sides.values():
        f().block_until_ready()
    took: dict = {side: [] for side in sides}
    for r in range(max(1, calls // block)):
        for side in ("entry", "bare") if r % 2 == 0 else ("bare", "entry"):
            f = sides[side]
            for _ in range(block):
                t0 = time.perf_counter_ns()
                y = f()
                took[side].append(time.perf_counter_ns() - t0)
                y.block_until_ready()
    us = {side: np.asarray(v) * 1e-3 for side, v in took.items()}
    q1, med, q3 = ({s: float(np.percentile(v, p)) for s, v in us.items()} for p in (25, 50, 75))
    return {
        "workload": cell.name,
        "op": op.name,
        "shape": list(x.shape),
        "calls_per_side": len(us["entry"]),
        "median_us": med,
        "quartiles_us": {s: [q1[s], q3[s]] for s in us},
        "mean_us": {s: float(v.mean()) for s, v in us.items()},
        "off_cost_us_median": med["entry"] - med["bare"],
        "off_cost_us_mean": float(us["entry"].mean() - us["bare"].mean()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell name: a file of chipbench/workloads/")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--overhead", type=int, default=0, help="time this many entry calls against bare ones instead")
    args = ap.parse_args(argv)
    sys.path.insert(1, str(HERE.parent / "src"))
    import os

    import jax

    import harness

    cell = harness.load_cell(args.workload)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"lane_trace: needs a TPU; JAX finds {device.platform}", file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(HERE.parent / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.overhead:
        out = entry_overhead(cell, args.seed, args.overhead)
    else:
        out = measure(cell, args.seed, args.seconds, bool(args.trace), harness.load_peaks(device.device_kind))
    out["device"] = device.device_kind
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
