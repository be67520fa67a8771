"""From a profiler trace to what the per-layer metrics read.

`load` takes the two things the benchmark uses out of an `.xplane.pb`:
  * the device operations, per TPU device plane, from its "XLA Ops" line
    (one event per HLO operation the device ran: kernels, fusions, copies);
  * the harness's own host spans (`chipbench.*`, written with
    `jax.profiler.TraceAnnotation`), from any host line.
Both are on the profiler's one clock, in nanoseconds.  The result is plain
data, so a small trace can be written by hand and kept as a test.

`reduce` cuts that to the steady window (the span the harness opened around
it) and gives the device's busy time (the union of its op intervals), the op
count, each op's time under a short label, and the idle gaps, each put down
to the host span that covers its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from pathlib import Path

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "chipbench."
# "%ntt_pallas.7 = u32[64,8,64,128]{3,2,1,0:T(8,128)S(1)} custom-call(..." ->
# "ntt_pallas.7 u32[64,8,64,128] custom-call"
_NAME = re.compile(r"^%?([\w.\-]+) = ")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")
_KIND = re.compile(r"[}\])] ([\w\-]+)\(")


def find_xplane(log_dir) -> Path:
    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path) -> dict:
    """{"device": {plane: [[label, start_ns, end_ns], ...]}, "host": [[span, start_ns, end_ns], ...]}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([op_label(e.name), e.start_ns, e.end_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, e.start_ns, e.end_ns] for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    return {"device": device, "host": host}


def op_label(hlo: str) -> str:
    """A short name for a device op: its HLO name, result shape and kind."""
    name, kind = _NAME.match(hlo), _KIND.search(hlo)
    if not (name and kind):
        return hlo[:120]
    shape = _SHAPE.search(hlo, name.end())
    return f"{name[1]} {shape[0] if shape else '?'} {kind[1]}"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float | None  # averaged over device planes; None when there are none
    device_ops: int
    op_s: dict  # label -> seconds, summed over planes
    idle_s: dict  # covering host span -> seconds of idle device, summed over planes

    def top_ops(self, k: int) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]

    def top_idle(self, k: int) -> list:
        return sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:k]


def _union(intervals) -> list:
    merged: list = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def reduce(trace: dict, steady: str) -> Reduced:
    """Busy time, op count and idle attribution inside the host span `steady`."""
    windows = [(s, e) for name, s, e in trace["host"] if name == steady]
    if len(windows) != 1:
        raise ValueError(f"expected one {steady!r} span in the trace, found {len(windows)}")
    w0, w1 = windows[0]
    spans = sorted((s, e, name[len(SPAN_PREFIX):]) for name, s, e in trace["host"] if name != steady)
    starts = [s for s, _, _ in spans]
    busy, count, op_s, idle_s = [], 0, {}, {}
    for ops in trace["device"].values():
        inside = [(max(s, w0), min(e, w1), label) for label, s, e in ops if s < w1 and e > w0]
        count += sum(1 for label, s, e in ops if w0 <= s < w1)
        for s, e, label in inside:
            op_s[label] = op_s.get(label, 0.0) + (e - s) * 1e-9
        merged = _union((s, e) for s, e, _ in inside)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                label = _covering(spans, starts, (lo + hi) / 2)
                idle_s[label] = idle_s.get(label, 0.0) + (hi - lo) * 1e-9
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=sum(busy) / len(busy) if busy else None,
        device_ops=count,
        op_s=op_s,
        idle_s=idle_s,
    )


def _covering(spans, starts, t) -> str:
    """The harness span around time t, or "none".  The harness's spans follow
    one another on one thread, so the last one to start before t is the only
    candidate."""
    i = bisect.bisect_right(starts, t) - 1
    return spans[i][2] if i >= 0 and spans[i][1] >= t else "none"
