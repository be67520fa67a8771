"""The kernel lane's host spans and counters (`repro.kernels.stats`).

The spans are read back from a real profiler trace on the CPU; the kernel
names and named scopes are checked in `tests/test_chip_compile.py`, on a
compile for a described TPU v5e."""
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.ntt import make_context  # noqa: E402
from repro.kernels import ops, stats  # noqa: E402

Q = 114689  # 7 * 2^14 + 1: a modulus no other test uses


def rand(shape, q=Q):
    return np.random.default_rng(7).integers(0, q, shape).astype(np.uint32)


@pytest.fixture(autouse=True)
def fresh():
    ops.reset_counters()
    yield
    ops.reset_counters()


def _host_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [
        (line.name, e.name, e.start_ns, e.end_ns, dict(e.stats))
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]


def test_polymul_span_holds_its_transforms(tmp_path):
    ctx = make_context(Q, 256)
    a, b = rand((3, 256)), rand((3, 256))
    ops.polymul_ntt(a, b, ctx).block_until_ready()  # compiled outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ops.polymul_ntt(a, b, ctx).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    lane = [ev for ev in _host_events(tmp_path) if ev[1].startswith("lane.")]
    (outer,) = [ev for ev in lane if ev[1] == "lane.polymul_ntt"]
    inner = sorted((ev for ev in lane if ev is not outer), key=lambda ev: ev[2])
    assert [ev[1] for ev in inner] == ["lane.ntt", "lane.ntt", "lane.intt"]
    for line, _, start, end, args in inner:
        assert line == outer[0] and outer[2] <= start and end <= outer[3]
        assert args == {"rows": 3, "n": 256}
    assert outer[4] == {"rows": 3, "n": 256}


def test_counters_count_calls_rows_and_host_time():
    ctx = make_context(Q, 256)
    x = rand((4, 256))

    def calls():
        ops.ntt(x, ctx)
        ops.ntt(x[:2], ctx)
        ops.intt(x, ctx)
        ops.polymul_ntt(x, x, ctx)

    calls()  # compiles
    ops.reset_counters()
    calls()
    c = ops.counters()
    assert set(c) == {"ntt", "intt", "polymul_ntt"}
    # the product's own transforms count under their entries too
    assert (c["ntt"]["calls"], c["ntt"]["rows"]) == (4, 4 + 2 + 4 + 4)
    assert (c["intt"]["calls"], c["intt"]["rows"]) == (2, 8)
    assert (c["polymul_ntt"]["calls"], c["polymul_ntt"]["rows"]) == (1, 4)
    for entry in c.values():
        assert set(entry) == set(stats.FIELDS)
        assert 0 < entry["host_ns_max"] <= entry["host_ns"]
        assert entry["over_50ms"] == entry["traces"] == 0


def test_a_trace_is_counted_once_per_new_shape():
    ctx = make_context(Q, 512)
    jax.clear_caches()
    x = rand((5, 512))
    for _ in range(3):
        ops.ntt(x, ctx)
    assert ops.counters()["ntt"]["traces"] == 1
    ops.ntt(rand((6, 512)), ctx)  # a new shape traces again
    ops.ntt(x, ctx, batch_block=1)  # so does a new static argument
    ops.ntt(x, ctx)
    c = ops.counters()
    assert c["ntt"]["traces"] == 3 and c["ntt"]["calls"] == 6
    assert "intt" not in c  # nothing traced or called under it


def test_batch_major_traces_count_small_n_programs():
    jax.clear_caches()
    ops.ntt(rand((2, 4096)), make_context(Q, 4096))  # the slab layout
    assert ops.counters()["ntt"]["traces"] == 1
    assert ops.counters()["ntt"]["batch_major_traces"] == 0
    ctx = make_context(Q, 256)
    for shape in [(4, 256), (4, 256), (9, 256)]:
        ops.ntt(rand(shape), ctx)
    ops.intt(rand((4, 128)), make_context(Q, 128))
    c = ops.counters()
    assert (c["ntt"]["traces"], c["ntt"]["batch_major_traces"]) == (3, 2)
    assert (c["intt"]["traces"], c["intt"]["batch_major_traces"]) == (1, 1)


def test_batch_major_tile_traces_count_slab_path_programs():
    jax.clear_caches()
    ctx = make_context(Q, 4096)
    for shape in [(2, 4096), (2, 4096), (3, 4096)]:
        ops.ntt(rand(shape), ctx)
    ops.ntt(rand((2, 4096)), ctx, tile=1024)  # tiles of a longer transform
    ops.ntt(rand((2, 512)), make_context(Q, 512), tile=256)  # so below n = 1024
    ops.intt(rand((2, 4096)), ctx)
    c = ops.counters()
    assert (c["ntt"]["traces"], c["ntt"]["batch_major_tile_traces"]) == (4, 4)
    assert (c["intt"]["traces"], c["intt"]["batch_major_tile_traces"]) == (1, 1)
    assert c["ntt"]["batch_major_traces"] == c["intt"]["batch_major_traces"] == 0


def test_batch_major_tile_traces_stay_0_for_whole_small_transforms():
    jax.clear_caches()
    for n in (128, 256, 512):
        ctx = make_context(Q, n)
        ops.intt(ops.ntt(rand((3, n)), ctx), ctx)
    c = ops.counters()
    for entry in ("ntt", "intt"):
        assert (c[entry]["traces"], c[entry]["batch_major_traces"]) == (3, 3)
        assert c[entry]["batch_major_tile_traces"] == 0


def test_batch_major_tile_traces_stay_put_in_a_warm_loop_over_towers():
    from repro.he.rns import rns_primes

    jax.clear_caches()
    n = 4096
    ctxs = [make_context(q, n) for q in rns_primes(n, 4, 28)]
    x = rand((2, n), min(ctx.q for ctx in ctxs))
    for ctx in ctxs:  # warm: every tower in both directions
        ops.intt(ops.ntt(x, ctx), ctx)
    c = ops.counters()
    assert c["ntt"]["batch_major_tile_traces"] == c["intt"]["batch_major_tile_traces"] == 1
    ops.reset_counters()
    for _ in range(2):
        for ctx in ctxs:
            ops.intt(ops.ntt(x, ctx), ctx)
    c = ops.counters()
    assert c["ntt"]["calls"] == c["intt"]["calls"] == 2 * len(ctxs)
    assert c["ntt"]["batch_major_tile_traces"] == c["intt"]["batch_major_tile_traces"] == 0


def test_slow_calls_are_counted(monkeypatch):
    monkeypatch.setattr(stats, "SLOW_NS", 0)
    ctx = make_context(Q, 256)
    ops.ntt(rand((2, 256)), ctx)
    ops.ntt(rand((2, 256)), ctx)
    assert ops.counters()["ntt"]["over_50ms"] == 2


def test_reset_and_snapshot():
    ctx = make_context(Q, 256)
    ops.intt(rand((2, 256)), ctx)
    snap = ops.counters()
    snap["intt"]["calls"] = 99  # a snapshot, not the live counts
    assert ops.counters()["intt"]["calls"] == 1
    ops.reset_counters()
    assert ops.counters() == {}


def test_counters_lose_no_update_across_threads():
    x = np.zeros((2, 256), np.uint32)
    threads, per_thread = 16, 500

    def nothing(x):
        return x

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                stats.call("stress", nothing, x, 256)

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    c = ops.counters()["stress"]
    assert (c["calls"], c["rows"]) == (threads * per_thread, 2 * threads * per_thread)
