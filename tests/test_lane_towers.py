"""The kernel lane under many moduli: an RNS basis of CKKS towers.

The modulus and the twiddle tables are operands of the lane's compiled
transforms (`repro.kernels.ntt.device_tables`), so every tower of one ring
size runs the same program.  The towers are those of the CKKS configuration
of the chip benchmark (`chipbench/configs/ckks_n16.json`: CraterLake's
sixty 28-bit words at N = 2^16).  All kernels run in interpret mode here;
`tests/test_chip_compile.py` lowers two towers for a described TPU v5e and
finds one program.
"""
import functools
import json
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.ntt import make_context  # noqa: E402
from repro.he.rns import rns_primes  # noqa: E402
from repro.kernels import ntt, ops, stats  # noqa: E402

CONFIG = json.loads((Path(__file__).resolve().parents[1] / "chipbench/configs/ckks_n16.json").read_text())
TOWERS = CONFIG["moduli"]


@pytest.fixture(autouse=True)
def fresh():
    """No program and no table kept from another test: every count starts at 0."""
    jax.clear_caches()
    ntt.device_tables.cache_clear()
    ops.reset_counters()
    yield
    ops.reset_counters()


def rand(shape, q, seed):
    return np.random.default_rng([seed, q]).integers(0, q, shape).astype(np.uint32)


# ---------------------------------------------------------------------------
# a plain per-tower reference: one stage at a time over all rows, int64
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _psi_tables(q, n):
    """psi^brv(k) and psi^-brv(k), psi = g^((q-1)/2n) for g the least primitive root."""
    factors, m, d = [], q - 1, 2
    while d * d <= m:
        if m % d == 0:
            factors.append(d)
            while m % d == 0:
                m //= d
        d += 1
    factors += [m] if m > 1 else []
    g = next(g for g in range(2, q) if all(pow(g, (q - 1) // f, q) != 1 for f in factors))
    psi = pow(g, (q - 1) // (2 * n), q)
    psi_inv = pow(psi, -1, q)
    bits = n.bit_length() - 1
    brv = [int(format(i, f"0{bits}b")[::-1], 2) for i in range(n)]
    pw, pw_inv = [1] * n, [1] * n
    for k in range(1, n):
        pw[k], pw_inv[k] = pw[k - 1] * psi % q, pw_inv[k - 1] * psi_inv % q
    return np.array([pw[i] for i in brv], np.int64), np.array([pw_inv[i] for i in brv], np.int64)


def forward_ref(a, q):
    """Negacyclic NTT of each row: natural order in, bit-reversed out."""
    x = np.asarray(a, np.int64)
    rows, n = x.shape
    psi_rev, _ = _psi_tables(q, n)
    t, m = n, 1
    while m < n:
        t //= 2
        xv = x.reshape(rows, m, 2, t)
        u, v = xv[:, :, 0, :], xv[:, :, 1, :] * psi_rev[m : 2 * m][None, :, None] % q
        x = np.stack([(u + v) % q, (u - v) % q], axis=2).reshape(rows, n)
        m *= 2
    return x.astype(np.uint32)


def inverse_ref(a, q):
    """Inverse negacyclic NTT of each row: bit-reversed in, natural out, times 1/n."""
    x = np.asarray(a, np.int64)
    rows, n = x.shape
    _, psi_inv_rev = _psi_tables(q, n)
    t, m = 1, n
    while m > 1:
        h = m // 2
        xv = x.reshape(rows, h, 2, t)
        u, v = xv[:, :, 0, :], xv[:, :, 1, :]
        w = psi_inv_rev[h:m][None, :, None]
        x = np.stack([(u + v) % q, (u - v) % q * w % q], axis=2).reshape(rows, n)
        t *= 2
        m = h
    return (x * pow(n, -1, q) % q).astype(np.uint32)


# ---------------------------------------------------------------------------


def test_config_towers_are_the_28_bit_ntt_primes():
    assert CONFIG["n"] == 1 << 16 and len(TOWERS) == CONFIG["towers"] == 60
    assert TOWERS == list(rns_primes(1 << 16, 60, 28))
    assert all((q - 1) % (1 << 17) == 0 and 1 << 27 <= q < 1 << 28 for q in TOWERS)


@pytest.mark.parametrize("log_n", [8, 12, 16])
def test_towers_are_exact_against_a_plain_reference(log_n):
    n = 1 << log_n
    for q in (TOWERS[0], TOWERS[29], TOWERS[-1]):
        ctx = make_context(q, n)
        x = rand((4, n), q, log_n)
        f = np.asarray(ops.ntt(x, ctx))
        np.testing.assert_array_equal(f, forward_ref(x, q))
        np.testing.assert_array_equal(np.asarray(ops.intt(x, ctx)), inverse_ref(x, q))
        np.testing.assert_array_equal(np.asarray(ops.intt(f, ctx)), x)
    c = ops.counters()
    # three moduli, one program per direction; one table build per modulus and direction
    assert (c["ntt"]["traces"], c["intt"]["traces"]) == (1, 1)
    assert (c["ntt"]["tables"], c["intt"]["tables"]) == (3, 3)
    # n = 256 runs batch-major, with the modulus as data there too
    assert (c["ntt"]["batch_major_traces"], c["intt"]["batch_major_traces"]) == ((1, 1) if n < 1024 else (0, 0))


#: (n, tile) where the chip's plan of the fused pass differs from the one it
#: runs interpreted: a tile of 3, 5 or 6 chunk stages (4 split two and two
#: either way); None is the default tile
CHIP_PLANS = [
    (1 << 10, None), (1 << 12, None), (1 << 13, None), (1 << 14, None),
    (1 << 11, 1 << 10), (1 << 12, 1 << 10), (1 << 13, 1 << 10), (1 << 14, 1 << 12),
]


@pytest.mark.parametrize("direction", ["forward", "inverse"])
@pytest.mark.parametrize("n,tile", CHIP_PLANS)
def test_chip_plan_is_exact(monkeypatch, n, tile, direction):
    """Interpreted, the fused pass carries groups through up to 2 chunk
    stages (`ntt.INTERPRET_GROUP_BITS`); compiled for the chip, groups of up
    to 8 chunks through up to 3 (`ntt.ROWS_GROUP_BITS`).  Here the chip's
    plan runs interpreted: its sweeps, its strided groups and, with more than
    one tile, its tile offset into the chunk twiddles."""
    forward = direction == "forward"
    words = min(tile or ntt.DEFAULT_TILE, n)  # of a tile
    assert ntt._rows_plan(n, words, forward, ntt.ROWS_GROUP_BITS) != ntt._rows_plan(n, words, forward, ntt.INTERPRET_GROUP_BITS)
    monkeypatch.setattr(ntt, "INTERPRET_GROUP_BITS", ntt.ROWS_GROUP_BITS)
    q = TOWERS[7]
    ctx = make_context(q, n)
    x = rand((3, n), q, n)
    got = np.asarray((ops.ntt if forward else ops.intt)(x, ctx, tile=tile))
    np.testing.assert_array_equal(got, (forward_ref if forward else inverse_ref)(x, q))


def test_one_program_per_direction_for_every_modulus():
    n = 1 << 12
    ctxs = [make_context(q, n) for q in TOWERS[:8]]
    x = {q: rand((2, n), q, 1) for q in TOWERS[:8]}
    for ctx in ctxs:  # warm: every tower in both directions
        ops.ntt(ops.intt(x[ctx.q], ctx), ctx)
    c = ops.counters()
    for entry in ("ntt", "intt"):
        assert c[entry]["traces"] == 1 and c[entry]["batch_major_traces"] == 0
        assert c[entry]["tables"] == len(ctxs)
        forward = entry == "ntt"
        per_table = sum(a.nbytes for a in jax.tree.leaves(ntt.device_tables(ctxs[0], forward, n)))
        assert c[entry]["table_bytes"] == len(ctxs) * per_table
    assert ntt._transform._cache_size() == 2
    ops.reset_counters()
    for _ in range(2):  # a warm window: round robin over the towers
        for ctx in ctxs:
            ops.ntt(ops.intt(x[ctx.q], ctx), ctx)
    c = ops.counters()
    for entry in ("ntt", "intt"):
        assert c[entry]["calls"] == 2 * len(ctxs)
        assert c[entry]["traces"] == c[entry]["tables"] == c[entry]["table_bytes"] == 0


def test_tables_are_built_once_and_kept_on_the_device():
    ctx = make_context(TOWERS[3], 1 << 12)
    tabs = ntt.device_tables(ctx, True, 1 << 12)
    assert ntt.device_tables(ctx, True, 1 << 12) is tabs
    assert ntt.device_tables(ctx, False, 1 << 12) is not tabs
    assert all(isinstance(a, jax.Array) for a in jax.tree.leaves(tabs))
    assert [int(v) for v in tabs.scalars[: ntt.HEAD]] == [ctx.q, ctx.n_inv, ctx.n_inv_shoup]
    assert ops.counters()["ntt"]["tables"] == 1 and ops.counters()["intt"]["tables"] == 1


def test_tables_built_under_an_outer_jit_are_concrete():
    ctx = make_context(TOWERS[4], 1 << 12)
    x = rand((2, 1 << 12), ctx.q, 2)
    got = jax.jit(lambda a: ntt.ntt_pallas(a, ctx))(x)  # builds the tables while tracing
    assert all(isinstance(a, jax.Array) for a in jax.tree.leaves(ntt.device_tables(ctx, True, 1 << 12)))
    np.testing.assert_array_equal(np.asarray(got), forward_ref(x, ctx.q))


def test_mldsa_modulus_stays_exact():
    q, n = 8380417, 256
    ctx = make_context(q, n)
    x = rand((13, n), q, 3)
    f = np.asarray(ops.ntt(x, ctx))
    np.testing.assert_array_equal(f, forward_ref(x, q))
    np.testing.assert_array_equal(np.asarray(ops.intt(x, ctx)), inverse_ref(x, q))
    assert ops.counters()["ntt"]["batch_major_traces"] == 1


def _host_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = Path(log_dir).glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [
        (e.name, e.start_ns, e.end_ns, dict(e.stats))
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    ]


def test_table_build_is_spanned_inside_its_entry(tmp_path):
    ctx = make_context(TOWERS[5], 1 << 12)
    x = rand((2, 1 << 12), ctx.q, 4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ops.ntt(x, ctx).block_until_ready()  # first use: builds the tables
        ops.ntt(x, ctx).block_until_ready()  # warm: builds nothing
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (build,) = [e for e in events if e[0] == stats.PREFIX + "tables"]
    nbytes = ops.counters()["ntt"]["table_bytes"]
    assert build[3] == {"q": ctx.q, "n": 1 << 12, "bytes": nbytes} and nbytes > 0
    first = min((e for e in events if e[0] == stats.PREFIX + "ntt"), key=lambda e: e[1])
    assert first[1] <= build[1] and build[2] <= first[2]
