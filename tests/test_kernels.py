"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

All kernels run in interpret mode on the CPU backend.  The same kernels
are compiled for a described TPU v5e in `tests/test_chip_compile.py`, and
run on the chip by `chip_smoke.py`."""
import numpy as np
import pytest
from hypo import given, settings, st

from repro.core import modmath as mm
from repro.core.ntt import make_context, schoolbook_negacyclic
from repro.kernels import ops, ref
from repro.kernels.modmul import modmul_pallas
from repro.kernels.ntt import ntt_pallas

Q = mm.DEFAULT_Q
RNG = np.random.default_rng(42)


def rand(shape, q=Q, rng=RNG):
    return rng.integers(0, q, shape).astype(np.uint32)


# ---------------------------------------------------------------------------
# shape sweep: fused-full and two-regime paths, both directions
# ---------------------------------------------------------------------------

SHAPES = [
    # (batch, n, tile, batch_block)
    (1, 256, None, None),
    (3, 512, None, 2),
    (8, 1024, None, 8),
    (5, 4096, None, 4),     # odd batch -> padding path
    (2, 4096, 512, None),   # two-regime
    (4, 8192, 1024, 2),
    (1, 16384, 2048, None),
    (2, 16384, 4096, 2),
    # batch-major path (a whole transform of n < 1024 words): batches that
    # are not a multiple of the block, an explicit block below 8 rows
    (13, 128, None, None),
    (700, 128, None, 3),
    (13, 256, None, None),
    (700, 256, None, None),
    (1, 512, None, None),
    (700, 512, None, 5),
    # the batch-major fused pass on a tile of a longer transform: a batch
    # that is not a multiple of the block, an explicit block below 8 rows,
    # a single row
    (13, 8192, 1024, None),
    (5, 16384, 2048, 3),
    (1, 4096, 512, None),
]


@pytest.mark.parametrize("batch,n,tile,bb", SHAPES)
@pytest.mark.parametrize("forward", [True, False])
def test_ntt_kernel_matches_ref(batch, n, tile, bb, forward):
    ctx = make_context(Q, n)
    x = rand((batch, n))
    got = np.asarray(ntt_pallas(x, ctx, forward=forward, tile=tile, batch_block=bb))
    exp_fn = ref.ntt_forward_ref if forward else ref.ntt_inverse_ref
    exp = np.asarray(exp_fn(x, ctx))
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("n,tile", [(128, None), (256, None), (512, None), (1024, None), (8192, 1024)])
def test_ntt_kernel_roundtrip(n, tile):
    ctx = make_context(Q, n)
    x = rand((3, n))
    f = ntt_pallas(x, ctx, forward=True, tile=tile)
    back = np.asarray(ntt_pallas(f, ctx, forward=False, tile=tile))
    np.testing.assert_array_equal(back, x)


@pytest.mark.parametrize("n,tile", [(1024, 192), (1024, 384), (64, None)])
def test_ntt_kernel_rejects_partial_lane_rows(n, tile):
    ctx = make_context(Q, n)
    with pytest.raises(ValueError, match="multiple of 128 words"):
        ntt_pallas(rand((1, n)), ctx, tile=tile)


def test_resolve_interpret_by_backend(monkeypatch):
    import jax

    from repro.kernels.ntt import resolve_interpret

    assert resolve_interpret(None) is True  # the tests run on the CPU backend
    assert resolve_interpret(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        resolve_interpret(None)


def test_ntt_kernel_1d_input():
    ctx = make_context(Q, 512)
    x = rand(512)
    got = np.asarray(ntt_pallas(x, ctx, forward=True))
    exp = np.asarray(ref.ntt_forward_ref(x, ctx))
    np.testing.assert_array_equal(got, exp)


# -- alternative modulus (dtype/parameter sweep: q is the "dtype" here) ------


@pytest.mark.parametrize("q", [998244353, 469762049, mm.find_ntt_prime(2**15, bits=30)])
def test_ntt_kernel_other_primes(q):
    n = 1024
    ctx = make_context(q, n)
    x = rand((2, n), q=q)
    got = np.asarray(ntt_pallas(x, ctx, forward=True))
    exp = np.asarray(ref.ntt_forward_ref(x, ctx))
    np.testing.assert_array_equal(got, exp)
    back = np.asarray(ntt_pallas(got, ctx, forward=False))
    np.testing.assert_array_equal(back, x)


# ---------------------------------------------------------------------------
# modmul kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(17,), (2, 1000), (3, 4, 256), (1, 65536)])
def test_modmul_matches_ref(shape):
    ctx = make_context(Q, 256)
    a, b = rand(shape), rand(shape)
    got = np.asarray(modmul_pallas(a, b, ctx))
    exp = np.asarray(ref.modmul_ref(a, b, ctx))
    np.testing.assert_array_equal(got, exp)
    exact = (a.astype(object) * b.astype(object)) % Q
    np.testing.assert_array_equal(got.astype(object), exact)


# ---------------------------------------------------------------------------
# composed ops
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [256, 2048])
def test_polymul_ntt_vs_schoolbook(n):
    ctx = make_context(Q, n)
    a, b = rand(n), rand(n)
    got = np.asarray(ops.polymul_ntt(a, b, ctx))
    np.testing.assert_array_equal(got, schoolbook_negacyclic(a, b, Q))


def test_polymul_batched():
    n = 512
    ctx = make_context(Q, n)
    a, b = rand((4, n)), rand((4, n))
    got = np.asarray(ops.polymul_ntt(a, b, ctx))
    for i in range(4):
        np.testing.assert_array_equal(got[i], schoolbook_negacyclic(a[i], b[i], Q))


def test_ntt_conv_fixedpoint_close_to_direct():
    n = 256
    ctx = make_context(Q, n)
    rng = np.random.default_rng(3)
    u = rng.standard_normal(n).astype(np.float32)
    k = (rng.standard_normal(n) * 0.1).astype(np.float32)
    got = np.asarray(ops.ntt_conv_fixedpoint(u, k, ctx, frac_bits=10))
    # direct negacyclic conv in float64
    direct = np.zeros(n)
    for i in range(n):
        for j in range(n):
            idx = (i + j) % n
            sign = 1.0 if i + j < n else -1.0
            direct[idx] += sign * float(u[i]) * float(k[j])
    np.testing.assert_allclose(got, direct, atol=0.05, rtol=0.01)


# ---------------------------------------------------------------------------
# property-based: kernel respects transform algebra
# ---------------------------------------------------------------------------


@given(st.sampled_from([256, 1024]), st.integers(0, 2**31 - 1))
@settings(max_examples=10)
def test_kernel_linearity(n, seed):
    rng = np.random.default_rng(seed)
    ctx = make_context(Q, n)
    a = rng.integers(0, Q, (1, n)).astype(np.uint32)
    b = rng.integers(0, Q, (1, n)).astype(np.uint32)
    fa = np.asarray(ntt_pallas(a, ctx)).astype(np.int64)
    fb = np.asarray(ntt_pallas(b, ctx)).astype(np.int64)
    ab = ((a.astype(np.int64) + b) % Q).astype(np.uint32)
    fab = np.asarray(ntt_pallas(ab, ctx)).astype(np.int64)
    np.testing.assert_array_equal(fab, (fa + fb) % Q)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10)
def test_kernel_delta_transform(seed):
    """NTT(delta_0) = all-ones (psi^0 * w^0 = 1 in every output)."""
    n = 512
    ctx = make_context(Q, n)
    delta = np.zeros((1, n), np.uint32)
    delta[0, 0] = 1
    out = np.asarray(ntt_pallas(delta, ctx))
    np.testing.assert_array_equal(out, np.ones((1, n), np.uint32))


# ---------------------------------------------------------------------------
# NttBackend: the unified {reference, pim-sim, pallas} differential
# ---------------------------------------------------------------------------


@given(st.sampled_from([256, 1024]), st.booleans(), st.integers(0, 2**31 - 1))
@settings(max_examples=8)
def test_backend_differential_property(n, forward, seed):
    """Random inputs, both directions: every available backend agrees
    BIT-exactly with the reference.  `tests/test_backend.py` is the
    deterministic twin that runs even without hypothesis."""
    from repro.kernels.backend import available_backends, get_backend

    x = np.random.default_rng(seed).integers(0, Q, (2, n)).astype(np.uint32)
    exp = get_backend("reference").ntt(x, forward=forward)
    for b in available_backends():
        assert np.array_equal(b.ntt(x, forward=forward), exp), b.name
