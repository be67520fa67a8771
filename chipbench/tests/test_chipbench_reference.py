"""The yardstick: `reference.py` against the transform's definition, and in
agreement with the program's own host reference (`repro.core.ntt`) at the
benchmark's rings, so a wrong yardstick shows before chip time is spent."""
import numpy as np
import pytest

import chipbench_testing  # noqa: F401  (puts the benchmark on the path)
import reference  # noqa: E402

MLDSA_Q = 8380417
#: The largest prime k * 2^17 + 1 below 2^31: a 31-bit modulus with a
#: negacyclic NTT at n = 65536, the widest word the lane holds.
Q31 = 2147352577


def _definition(a, q, n):
    """out[i] = sum_j a[j] psi^((2 brv(i) + 1) j) mod q, by Python ints."""
    psi = reference.psi(q, n)
    brv = reference.bit_reverse(n)
    return np.array([
        [sum(int(a[r, j]) * pow(psi, (2 * int(brv[i]) + 1) * j, q) for j in range(n)) % q for i in range(n)]
        for r in range(a.shape[0])
    ])


@pytest.mark.parametrize("q, n", [(MLDSA_Q, 16), (Q31, 32), (17, 8)])
def test_forward_is_the_definition_and_inverse_undoes_it(q, n):
    a = np.random.default_rng(n).integers(0, q, (2, n))
    out = reference.forward(a, q)
    assert np.array_equal(out, _definition(a, q, n))
    assert np.array_equal(reference.inverse(out, q), a)


def test_polymul_is_the_negacyclic_product():
    q, n = MLDSA_Q, 32
    rng = np.random.default_rng(3)
    a, b = rng.integers(0, q, (1, n)), rng.integers(0, q, (1, n))
    want = np.zeros(n, dtype=object)
    for i in range(n):
        for j in range(n):
            k, sign = (i + j) % n, (-1 if i + j >= n else 1)
            want[k] += sign * int(a[0, i]) * int(b[0, j])
    assert np.array_equal(reference.polymul(a, b, q)[0], np.array([w % q for w in want]))


@pytest.mark.parametrize("q, n, rows", [(MLDSA_Q, 256, 8), (Q31, 65536, 2)],
                         ids=["mldsa65", "q31_n65536"])
def test_agrees_with_core_ntt(q, n, rows):
    from repro.core import ntt as core

    ctx = core.make_context(q, n)
    a = np.random.default_rng(rows).integers(0, q, (rows, n)).astype(np.uint32)
    assert reference.psi(q, n) == ctx.psi
    assert np.array_equal(reference.forward(a, q), core.ntt_forward_np(a, ctx))
    assert np.array_equal(reference.inverse(a, q), core.ntt_inverse_np(a, ctx))


@pytest.mark.parametrize("fn", ["ntt", "intt"])
def test_control_is_congruent_but_not_canonical(fn):
    q, n = Q31, 1024
    a = np.random.default_rng(5).integers(0, q, (4, n))
    exact = reference.TRANSFORMS[fn](a, q)
    lazy = reference.TRANSFORMS[fn](a, q, lazy=True)
    assert np.array_equal(lazy.astype(np.int64) % q, exact)
    assert lazy.max() < 2 * q and np.sum(lazy != exact) > 0


def test_inputs_out_of_range_are_refused():
    with pytest.raises(ValueError, match="in \\[0, q\\)"):
        reference.forward(np.full((1, 8), 17), 17)
    with pytest.raises(ValueError, match="not a prime"):
        reference.psi(15, 4)
