"""Property twin for `repro.he`: towers x N x banks sweeps.

Every drawn configuration must (a) stay bit-exact against the
big-integer CRT oracles and (b) obey the timing invariants of the
tower->bank gang model (speedup bounded by banks, single-bank baseline
burst-free, phase durations summing below the makespan's span).
"""
import random

import numpy as np

import repro.he as he
from hypo import given, settings, st
from repro.core.pim_config import PimConfig
from repro.pimsys import PimSession

CFG = PimConfig(num_channels=2, num_banks=2, param_cache_entries=4)
SESS = PimSession(CFG)  # shared across examples: plan-cache reuse

ns = st.sampled_from([16, 32, 64])
towers = st.integers(min_value=1, max_value=5)
banks = st.integers(min_value=1, max_value=4)
seeds = st.integers(min_value=0, max_value=2 ** 16)


@settings(max_examples=25)
@given(n=ns, big_l=towers, seed=seeds)
def test_crt_roundtrip_and_ct_mul_exact(n, big_l, seed):
    basis = he.make_basis(n, big_l)
    rng = random.Random(seed)  # Python ints: the modulus passes int64 at L >= 3
    coeffs = [rng.randrange(basis.modulus) for _ in range(n)]
    assert basis.decode(basis.encode(coeffs)) == coeffs
    a, b = he.random_ct(basis, seed), he.random_ct(basis, seed + 1)
    assert np.array_equal(he.ct_mul(basis, a, b),
                          he.ct_mul_reference(basis, a, b))


@settings(max_examples=15)
@given(n=ns, big_l=towers, seed=seeds)
def test_keyswitch_and_rescale_exact(n, big_l, seed):
    basis = he.make_basis(n, big_l)
    s = he.make_secret(basis, seed)
    rlk = he.relin_key(basis, s, seed=seed + 1)
    c2 = he.random_poly(basis, seed + 2)
    assert np.array_equal(he.keyswitch(basis, c2, rlk),
                          he.keyswitch_reference(basis, c2, rlk))
    if big_l >= 2:
        ct = he.random_ct(basis, seed + 3)
        assert np.array_equal(he.rescale(basis, ct),
                              he.rescale_reference(basis, ct))


@settings(max_examples=15)
@given(n=ns, big_l=towers, b=banks, seed=seeds)
def test_device_plan_invariants(n, big_l, b, seed):
    b = min(b, CFG.num_channels * CFG.num_banks)
    op = he.RlweCtMulOp(n=n, towers=big_l, banks=b)
    plan = SESS.compile(op)
    assert SESS.compile(op) is plan  # memoized under the sweep
    basis = he.basis_for(op)
    a, c = he.random_ct(basis, seed), he.random_ct(basis, seed + 1)
    r = SESS.run(plan, a, c)
    assert np.array_equal(r.value, he.ct_mul_reference(basis, a, c))
    t = r.timing
    assert t.banks == b
    assert t.latency_ns > 0
    assert t.latency_ns <= t.single_ns + 1e-9
    # Mildly superlinear speedup is legitimate: the one-bank baseline
    # walks every tower's programs through one param LRU (capacity
    # thrash) while dedicated banks keep theirs resident.
    assert 0 < t.speedup <= 1.5 * b
    assert 0 < t.efficiency <= 1.5
    assert t.xfer_atoms == 0  # ct_mul never moves data between banks
    assert len(t.tower_done_ns) == big_l
    assert max(t.tower_done_ns) <= t.latency_ns + 1e-9
    assert set(t.phase_ns) == {"fwd", "pointwise", "inv"}
    assert all(v >= 0 for v in t.phase_ns.values())


@settings(max_examples=10)
@given(n=ns, big_l=st.integers(min_value=2, max_value=5), b=banks,
       seed=seeds)
def test_keyswitch_device_invariants(n, big_l, b, seed):
    b = min(b, CFG.num_channels * CFG.num_banks)
    op = he.KeySwitchOp(n=n, towers=big_l, banks=b)
    plan = SESS.compile(op)
    basis = he.basis_for(op)
    rlk = he.relin_key(basis, he.make_secret(basis, seed), seed=seed + 1)
    c2 = he.random_poly(basis, seed + 2)
    r = SESS.run(plan, c2, rlk)
    assert np.array_equal(r.value, he.keyswitch_reference(basis, c2, rlk))
    t = r.timing
    if b == 1 or big_l == 1:
        assert t.xfer_atoms == 0
    else:
        # each tower broadcasts one poly to every *other* reserved bank
        atoms_per_poly = max(1, n // CFG.atom_words)
        reserved = min(b, big_l)
        assert t.xfer_atoms == big_l * (reserved - 1) * atoms_per_poly
    assert t.phase_ns["base_extend"] >= 0
