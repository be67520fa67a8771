"""Plain negacyclic NTT over Z_q[X]/(X^n + 1): the benchmark's yardstick.

Written from the textbook (Longa and Naehrig, "Speeding up the Number
Theoretic Transform for Faster Ideal Lattice-Based Cryptography",
Algorithms 1 and 2) in numpy int64, one stage at a time over all rows.
It imports nothing of the program under test, so no change to the program
can move it.

The transform is the one the configurations state:
  * psi = g^((q-1)/(2n)) mod q, with g the least primitive root mod q;
  * forward: natural order in, bit-reversed order out,
      out[i] = sum_j a[j] * psi^((2*brv(i) + 1) * j)  mod q;
  * inverse: bit-reversed in, natural out, scaled by 1/n;
  * every output word is the canonical residue in [0, q).

The `lazy` flag gives the benchmark's control: the same transforms with the
last reduction left out, the step a faster implementation is tempted to
drop (outputs congruent, but in [0, 2q) instead of [0, q)).
"""
from __future__ import annotations

import functools

import numpy as np


def _is_prime(q: int) -> bool:
    if q < 2:
        return False
    d = 2
    while d * d <= q:
        if q % d == 0:
            return False
        d += 1
    return True


def _prime_factors(m: int) -> list[int]:
    out, d = [], 2
    while d * d <= m:
        if m % d == 0:
            out.append(d)
            while m % d == 0:
                m //= d
        d += 1
    if m > 1:
        out.append(m)
    return out


@functools.lru_cache(maxsize=None)
def psi(q: int, n: int) -> int:
    """The primitive 2n-th root of unity the transform is defined with."""
    if not _is_prime(q) or (q - 1) % (2 * n):
        raise ValueError(f"q={q} is not a prime with 2n={2 * n} dividing q-1")
    factors = _prime_factors(q - 1)
    g = next(g for g in range(2, q) if all(pow(g, (q - 1) // f, q) != 1 for f in factors))
    return pow(g, (q - 1) // (2 * n), q)


def bit_reverse(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    return np.array([int(format(i, f"0{bits}b")[::-1], 2) if bits else 0 for i in range(n)])


@functools.lru_cache(maxsize=None)
def _tables(q: int, n: int):
    """psi^brv(k) and psi^-brv(k) for k < n, as int64."""
    w = psi(q, n)
    w_inv = pow(w, -1, q)
    pw = np.empty(n, np.int64)
    pw_inv = np.empty(n, np.int64)
    a = b = 1
    for k in range(n):
        pw[k], pw_inv[k] = a, b
        a, b = a * w % q, b * w_inv % q
    brv = bit_reverse(n)
    return pw[brv], pw_inv[brv]


def _rows(a, q: int) -> np.ndarray:
    a = np.asarray(a)
    if a.ndim != 2:
        raise ValueError(f"expected (rows, n), got shape {a.shape}")
    if a.size and (a.min() < 0 or a.max() >= q):
        raise ValueError("input words must lie in [0, q)")
    return a.astype(np.int64)


def forward(a, q: int, lazy: bool = False) -> np.ndarray:
    """Negacyclic NTT of each row: natural order in, bit-reversed out."""
    x = _rows(a, q)
    rows, n = x.shape
    psi_rev, _ = _tables(q, n)
    t, m = n, 1
    while m < n:
        t //= 2
        last = lazy and m * 2 == n
        xv = x.reshape(rows, m, 2, t)
        u = xv[:, :, 0, :]
        v = xv[:, :, 1, :] * psi_rev[m : 2 * m][None, :, None] % q
        top = u + v if last else (u + v) % q
        bottom = u - v + q if last else (u - v) % q
        x = np.stack([top, bottom], axis=2).reshape(rows, n)
        m *= 2
    return x.astype(np.uint32)


def inverse(a, q: int, lazy: bool = False) -> np.ndarray:
    """Inverse negacyclic NTT of each row: bit-reversed in, natural out, times 1/n."""
    x = _rows(a, q)
    rows, n = x.shape
    _, psi_inv_rev = _tables(q, n)
    n_inv = pow(n, -1, q)
    t, m = 1, n
    while m > 1:
        h = m // 2
        xv = x.reshape(rows, h, 2, t)
        u, v = xv[:, :, 0, :], xv[:, :, 1, :]
        w = psi_inv_rev[h:m][None, :, None]
        if lazy and h == 1:
            # the last stage with 1/n folded into it and its products left
            # in [0, 2q), as lazy implementations do
            top = _montgomery_lazy((u + v) % q, n_inv, q)
            bottom = _montgomery_lazy((u - v) % q, int(w[0, 0, 0]) * n_inv % q, q)
        else:
            top = (u + v) % q
            bottom = (u - v) % q * w % q
        x = np.stack([top, bottom], axis=2).reshape(rows, n)
        t *= 2
        m = h
    return (x if lazy else x * n_inv % q).astype(np.uint32)


def _montgomery_lazy(x, w: int, q: int):
    """x*w mod q by one Montgomery product (R = 2^32) without its last
    subtraction: congruent, but in [0, 2q)."""
    t = x.astype(np.uint64) * np.uint64((w << 32) % q)  # < q^2 < 2^62
    q_neg_inv = np.uint64(-pow(q, -1, 1 << 32) % (1 << 32))
    low = np.uint64(0xFFFFFFFF)
    m = ((t & low) * q_neg_inv) & low  # the product wraps mod 2^64: low bits exact
    return ((t + m * np.uint64(q)) >> np.uint64(32)).astype(np.int64)  # sum < 2^64


def polymul(a, b, q: int, lazy: bool = False) -> np.ndarray:
    """Row-wise a*b in Z_q[X]/(X^n + 1) through the two transforms."""
    prod = forward(a, q).astype(np.int64) * forward(b, q).astype(np.int64) % q
    return inverse(prod, q, lazy=lazy)


TRANSFORMS = {"ntt": forward, "intt": inverse, "polymul_ntt": polymul}
