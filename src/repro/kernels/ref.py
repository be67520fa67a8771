"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (in
interpret mode on CPU, and on real TPU via the same assert_allclose
sweeps).  They reuse the uint32 16-bit-limb arithmetic from repro.core so
that kernel-vs-ref differences isolate *tiling/scheduling* bugs, while
the limb primitives themselves are validated against python big-ints in
tests/test_core_ntt.py.
"""
from __future__ import annotations

from repro.core import modmath as mm
from repro.core import ntt as ntt_core
from repro.core.ntt import NttContext, make_context  # re-export


def ntt_forward_ref(x, ctx: NttContext):
    """Negacyclic forward NTT over the last axis (natural in, brv out)."""
    return ntt_core.ntt_forward_jnp(x, ctx)


def ntt_inverse_ref(x, ctx: NttContext):
    """Negacyclic inverse NTT over the last axis (brv in, natural out)."""
    return ntt_core.ntt_inverse_jnp(x, ctx)


def modmul_ref(a, b, ctx: NttContext):
    """Element-wise a*b mod q."""
    return mm.mulmod_u32(a, b, ctx.q, ctx.qprime, ctx.r2_mod_q)


def polymul_ref(a, b, ctx: NttContext):
    """Negacyclic polynomial product over the last axis (eq. 1)."""
    return ntt_core.polymul_negacyclic_jnp(a, b, ctx)
