"""Public jit'd API over the Pallas kernels.

  ntt / intt           batched negacyclic NTT (forward: natural->brv,
                       inverse: brv->natural, 1/N folded in)
  polymul_ntt          a*b in Z_q[X]/(X^N+1), eq. (1) of the paper — no
                       bit-reversal anywhere (element-wise NTT domain)
  ntt_conv             integer negacyclic convolution (sequence-mixing
                       primitive for the LM stack; exact, O(N log N))
  ntt_conv_fixedpoint  float sequences via fixed-point lift, exact
                       integer convolution, and un-lift

Batching across independent transforms == the paper's bank-level
parallelism (the batch grid axis of each kernel).  The ops run on one
device; nothing here shards them across chips.

`ntt` and `intt` take the modulus as data: a context's modulus, 1/N and
twiddle tables are built once, placed on the device and cached
(`kernels.ntt.device_tables`), and are operands of one compiled transform
per ring size, direction and shape, so the towers of an RNS basis share
their programs.  The product's pointwise step still compiles per context.

`ntt`, `intt` and `polymul_ntt` each run inside a host span `lane.<name>`
and count their calls; a context's table build runs inside `lane.tables`
and is counted under its direction's entry; `counters()` and
`reset_counters()` read and zero the counts (`repro.kernels.stats`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ntt import NttContext, make_context  # re-export for users
from repro.kernels import stats
from repro.kernels.modmul import modmul_pallas
from repro.kernels.ntt import ntt_pallas
from repro.kernels.stats import counters, reset_counters  # noqa: F401


def ntt(x, ctx: NttContext, **kw):
    """Forward negacyclic NTT over the last axis (natural in, brv out)."""
    return stats.call("ntt", ntt_pallas, x, ctx.n, ctx, forward=True, **kw)


def intt(x, ctx: NttContext, **kw):
    """Inverse negacyclic NTT over the last axis (brv in, natural out, /N)."""
    return stats.call("intt", ntt_pallas, x, ctx.n, ctx, forward=False, **kw)


def polymul_ntt(a, b, ctx: NttContext, **kw):
    """a*b mod (X^N + 1): NTT -> element-wise modmul -> INTT."""
    return stats.call("polymul_ntt", _polymul, a, ctx.n, b, ctx, **kw)


def _polymul(a, b, ctx, **kw):
    ah = ntt(a, ctx, **kw)
    bh = ntt(b, ctx, **kw)
    prod = _pointwise(ah, bh, ctx, interpret=kw.get("interpret"))
    return intt(prod, ctx, **kw)


@functools.partial(jax.jit, static_argnames=("ctx", "interpret"))
def _pointwise(ah, bh, ctx: NttContext, interpret: bool | None):
    """The NTT-domain product of `polymul_ntt`, under its scope."""
    with stats.scope("polymul_ntt"):
        return modmul_pallas(ah, bh, ctx, interpret=interpret)


def ntt_conv(u, k, ctx: NttContext, **kw):
    """Exact negacyclic convolution of uint32 sequences in [0, q)."""
    return polymul_ntt(jnp.asarray(u, jnp.uint32), jnp.asarray(k, jnp.uint32), ctx, **kw)


@functools.partial(jax.jit, static_argnames=("ctx", "frac_bits", "interpret"))
def ntt_conv_fixedpoint(u, k, ctx: NttContext, frac_bits: int = 10, interpret: bool | None = None):
    """Negacyclic convolution of float sequences via fixed-point lift.

    Values are scaled by 2^frac_bits, rounded, lifted to [0, q) (negatives
    as q - |x|), convolved exactly over Z_q, and mapped back assuming the
    true result magnitude < q / 2^(2*frac_bits + 1).  This makes the NTT
    engine usable as an *exact* long-convolution mixer for sequence
    models (no FFT rounding error), the framework's point of contact
    between the paper's kernel and the LM stack.
    """
    q = ctx.q
    scale = np.float32(1 << frac_bits)

    def lift(x):
        xi = jnp.round(x * scale).astype(jnp.int64) if False else jnp.round(x * scale).astype(jnp.int32)
        return jnp.where(xi < 0, np.uint32(q) + xi.astype(jnp.uint32), xi.astype(jnp.uint32))

    uh = lift(u)
    kh = lift(k)
    ch = ntt_conv(uh, kh, ctx, interpret=interpret)
    # map back to signed: values > q/2 are negative
    signed = jnp.where(ch > np.uint32(q // 2), ch.astype(jnp.float32) - np.float32(q), ch.astype(jnp.float32))
    return signed / (scale * scale)
