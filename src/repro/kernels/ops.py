"""Public jit'd API over the Pallas kernels.

  ntt / intt           batched negacyclic NTT (forward: natural->brv,
                       inverse: brv->natural, 1/N folded in)
  polymul_ntt          a*b in Z_q[X]/(X^N+1), eq. (1) of the paper — no
                       bit-reversal anywhere (element-wise NTT domain)

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
