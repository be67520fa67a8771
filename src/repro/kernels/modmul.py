"""Element-wise modular multiply Pallas kernel (NTT-domain ⊙ of eq. 1).

Montgomery round-trip per element (two REDC passes), uint32 in/out in
[0, q).  The analogue of streaming atom pairs through the CU's CMul path;
tiles are sized so two operand tiles + one result alias fit comfortably
in VMEM and the grid pipeline overlaps HBM DMA with compute.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath as mm
from repro.core.ntt import NttContext
from repro.kernels.ntt import resolve_interpret

DEFAULT_BLOCK = 16384  # words = 64 KiB per operand tile


def _modmul_kernel(a_ref, b_ref, o_ref, *, q, qprime, r2):
    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] = mm.mulmod_u32(a, b, q, qprime, r2)


@functools.partial(jax.jit, static_argnames=("ctx", "block", "interpret"))
def modmul_pallas(a, b, ctx: NttContext, block: int | None = None, interpret: bool | None = None):
    """Element-wise a*b mod q over arbitrary (batch..., n) uint32 arrays."""
    interpret = resolve_interpret(interpret)
    shape = a.shape
    assert a.shape == b.shape
    flat_a = a.reshape(-1)
    flat_b = b.reshape(-1)
    n = flat_a.shape[0]
    blk = min(block or DEFAULT_BLOCK, n)
    pad = (-n) % blk
    if pad:
        flat_a = jnp.pad(flat_a, (0, pad))
        flat_b = jnp.pad(flat_b, (0, pad))
    kernel = functools.partial(
        _modmul_kernel, q=ctx.q, qprime=ctx.qprime, r2=ctx.r2_mod_q
    )
    out = pl.pallas_call(
        kernel,
        grid=(flat_a.shape[0] // blk,),
        in_specs=[
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((blk,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct(flat_a.shape, jnp.uint32),
        input_output_aliases={0: 0},
        interpret=interpret,
        name="modmul",
    )(flat_a, flat_b)
    if pad:
        out = out[:n]
    return out.reshape(shape)
