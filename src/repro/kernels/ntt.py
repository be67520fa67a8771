"""Row-centric NTT as Pallas TPU kernels.

The PIM -> TPU mapping:

  regime A (intra-atom + intra-row)  -> `_ntt_tile_kernel`: ALL stages with
      stride < T fused over a single VMEM-resident tile; one HBM read +
      one HBM write covers log(T) stages (the paper's "process a row-sized
      block with one row activation").
  regime B (inter-row)               -> `_ntt_pair_kernel`: one pass per
      remaining stage; each grid step's block CONTAINS both butterfly
      halves (u and v tiles), is updated IN PLACE
      (`input_output_aliases`) — the paper's BU-grained scheduling +
      in-place update, so no third buffer / no extra HBM allocation.
      Pallas's grid pipeline multi-buffers HBM<->VMEM DMAs against
      compute — the Nb-buffer pipelining idea; each HBM tile is touched
      exactly once (read+write) per stage — the activation-grouping idea.
  bank-level parallelism             -> the batch grid axis (FHE runs many
      independent NTTs in one call; see ops.ntt).

Layout.  A tile of T words is a (T/128, 128) slab: vector rows of 128
lanes, so no reshape ever splits the lane axis.  Inside a tile, a stage
of stride s pairs element i with element i ^ s:
  * s < 128: lane l pairs with lane l ^ s of the same row;
  * s >= 128: row r pairs with row r ^ (s/128), lane by lane.
Both are done with one rotation each way (`pltpu.roll`) and a select on
the stride bit of the position, so every butterfly is a whole-slab
vector op.  Twiddles are laid out one per element on the host: the
stage's twiddle at the upper ("v") element of each pair and 1 at the
lower, so one Shoup multiply over the slab scales exactly the v half.
The inter-tile stages keep a tile's (rows, 128) slab as the block's last
two dims and read their one twiddle per butterfly group from SMEM
(scalar prefetch), indexed by the grid.

Twiddles are precomputed tables shared across the batch (the paper's
on-the-fly (w0, r_w) generation saves DRAM bandwidth; on TPU a serial
recurrence would idle the VPU, and the tables cost O(T) VMEM).

All arithmetic is uint32 with 16-bit-limb emulation of 32x32->64
products (TPUs have no 64-bit integer multiply); q < 2^31.  Kernels run
interpreted on the CPU backend and compiled on the TPU (`resolve_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import modmath as mm
from repro.core.ntt import NttContext, forward_stages, inverse_stages
from repro.kernels import stats

LANES = 128  # words per vector row: the minor dim of every block
DEFAULT_TILE = 8192  # words: a (64, 128) slab, 32 KiB per row of the batch
DEFAULT_BATCH_BLOCK = 8


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode on the CPU backend, compiled on the TPU.

    An explicit `interpret` wins.  Any other backend raises instead of
    quietly interpreting, so a run that lost its TPU fails loudly.
    """
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile for 'tpu' or interpret on 'cpu'; "
        f"the default backend is {backend!r}"
    )


# ---------------------------------------------------------------------------
# stage micro-kernel — one butterfly stage over a (bb, rows, 128) slab
# ---------------------------------------------------------------------------


def _butterfly(x, w, w_sh, stride: int, axis: int, gs: bool, q: int):
    """One stage: element i pairs with i ^ stride along `axis`.

    `w` holds the pair's twiddle at the upper element and 1 at the lower.
    CT (forward): (u, v) -> (u + w v, u - w v).
    GS (inverse): (u, v) -> (u + v, (u - v) w).
    """
    size = x.shape[axis]
    upper = (jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) & stride) != 0

    def from_lower(a):  # a[i - stride]: the u partner of an upper element
        return pltpu.roll(a, stride, axis)

    def from_upper(a):  # a[i + stride]: the v partner of a lower element
        return pltpu.roll(a, size - stride, axis)

    if gs:
        z = jnp.where(upper, mm.submod_u32(from_lower(x), x, q), mm.addmod_u32(x, from_upper(x), q))
        return mm.shoup_mulmod_u32(z, w, w_sh, q)
    y = mm.shoup_mulmod_u32(x, w, w_sh, q)  # u at lower, w*v at upper
    return jnp.where(upper, mm.submod_u32(from_lower(y), y, q), mm.addmod_u32(y, from_upper(y), q))


# ---------------------------------------------------------------------------
# regime A kernel: fused stages over one VMEM tile
# ---------------------------------------------------------------------------


def _ntt_tile_kernel(x_ref, tw_ref, o_ref, *, strides, gs, q, scale):
    x = x_ref[...]  # (bb, rows, 128)
    for k, s in enumerate(strides):
        w, w_sh = tw_ref[0, k], tw_ref[1, k]  # (rows, 128) each
        if s < LANES:
            x = _butterfly(x, w, w_sh, s, 2, gs, q)
        else:
            x = _butterfly(x, w, w_sh, s // LANES, 1, gs, q)
    if scale is not None:
        n_inv, n_inv_sh = scale
        x = mm.shoup_mulmod_u32(x, np.uint32(n_inv), np.uint32(n_inv_sh), q)
    o_ref[...] = x


def _tile_tables(ctx: NttContext, tile: int, forward: bool):
    """Per-element twiddles of the stages with stride < tile.

    Returns (strides, tables) with tables of shape
    (n_tiles, 2, n_stages, tile/128, 128): [w, shoup(w)] for each stage,
    w at the upper element of each pair and 1 at the lower.
    """
    n, q = ctx.n, ctx.q
    table = ctx.psi_brv if forward else ctx.psi_inv_brv
    table_sh = ctx.psi_brv_shoup if forward else ctx.psi_inv_brv_shoup
    plan = forward_stages(n) if forward else inverse_stages(n)
    stages = [st for st in plan if st.stride < tile]
    pos = np.arange(n)
    w = np.ones((len(stages), n), np.uint32)
    w_sh = np.full((len(stages), n), mm.shoup(1, q), np.uint32)
    for k, st in enumerate(stages):
        upper = (pos & st.stride) != 0
        idx = st.tw_lo + pos[upper] // (2 * st.stride)
        w[k, upper] = table[idx]
        w_sh[k, upper] = table_sh[idx]
    tabs = np.stack([w, w_sh]).reshape(2, len(stages), n // tile, tile // LANES, LANES)
    return tuple(st.stride for st in stages), np.ascontiguousarray(tabs.transpose(2, 0, 1, 3, 4))


# ---------------------------------------------------------------------------
# regime B kernel: one inter-tile stage, block contains both halves
# ---------------------------------------------------------------------------


def _ntt_pair_kernel(tw_ref, x_ref, o_ref, *, gs, q):
    # block (bb, 2, rows, 128): dim 1 separates the butterfly halves;
    # tw_ref is the (2, n_groups) [w, shoup(w)] table in SMEM.
    g = pl.program_id(1)
    w, w_sh = tw_ref[0, g], tw_ref[1, g]
    u = x_ref[:, 0]
    v = x_ref[:, 1]
    if gs:
        nu = mm.addmod_u32(u, v, q)
        nv = mm.shoup_mulmod_u32(mm.submod_u32(u, v, q), w, w_sh, q)
    else:
        wv = mm.shoup_mulmod_u32(v, w, w_sh, q)
        nu = mm.addmod_u32(u, wv, q)
        nv = mm.submod_u32(u, wv, q)
    o_ref[:, 0] = nu
    o_ref[:, 1] = nv


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("ctx", "forward", "tile", "batch_block", "interpret")
)
def ntt_pallas(
    x,
    ctx: NttContext,
    forward: bool = True,
    tile: int | None = None,
    batch_block: int | None = None,
    interpret: bool | None = None,
):
    """Batched negacyclic NTT over the last axis of (batch, n) uint32.

    forward: natural order in -> bit-reversed out (CT butterflies).
    inverse: bit-reversed in -> natural out, scaled by 1/N (GS).
    Its device ops carry the scope `lane.ntt` or `lane.intt`; the kernels
    are named `ntt_tile_fwd`/`_inv` (the fused intra-tile pass) and
    `ntt_stage_fwd`/`_inv` (one inter-tile stage).
    """
    with stats.scope("ntt" if forward else "intt"):
        interpret = resolve_interpret(interpret)
        n = ctx.n
        if x.shape[-1] != n:
            raise ValueError(f"last axis is {x.shape[-1]}, the context is for n={n}")
        tile = min(tile or DEFAULT_TILE, n)
        if tile % LANES or tile & (tile - 1):
            raise ValueError(
                f"tile must be a power of two and a multiple of {LANES} words, got "
                f"{tile}: each tile is laid out as rows of {LANES} lanes"
            )
        squeeze = x.ndim == 1
        if squeeze:
            x = x[None, :]
        batch = x.shape[0]
        bb = min(batch_block or DEFAULT_BATCH_BLOCK, batch)
        pad = (-batch) % bb
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        out = _two_regime(x, ctx, forward, tile, bb, interpret)
        if pad:
            out = out[:batch]
        return out[0] if squeeze else out


def _two_regime(x, ctx, forward, tile, bb, interpret):
    """Fused intra-tile pass + one in-place pass per inter-tile stage.

    With tile == n there are no inter-tile stages and the whole transform,
    1/N scale included, is one fused pass.
    """
    n, q = ctx.n, ctx.q
    batch = x.shape[0]
    n_tiles = n // tile
    rows = tile // LANES
    table = ctx.psi_brv if forward else ctx.psi_inv_brv
    table_sh = ctx.psi_brv_shoup if forward else ctx.psi_inv_brv_shoup
    plan = forward_stages(n) if forward else inverse_stages(n)
    inter = [st for st in plan if st.stride >= tile]
    scale = None if forward else (ctx.n_inv, ctx.n_inv_shoup)
    way = "fwd" if forward else "inv"

    def run_intra(x, scale):
        strides, tabs = _tile_tables(ctx, tile, forward)
        kernel = functools.partial(_ntt_tile_kernel, strides=strides, gs=not forward, q=q, scale=scale)
        xr = x.reshape(batch, n_tiles, rows, LANES)
        # tiles outermost: a tile's twiddle block is fetched once, not per batch block
        out = pl.pallas_call(
            kernel,
            grid=(n_tiles, batch // bb),
            in_specs=[
                pl.BlockSpec((bb, None, rows, LANES), lambda j, i: (i, j, 0, 0)),
                pl.BlockSpec((None, 2, len(strides), rows, LANES), lambda j, i: (j, 0, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((bb, None, rows, LANES), lambda j, i: (i, j, 0, 0)),
            out_shape=jax.ShapeDtypeStruct(xr.shape, jnp.uint32),
            input_output_aliases={0: 0},
            interpret=interpret,
            name=f"ntt_tile_{way}",
        )(xr, jnp.asarray(tabs))
        return out.reshape(batch, n)

    def run_inter_stage(x, st):
        st_tiles = st.stride // tile
        n_groups = n_tiles // (2 * st_tiles)
        h = n // (2 * st.stride)
        # twiddle depends only on the group index g: u-tile offset
        # = (g*2*st_tiles + s)*tile, and (offset)/(2*stride) = g.
        tw = np.stack([table[h : h + n_groups], table_sh[h : h + n_groups]]).astype(np.uint32)
        x6 = x.reshape(batch, n_groups, 2, st_tiles, rows, LANES)
        block = pl.BlockSpec((bb, None, 2, None, rows, LANES), lambda i, g, s, tw: (i, g, 0, s, 0, 0))
        out = pl.pallas_call(
            functools.partial(_ntt_pair_kernel, gs=st.gs, q=q),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(batch // bb, n_groups, st_tiles),
                in_specs=[block],
                out_specs=block,
            ),
            out_shape=jax.ShapeDtypeStruct(x6.shape, jnp.uint32),
            input_output_aliases={1: 0},
            interpret=interpret,
            name=f"ntt_stage_{way}",
        )(jnp.asarray(tw), x6)
        return out.reshape(batch, n)

    if not inter:
        return run_intra(x, scale)
    if forward:
        for st in inter:  # large strides first
            x = run_inter_stage(x, st)
        return run_intra(x, None)
    x = run_intra(x, None)
    for st in inter:
        x = run_inter_stage(x, st)
    n_inv, n_inv_sh = scale
    return mm.shoup_mulmod_u32(x, np.uint32(n_inv), np.uint32(n_inv_sh), q)
