"""Row-centric NTT as Pallas TPU kernels.

The PIM -> TPU mapping:

  regime A (intra-atom + intra-row)  -> `_ntt_tile_kernel`: ALL stages with
      stride < T fused over a single VMEM-resident tile; one HBM read +
      one HBM write covers log(T) stages (the paper's "process a row-sized
      block with one row activation").  A whole transform of n < 1024
      words runs as `_ntt_rows_kernel` instead, batch-major (below).
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

Batch-major layout (`_ntt_rows_kernel`).  A whole transform of n = 128,
256 or 512 words is a slab of fewer than 8 rows, which would fill only
n/128 of a vreg's 8 sublanes.  Such a transform (tile == n) keeps the
(batch, n) input as it is in HBM and blocks it as (bb, n), with bb chosen
from bytes.  Inside the kernel each 128-lane column chunk of a sub-block
of rows is one (rows, 128) value, so the batch fills the sublanes.  A
stage of stride s >= 128 pairs chunk j with chunk j ^ (s/128): plain
vector arithmetic on whole chunks, with the stage's one twiddle per chunk
pair a scalar and the multiply on the v chunk alone.  A stage of stride
s < 128 is the lane butterfly above on each chunk, its twiddles one
(1, 128) row per chunk broadcast over the sublanes.  The arithmetic, the
stage order and the output order are those of the slab layout.

The modulus is data.  Each kernel reads q, and the inverse's 1/N with its
Shoup companion, from a small scalar operand in SMEM (scalar prefetch),
which also holds the one (w, shoup(w)) of each inter-tile butterfly group
or chunk pair; the per-element twiddle rows are a device operand too.  So
one compiled program per (n, direction, tile, batch block, shape) serves
every modulus: an RNS basis of many towers compiles its transforms once.
`ntt_pallas` builds a context's tables (`Tables`) on first use, places
them on the device and keeps them (`device_tables`).  Twiddles are
precomputed tables, not generated on the fly (the paper's (w0, r_w)
recurrence saves DRAM bandwidth; on TPU a serial recurrence would idle the
VPU, and the tables cost O(T) VMEM).

All arithmetic is uint32 with 16-bit-limb emulation of 32x32->64
products (TPUs have no 64-bit integer multiply); q < 2^31.  Kernels run
interpreted on the CPU backend and compiled on the TPU (`resolve_interpret`).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import modmath as mm
from repro.core.ntt import NttContext, forward_stages, inverse_stages
from repro.kernels import stats

LANES = 128  # words per vector row: the minor dim of every block
SUBLANES = 8  # rows of a uint32 vreg
DEFAULT_TILE = 8192  # words: a (64, 128) slab, 32 KiB per row of the batch
DEFAULT_BATCH_BLOCK = 8
# batch-major layout: blocks of about ROWS_BLOCK_BYTES, at least
# ROWS_MIN_STEPS grid steps so the DMAs overlap compute, and sub-blocks of
# ROWS_SUB_WORDS words (32 vregs) carried through every stage at once: a
# stage is a chain of dependent ops, and fewer vregs leave the VPU waiting
# on its latency (4 vregs took 3.7x the time on a v5e)
ROWS_BLOCK_BYTES = 512 * 1024
ROWS_MIN_STEPS = 4
ROWS_SUB_WORDS = 32768


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


def _butterfly(x, w, w_sh, stride: int, axis: int, gs: bool, q):
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
# a context's operands: the modulus and its tables, on the device
# ---------------------------------------------------------------------------

#: scalars[:HEAD] of every transform: q, n^-1 mod q and its Shoup companion
HEAD = 3


class Tables(NamedTuple):
    """The operands of one context's transforms in one direction and tile.

    scalars  u32, read by the kernels from SMEM: q, n_inv, shoup(n_inv),
             then on the slab path, for each inter-tile stage in pass order,
             its groups' w and then their shoup(w); batch-major, (w,
             shoup(w)) of each chunk pair in plan order
    tile     the per-element twiddles of the stages inside a tile:
             (n_tiles, 2, stages, rows, 128) on the slab path,
             (2, lane_stages, n/128, 1, 128) batch-major
    """

    scalars: object
    tile: object


def _batch_major_shape(n: int, tile: int) -> bool:
    """A whole transform of fewer than 8 slab rows runs batch-major."""
    return tile == n and n // LANES < SUBLANES


def _plan(n: int, forward: bool):
    return forward_stages(n) if forward else inverse_stages(n)


def _twiddles(ctx: NttContext, forward: bool):
    if forward:
        return ctx.psi_brv, ctx.psi_brv_shoup
    return ctx.psi_inv_brv, ctx.psi_inv_brv_shoup


def _host_tables(ctx: NttContext, forward: bool, tile: int) -> Tables:
    """`Tables` of `ctx` as numpy arrays."""
    n = ctx.n
    table, table_sh = _twiddles(ctx, forward)
    head = [ctx.q, ctx.n_inv, ctx.n_inv_shoup]
    _, tabs = _tile_tables(ctx, tile, forward)
    if _batch_major_shape(n, tile):
        _, lane_ks, chunk_tw = _rows_plan(n, forward)
        pairs = [int(v) for i in chunk_tw for v in (table[i], table_sh[i])]
        lanes = np.ascontiguousarray(tabs[0][:, list(lane_ks), :, None, :])
        return Tables(np.array(head + pairs, np.uint32), lanes)
    # the twiddle of an inter-tile stage depends only on its group g: the
    # u tile of (g, s) starts at (2 g st_tiles + s) tile, over 2 stride = g
    stages = [
        np.concatenate([table[h : 2 * h], table_sh[h : 2 * h]])
        for h in (n // (2 * st.stride) for st in _plan(n, forward) if st.stride >= tile)
    ]
    return Tables(np.concatenate([np.array(head, np.uint32), *stages]).astype(np.uint32), tabs)


@functools.cache
def device_tables(ctx: NttContext, forward: bool, tile: int) -> Tables:
    """`Tables` of `ctx` on the default device, built on first use and kept.

    A build is counted and spanned (`stats.tables`) under the entry of its
    direction.  It runs eagerly even while an outer `jax.jit` traces the
    caller, so the cache never holds a tracer (that caller's program then
    holds the tables as constants).
    """

    def build():
        with jax.ensure_compile_time_eval():
            return jax.device_put(_host_tables(ctx, forward, tile))

    return stats.tables("ntt" if forward else "intt", ctx.q, ctx.n, build)


# ---------------------------------------------------------------------------
# regime A kernel: fused stages over one VMEM tile
# ---------------------------------------------------------------------------


def _ntt_tile_kernel(s_ref, x_ref, tw_ref, o_ref, *, strides, gs, scale, interpret):
    q = s_ref[0]
    x = x_ref[...]  # (bb, rows, 128)
    for k, s in enumerate(strides):
        w, w_sh = tw_ref[0, k], tw_ref[1, k]  # (rows, 128) each
        if s < LANES:
            x = _butterfly(x, w, w_sh, s, 2, gs, q)
        else:
            x = _butterfly(x, w, w_sh, s // LANES, 1, gs, q)
        if interpret:
            x = _stage_boundary(x, q)
    if scale:
        x = mm.shoup_mulmod_u32(x, s_ref[1], s_ref[2], q)
    o_ref[...] = x


def _stage_boundary(x, q):
    """`x`, through a conditional that XLA cannot fuse across.

    Interpreted on the CPU, the kernel body is plain XLA, which fuses a
    stage's rolled rows into the next stage and so recomputes every earlier
    row stage inside each later one: a tile's time grows with the square of
    its stages (0.43 s instead of 0.13 s for a forward transform of 8 x
    65536 words on one core).
    The branch is taken whenever q > 0, that is always.  Compiled for the
    TPU the body is one Mosaic kernel, which needs no such boundary.
    """
    return jax.lax.cond(q > 0, lambda v: v, jnp.zeros_like, x)


def _tile_tables(ctx: NttContext, tile: int, forward: bool):
    """Per-element twiddles of the stages with stride < tile.

    Returns (strides, tables) with tables of shape
    (n_tiles, 2, n_stages, tile/128, 128): [w, shoup(w)] for each stage,
    w at the upper element of each pair and 1 at the lower.
    """
    n, q = ctx.n, ctx.q
    table, table_sh = _twiddles(ctx, forward)
    stages = [st for st in _plan(n, forward) if st.stride < tile]
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
# small-N kernel: every stage over a batch-major (bb, n) block
# ---------------------------------------------------------------------------


def _ntt_rows_kernel(s_ref, x_ref, tw_ref, o_ref, *, plan, sb, gs, scale):
    """Every stage over a (bb, n) block, sb rows at a time.

    `plan` holds one entry per stage: ("lanes", s, k) for stride s < 128,
    its twiddles tw_ref[:, k, j] (a (1, 128) row per chunk j); or
    ("chunks", d, at) for stride 128 d, the (w, shoup(w)) of each lower
    chunk in order at s_ref[at], s_ref[at + 1], ...
    """
    chunks = x_ref.shape[1] // LANES
    q = s_ref[0]

    def sub_block(i, carry):
        rows = pl.ds(pl.multiple_of(i * sb, sb), sb)
        c = [x_ref[rows, pl.ds(j * LANES, LANES)] for j in range(chunks)]  # (sb, 128) each
        for kind, step, tw in plan:
            if kind == "lanes":
                for j in range(chunks):
                    c[j] = _butterfly(c[j], tw_ref[0, tw, j], tw_ref[1, tw, j], step, 1, gs, q)
                continue
            lower = [j for j in range(chunks) if not j & step]
            for m, lo in enumerate(lower):
                w, w_sh = s_ref[tw + 2 * m], s_ref[tw + 2 * m + 1]
                u, v = c[lo], c[lo + step]
                if gs:
                    c[lo] = mm.addmod_u32(u, v, q)
                    c[lo + step] = mm.shoup_mulmod_u32(mm.submod_u32(u, v, q), w, w_sh, q)
                else:
                    wv = mm.shoup_mulmod_u32(v, w, w_sh, q)
                    c[lo], c[lo + step] = mm.addmod_u32(u, wv, q), mm.submod_u32(u, wv, q)
        for j in range(chunks):
            if scale:
                c[j] = mm.shoup_mulmod_u32(c[j], s_ref[1], s_ref[2], q)
            o_ref[rows, pl.ds(j * LANES, LANES)] = c[j]
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0] // sb, sub_block, 0)


@functools.lru_cache(maxsize=None)
def _rows_plan(n: int, forward: bool):
    """The batch-major stage plan of `_ntt_rows_kernel`.

    Returns (plan, lane_ks, chunk_tw): lane_ks the indices, among all
    stages, of the stages with stride < 128, whose twiddle rows `Tables.tile`
    holds as the slab layout's `_tile_tables` lays them out; chunk_tw the
    twiddle-table index of each chunk pair of the stages with stride >= 128,
    in the order `Tables.scalars` holds them after its HEAD.
    """
    plan, lane_ks, chunk_tw = [], [], []
    for k, st in enumerate(_plan(n, forward)):
        if st.stride < LANES:
            plan.append(("lanes", st.stride, len(lane_ks)))
            lane_ks.append(k)
            continue
        d = st.stride // LANES
        plan.append(("chunks", d, HEAD + 2 * len(chunk_tw)))
        # lower chunk j holds words 128 j .. 128 j + 127: one butterfly group
        chunk_tw += [st.tw_lo + j * LANES // (2 * st.stride) for j in range(n // LANES) if not j & d]
    return tuple(plan), tuple(lane_ks), tuple(chunk_tw)


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def _rows_blocks(batch: int, n: int, batch_block: int | None):
    """(bb, sb): rows of a grid step's block and of an inner sub-block."""
    sub = max(SUBLANES, ROWS_SUB_WORDS // n)
    if batch_block:
        bb = _round_up(batch_block, SUBLANES)
    else:
        steps = max(ROWS_MIN_STEPS, -(-batch * n * 4 // ROWS_BLOCK_BYTES))
        bb = _round_up(-(-batch // steps), SUBLANES)
        if bb > sub:
            bb = _round_up(bb, sub)  # whole sub-blocks: a smaller one idles the VPU
    bb = min(bb, _round_up(batch, SUBLANES))
    return bb, math.gcd(bb, sub)


def _batch_major(x, tabs: Tables, forward: bool, batch_block, interpret: bool):
    """The whole transform of (batch, n) rows, n < 8 * 128, in one pass."""
    batch, n = x.shape
    bb, sb = _rows_blocks(batch, n, batch_block)
    pad = (-batch) % bb
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    plan, _, _ = _rows_plan(n, forward)
    kernel = functools.partial(_ntt_rows_kernel, plan=plan, sb=sb, gs=not forward, scale=not forward)
    lanes = tabs.tile
    # no input_output_aliases: without a relayout before the call there is
    # no fresh buffer to give up, and XLA would copy the caller's input
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(x.shape[0] // bb,),
            in_specs=[
                pl.BlockSpec((bb, n), lambda i, s: (i, 0)),
                pl.BlockSpec(lanes.shape, lambda i, s: (0,) * lanes.ndim),
            ],
            out_specs=pl.BlockSpec((bb, n), lambda i, s: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
        interpret=interpret,
        name=f"ntt_tile_{'fwd' if forward else 'inv'}",
    )(tabs.scalars, x, lanes)
    return out[:batch] if pad else out


# ---------------------------------------------------------------------------
# regime B kernel: one inter-tile stage, block contains both halves
# ---------------------------------------------------------------------------


def _ntt_pair_kernel(s_ref, x_ref, o_ref, *, gs, at, groups):
    # block (bb, 2, rows, 128): dim 1 separates the butterfly halves; the
    # stage's w of group g is s_ref[at + g], its shoup(w) s_ref[at + groups + g]
    q = s_ref[0]
    g = pl.program_id(1)
    w, w_sh = s_ref[at + g], s_ref[at + groups + g]
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
    The context's modulus and tables are operands of the compiled program
    (`device_tables`), so every context of one n shares its programs.
    Its device ops carry the scope `lane.ntt` or `lane.intt`; the kernels
    are named `ntt_tile_fwd`/`_inv` (the fused intra-tile pass) and
    `ntt_stage_fwd`/`_inv` (one inter-tile stage).  A whole transform of
    n < 1024 words takes the batch-major layout, on its (batch, n) input as
    it is, and `batch_block` is rounded up to a multiple of 8 rows there.
    """
    n = ctx.n
    if x.shape[-1] != n:
        raise ValueError(f"last axis is {x.shape[-1]}, the context is for n={n}")
    tile = min(tile or DEFAULT_TILE, n)
    if tile % LANES or tile & (tile - 1):
        raise ValueError(
            f"tile must be a power of two and a multiple of {LANES} words, got "
            f"{tile}: each tile is laid out as rows of {LANES} lanes"
        )
    tabs = device_tables(ctx, forward, tile)
    return _transform(x, tabs, forward=forward, tile=tile, batch_block=batch_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("forward", "tile", "batch_block", "interpret"))
def _transform(x, tabs: Tables, forward: bool, tile: int, batch_block, interpret):
    """The compiled transform: keyed by n (the shape), direction, tile,
    batch block and interpret, never by the modulus."""
    entry = "ntt" if forward else "intt"
    with stats.scope(entry):
        interpret = resolve_interpret(interpret)
        n = x.shape[-1]
        squeeze = x.ndim == 1
        if squeeze:
            x = x[None, :]
        if _batch_major_shape(n, tile):
            stats.batch_major_trace(entry)
            out = _batch_major(x, tabs, forward, batch_block, interpret)
            return out[0] if squeeze else out
        batch = x.shape[0]
        bb = min(batch_block or DEFAULT_BATCH_BLOCK, batch)
        pad = (-batch) % bb
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        out = _two_regime(x, tabs, forward, tile, bb, interpret)
        if pad:
            out = out[:batch]
        return out[0] if squeeze else out


def _two_regime(x, tabs: Tables, forward, tile, bb, interpret):
    """Fused intra-tile pass + one in-place pass per inter-tile stage.

    With tile == n there are no inter-tile stages and the whole transform,
    1/N scale included, is one fused pass.
    """
    batch, n = x.shape
    n_tiles = n // tile
    rows = tile // LANES
    plan = _plan(n, forward)
    strides = tuple(st.stride for st in plan if st.stride < tile)
    inter = [st for st in plan if st.stride >= tile]
    way = "fwd" if forward else "inv"

    def run_intra(x, scale):
        kernel = functools.partial(_ntt_tile_kernel, strides=strides, gs=not forward, scale=scale, interpret=interpret)
        xr = x.reshape(batch, n_tiles, rows, LANES)
        # tiles outermost: a tile's twiddle block is fetched once, not per batch block
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_tiles, batch // bb),
                in_specs=[
                    pl.BlockSpec((bb, None, rows, LANES), lambda j, i, s: (i, j, 0, 0)),
                    pl.BlockSpec((None, 2, len(strides), rows, LANES), lambda j, i, s: (j, 0, 0, 0, 0)),
                ],
                out_specs=pl.BlockSpec((bb, None, rows, LANES), lambda j, i, s: (i, j, 0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct(xr.shape, jnp.uint32),
            input_output_aliases={1: 0},
            interpret=interpret,
            name=f"ntt_tile_{way}",
        )(tabs.scalars, xr, tabs.tile)
        return out.reshape(batch, n)

    def run_inter_stages(x):
        at = HEAD  # the stages' twiddles follow the head of the scalars, in pass order
        for st in inter:
            st_tiles = st.stride // tile
            n_groups = n_tiles // (2 * st_tiles)
            x6 = x.reshape(batch, n_groups, 2, st_tiles, rows, LANES)
            block = pl.BlockSpec((bb, None, 2, None, rows, LANES), lambda i, g, s, sc: (i, g, 0, s, 0, 0))
            x6 = pl.pallas_call(
                functools.partial(_ntt_pair_kernel, gs=st.gs, at=at, groups=n_groups),
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
            )(tabs.scalars, x6)
            x = x6.reshape(batch, n)
            at += 2 * n_groups
        return x

    if not inter:
        return run_intra(x, not forward)
    if forward:  # large strides first
        return run_intra(run_inter_stages(x), False)
    x = run_inter_stages(run_intra(x, False))
    q, n_inv, n_inv_sh = tabs.scalars[0], tabs.scalars[1], tabs.scalars[2]
    return mm.shoup_mulmod_u32(x, n_inv, n_inv_sh, q)
