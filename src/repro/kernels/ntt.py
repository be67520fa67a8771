"""Row-centric NTT as Pallas TPU kernels.

The PIM -> TPU mapping:

  regime A (intra-atom + intra-row)  -> `_ntt_rows_kernel`: ALL stages with
      stride < T fused over (rows, T) blocks of the batch, VMEM-resident;
      one HBM read + one HBM write covers log(T) stages (the paper's
      "process a row-sized block with one row activation").  With T == n
      it is the whole transform.
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

Batch-major layout (regime A).  The fused pass keeps the (batch, n) input
as it is in HBM, where a (8, 128) vreg tile holds 8 rows of the batch by
128 words, and blocks it as (bb, T): tile t of bb rows, bb chosen from
bytes.  Inside the kernel each 128-lane column chunk of a sub-block of
rows is one (rows, 128) value, so the batch fills the sublanes.  A stage
of stride s >= 128 pairs chunk j with chunk j ^ (s/128): plain vector
arithmetic on whole chunks, with the stage's one twiddle per chunk pair a
scalar and the multiply on the v chunk alone.  A stage of stride s < 128
pairs lane l with lane l ^ s of each chunk, with one rotation each way
(`pltpu.roll`) and a select on the stride bit of the position; its
twiddles are one (1, 128) row per chunk broadcast over the sublanes, the
stage's twiddle at the upper ("v") lane of each pair and 1 at the lower,
so one Shoup multiply over the chunk scales exactly the v half.  The
stages run in sweeps: a sweep carries a group of up to 2^ROWS_GROUP_BITS
chunks, closed under its chunk stages' pairings, through those stages in
vregs, then the next group; a tile of 64 chunks takes two sweeps.  A
chunk's twiddles depend on its position in the whole transform, so the
kernel picks them by its tile's grid coordinate.  A whole transform of
n < 1024 words is the same algorithm with one tile and one group.

Regime B keeps a tile's (T/128, 128) slab as the block's last two dims, so
no reshape ever splits the lane axis, and reads its one twiddle per
butterfly group from SMEM (scalar prefetch), indexed by the grid.  XLA
relays the (batch, n) array out to that view and back around its passes.

The modulus is data.  Each kernel reads q, and the inverse's 1/N with its
Shoup companion, from a small scalar operand in SMEM (scalar prefetch),
which also holds the one (w, shoup(w)) of each inter-tile butterfly group
and of each chunk pair; the lane twiddle rows are a device operand too.
So one compiled program per (n, direction, tile, batch block, shape)
serves every modulus: an RNS basis of many towers compiles its transforms
once.  `ntt_pallas` builds a context's tables (`Tables`) on first use,
places them on the device and keeps them (`device_tables`).  Twiddles are
precomputed tables, not generated on the fly (the paper's (w0, r_w)
recurrence saves DRAM bandwidth; on TPU a serial recurrence would idle the
VPU, and the tables cost O(n) memory).

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
DEFAULT_TILE = 8192  # words: 64 chunks of 128, 32 KiB per row of the batch
DEFAULT_BATCH_BLOCK = 8  # rows of an inter-tile stage pass's block
# the fused pass: blocks of about ROWS_BLOCK_BYTES, at least ROWS_MIN_STEPS
# grid steps so the DMAs overlap compute, and groups of up to
# 2^ROWS_GROUP_BITS chunks by sub-blocks of rows, ROWS_SUB_WORDS words (32
# vregs) in all, carried through a sweep's stages at once: a stage is a
# chain of dependent ops, and fewer vregs leave the VPU waiting on its
# latency (on a v5e, 4 vregs took 3.7x the time at n = 256, and 16 vregs
# 1.25x at a tile of 8192)
ROWS_BLOCK_BYTES = 512 * 1024
ROWS_MIN_STEPS = 4
ROWS_SUB_WORDS = 32768
ROWS_GROUP_BITS = 3
# interpreted on the CPU, XLA's compile time grows with the ops of a traced
# group, and its run time with the number of groups: two chunk stages a
# sweep balance them (run time on a par with the old slab kernel's, its
# compile ~40% longer)
INTERPRET_GROUP_BITS = 2


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
# stage micro-kernel — one lane butterfly stage over a (rows, 128) chunk
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

    scalars  u32, read by the kernels from SMEM: q, n_inv, shoup(n_inv);
             then, for each inter-tile stage in pass order, its groups' w
             and then their shoup(w); then tile by tile the (w, shoup(w))
             of each chunk pair (`_chunk_twiddles`)
    tile     the lane rows: (2, lane_stages, n/128, 1, 128), [w, shoup(w)]
             of each stage with stride < 128, one row per chunk of n
    """

    scalars: object
    tile: object


def _small_whole(n: int, tile: int) -> bool:
    """A whole transform of fewer than 8 chunks: its fused pass keeps one
    value a chunk through the lane stages, the program tuned for it, where
    a tile carries a group's chunks through them as one value."""
    return tile == n and n < SUBLANES * LANES


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
    # the twiddle of an inter-tile stage depends only on its group g: the
    # u tile of (g, s) starts at (2 g st_tiles + s) tile, over 2 stride = g
    stages = [
        np.concatenate([table[h : 2 * h], table_sh[h : 2 * h]])
        for h in (n // (2 * st.stride) for st in _plan(n, forward) if st.stride >= tile)
    ]
    chunk_tw = _chunk_twiddles(n, tile, forward)
    pairs = np.stack([table[chunk_tw], table_sh[chunk_tw]], axis=1).ravel()
    scalars = np.concatenate([np.array(head, np.uint32), *stages, pairs]).astype(np.uint32)
    return Tables(scalars, _lane_rows(ctx, forward))


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


def _lane_rows(ctx: NttContext, forward: bool):
    """[w, shoup(w)] of each stage with stride < 128, as one 128-lane row per
    chunk of n: (2, stages, n/128, 1, 128), w at the upper element of each
    pair and 1 at the lower."""
    n, q = ctx.n, ctx.q
    table, table_sh = _twiddles(ctx, forward)
    stages = [st for st in _plan(n, forward) if st.stride < LANES]
    pos = np.arange(n)
    w = np.ones((len(stages), n), np.uint32)
    w_sh = np.full((len(stages), n), mm.shoup(1, q), np.uint32)
    for k, st in enumerate(stages):
        upper = (pos & st.stride) != 0
        idx = st.tw_lo + pos[upper] // (2 * st.stride)
        w[k, upper] = table[idx]
        w_sh[k, upper] = table_sh[idx]
    return np.stack([w, w_sh]).reshape(2, len(stages), n // LANES, 1, LANES)


# ---------------------------------------------------------------------------
# regime A kernel: every stage with stride < tile over a batch-major block
# ---------------------------------------------------------------------------


def _ntt_rows_kernel(s_ref, x_ref, tw_ref, o_ref, *, sweeps, per_tile, sb, gs, scale, stack, interpret):
    """The stages of stride < tile over a (bb, tile) block, in sweeps.

    A sweep (low, bits, plan) of `_rows_plan` takes each group of 2^bits
    chunks, base + (m << low) for m < 2^bits, sb rows at a time, through
    its stages as (sb, 128) values, then writes it back: the first sweep
    reads `x_ref`, the others rework `o_ref` in place.  `plan` holds one
    entry per stage: ("lanes", s, k) for stride s < 128, its twiddles
    tw_ref[:, k, j] (a (1, 128) row per chunk j), run on the group stacked
    as one (2^bits, sb, 128) value with `stack`, else chunk by chunk; or
    ("chunks", e, at) for stride 128 * 2^e, the (w, shoup(w)) of each
    lower chunk of tile 0 in order at s_ref[at], s_ref[at + 1], ...,
    per_tile words further on for each tile before this one (the grid's
    first coordinate).
    """
    chunks = x_ref.shape[1] // LANES
    q = s_ref[0]
    at_tile = pl.program_id(0) * per_tile if per_tile else 0
    for n_sweep, (low, bits, plan) in enumerate(sweeps):
        src = o_ref if n_sweep else x_ref
        last = n_sweep == len(sweeps) - 1
        group_bits = (chunks >> bits).bit_length() - 1

        def sub_block(i, carry, src=src, low=low, bits=bits, plan=plan, last=last, group_bits=group_bits):
            if group_bits:
                g = i & ((1 << group_bits) - 1)
                i = i >> group_bits
                base = ((g >> low) << (low + bits)) | (g & ((1 << low) - 1))
            else:
                base = 0
            rows = pl.ds(pl.multiple_of(i * sb, sb), sb)
            js = [base + (m << low) for m in range(1 << bits)]
            cols = [pl.ds(j * LANES, LANES) if group_bits == 0 else pl.ds(pl.multiple_of(j * LANES, LANES), LANES) for j in js]
            c = [src[rows, col] for col in cols]  # (sb, 128) each
            for kind, step, tw in plan:
                if kind == "lanes" and stack:
                    g3 = _butterfly(jnp.stack(c), tw_ref[0, tw, pl.ds(base, len(c))], tw_ref[1, tw, pl.ds(base, len(c))], step, 2, gs, q)
                    if interpret:
                        g3 = _stage_boundary(g3, q)
                    c = [g3[m] for m in range(len(c))]
                    continue
                if kind == "lanes":
                    for m, j in enumerate(js):
                        c[m] = _butterfly(c[m], tw_ref[0, tw, j], tw_ref[1, tw, j], step, 1, gs, q)
                    continue
                d = 1 << (step - low)  # the pair's distance among the group's chunks
                at = tw + at_tile + 2 * _pair_rank(base, step)
                for lo in (m for m in range(len(c)) if not m & d):
                    k = at + 2 * _pair_rank(lo << low, step)
                    w, w_sh = s_ref[k], s_ref[k + 1]
                    u, v = c[lo], c[lo + d]
                    if gs:
                        c[lo] = mm.addmod_u32(u, v, q)
                        c[lo + d] = mm.shoup_mulmod_u32(mm.submod_u32(u, v, q), w, w_sh, q)
                    else:
                        wv = mm.shoup_mulmod_u32(v, w, w_sh, q)
                        c[lo], c[lo + d] = mm.addmod_u32(u, wv, q), mm.submod_u32(u, wv, q)
            for m, col in enumerate(cols):
                if scale and last:
                    c[m] = mm.shoup_mulmod_u32(c[m], s_ref[1], s_ref[2], q)
                o_ref[rows, col] = c[m]
            return carry

        jax.lax.fori_loop(0, (x_ref.shape[0] // sb) << group_bits, sub_block, 0)


def _stage_boundary(x, q):
    """`x`, through a conditional that XLA cannot fuse across.

    Interpreted on the CPU, the kernel body is plain XLA, which fuses a
    stage's rolled lanes into the next stage and so recomputes every earlier
    stage inside each later one (an inverse of 8 x 65536 words took 0.095 s
    instead of 0.037 s).  The branch is taken whenever q > 0, that is
    always.  Compiled for the TPU the body is one Mosaic kernel, which needs
    no such boundary.
    """
    return jax.lax.cond(q > 0, lambda v: v, jnp.zeros_like, x)


def _pair_rank(j, e: int):
    """The rank of lower chunk j among the lower chunks of a stage of stride
    128 * 2^e: j without its bit e, which is clear."""
    return ((j >> (e + 1)) << e) | (j & ((1 << e) - 1))


@functools.lru_cache(maxsize=None)
def _rows_plan(n: int, tile: int, forward: bool, group_bits: int):
    """The sweeps of `_ntt_rows_kernel` over tiles of `tile` words.

    A sweep is (low, bits, plan): `plan` its stages in order, and its chunk
    stages those of strides 128 * 2^low to 128 * 2^(low + bits - 1).  A
    sweep takes at most `group_bits` chunk stages, the sweeps as even as
    they come; a lane stage joins the sweep of its neighbours in the stage
    order.
    """
    chunks = tile // LANES
    stages = [st for st in _plan(n, forward) if st.stride < tile]
    n_chunk = chunks.bit_length() - 1
    n_sweeps = max(1, -(-n_chunk // group_bits))
    quota = [n_chunk // n_sweeps + (i < n_chunk % n_sweeps) for i in range(n_sweeps)]
    at = HEAD + 2 * (n // tile - 1)  # the inter-tile stages' twiddles come first
    plans, es, chunk_stages, lanes = [[]], [[]], 0, 0
    for st in stages:
        if st.stride < LANES:
            plans[-1].append(("lanes", st.stride, lanes))
            lanes += 1
            continue
        if len(es[-1]) == quota[len(es) - 1]:
            plans.append([])
            es.append([])
        e = (st.stride // LANES).bit_length() - 1
        plans[-1].append(("chunks", e, at + chunks * chunk_stages))  # a tile's chunks / 2 pairs before
        es[-1].append(e)
        chunk_stages += 1
    return tuple((min(e, default=0), len(e), tuple(p)) for e, p in zip(es, plans))


def _chunk_twiddles(n: int, tile: int, forward: bool) -> list:
    """The twiddle-table index of each chunk pair: tile by tile, chunk stage
    by chunk stage in plan order, lower chunk by lower chunk, the order in
    which `Tables.scalars` holds them after the inter-tile stages' twiddles."""
    chunks = tile // LANES
    # lower chunk j of tile t holds words 128 (t chunks + j) ..: one butterfly group
    return [
        st.tw_lo + (t * chunks + j) * LANES // (2 * st.stride)
        for t in range(n // tile)
        for st in _plan(n, forward)
        if LANES <= st.stride < tile
        for j in range(chunks)
        if not j & (st.stride // LANES)
    ]


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def _rows_blocks(batch: int, n: int, tile: int, group: int, batch_block: int | None):
    """(bb, sb): rows of a grid step's block and of an inner sub-block, for
    groups of `group` chunks."""
    sub = max(SUBLANES, ROWS_SUB_WORDS // (group * LANES))
    if batch_block:
        bb = _round_up(batch_block, SUBLANES)
    else:
        steps = max(ROWS_MIN_STEPS, -(-batch * n * 4 // ROWS_BLOCK_BYTES))
        bb = _round_up(-(-batch * (n // tile) // steps), SUBLANES)
        if bb > sub or not _small_whole(n, tile):
            bb = _round_up(bb, sub)  # whole sub-blocks: a smaller one idles the VPU
    bb = min(bb, _round_up(batch, SUBLANES))
    return bb, math.gcd(bb, sub)


def _fused_pass(x, tabs: Tables, forward: bool, tile: int, sweeps, bb: int, sb: int, scale: bool, alias: bool, interpret: bool):
    """Every stage with stride < tile of (batch, n) rows in one pass over
    (bb, tile) blocks; with tile == n the whole transform."""
    batch, n = x.shape
    tiles = n // tile
    chunks = tile // LANES
    # a tile's chunk pairs: chunks / 2 a chunk stage, two words each
    per_tile = chunks * (chunks.bit_length() - 1) if tiles > 1 else 0
    stack = not _small_whole(n, tile)
    kernel = functools.partial(
        _ntt_rows_kernel, sweeps=sweeps, per_tile=per_tile, sb=sb, gs=not forward, scale=scale, stack=stack, interpret=interpret
    )
    lanes = tabs.tile
    if tiles == 1:  # the whole transform: a grid over the batch alone
        grid = (batch // bb,)
        block = pl.BlockSpec((bb, n), lambda i, s: (i, 0))
        lane_block = pl.BlockSpec(lanes.shape, lambda i, s: (0,) * lanes.ndim)
    else:  # tiles outermost: a tile's lane rows are fetched once, not per batch block
        grid = (tiles, batch // bb)
        block = pl.BlockSpec((bb, tile), lambda t, i, s: (i, t))
        lane_block = pl.BlockSpec((*lanes.shape[:2], chunks, 1, LANES), lambda t, i, s: (0, 0, t, 0, 0))
    # alias only a fresh buffer: XLA would copy the caller's input to give it up
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=[block, lane_block], out_specs=block
        ),
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
        input_output_aliases={1: 0} if alias else {},
        interpret=interpret,
        name=f"ntt_tile_{'fwd' if forward else 'inv'}",
    )(tabs.scalars, x, lanes)


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


def _inter_stages(x, tabs: Tables, forward: bool, tile: int, bb: int, interpret: bool):
    """One in-place pass per stage with stride >= tile, on each tile's
    (tile/128, 128) slab."""
    batch, n = x.shape
    n_tiles = n // tile
    rows = tile // LANES
    at = HEAD  # the stages' twiddles follow the head of the scalars, in pass order
    for st in (st for st in _plan(n, forward) if st.stride >= tile):
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
            name=f"ntt_stage_{'fwd' if forward else 'inv'}",
        )(tabs.scalars, x6)
        x = x6.reshape(batch, n)
        at += 2 * n_groups
    return x


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
    `ntt_stage_fwd`/`_inv` (one inter-tile stage).  The fused pass works on
    the (batch, n) input as it is; `batch_block` is rounded up to a
    multiple of 8 rows there.
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
    batch block and interpret, never by the modulus.

    The fused pass, and one in-place pass per stage of stride >= tile:
    before the fused pass forward, after it inverse, then 1/N.  With tile
    == n the fused pass is the whole transform, 1/N included.
    """
    entry = "ntt" if forward else "intt"
    with stats.scope(entry):
        interpret = resolve_interpret(interpret)
        n = x.shape[-1]
        squeeze = x.ndim == 1
        if squeeze:
            x = x[None, :]
        # a whole transform of fewer than 8 rows of 128 words, or a tile of a longer one
        stats.batch_major_trace(entry, tile=not _small_whole(n, tile))
        batch = x.shape[0]
        sweeps = _rows_plan(n, tile, forward, INTERPRET_GROUP_BITS if interpret else ROWS_GROUP_BITS)
        bb, sb = _rows_blocks(batch, n, tile, 1 << max(bits for _, bits, _ in sweeps), batch_block)
        stage_bb = min(batch_block or DEFAULT_BATCH_BLOCK, _round_up(batch, bb))
        pad = (-batch) % (bb if tile == n else math.lcm(bb, stage_bb))
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        if tile == n:
            out = _fused_pass(x, tabs, forward, tile, sweeps, bb, sb, not forward, False, interpret)
        elif forward:  # large strides first
            out = _fused_pass(_inter_stages(x, tabs, forward, tile, stage_bb, interpret), tabs, forward, tile, sweeps, bb, sb, False, True, interpret)
        else:
            out = _inter_stages(_fused_pass(x, tabs, forward, tile, sweeps, bb, sb, False, False, interpret), tabs, forward, tile, stage_bb, interpret)
            q, n_inv, n_inv_sh = tabs.scalars[0], tabs.scalars[1], tabs.scalars[2]
            out = mm.shoup_mulmod_u32(out, n_inv, n_inv_sh, q)
        if pad:
            out = out[:batch]
        return out[0] if squeeze else out
