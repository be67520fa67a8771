"""The fused pass's plan and blocks, checked in plain Python.

`kernels.ntt._ntt_rows_kernel` runs the stages of stride < tile in the
sweeps of `_rows_plan` and reads each chunk pair's twiddle from the scalars
of `_host_tables` at an offset the plan gives; `_rows_blocks` cuts the batch
into its grid's blocks.  These tests check both at every ring size the lane
serves, for the plan the chip runs and the one interpreted on the CPU,
without running a kernel.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import modmath as mm  # noqa: E402
from repro.core.ntt import forward_stages, inverse_stages, make_context  # noqa: E402
from repro.kernels import ntt  # noqa: E402

LANES = ntt.LANES
GROUP_BITS = {"interpreted": ntt.INTERPRET_GROUP_BITS, "chip": ntt.ROWS_GROUP_BITS}


def _stride(kind: str, step: int) -> int:
    return step if kind == "lanes" else LANES << step


@pytest.mark.parametrize("group", sorted(GROUP_BITS))
@pytest.mark.parametrize("direction", ["forward", "inverse"])
@pytest.mark.parametrize("log_n", range(7, 18))
def test_rows_plan_covers_every_stage_once(log_n, direction, group):
    n, forward, group_bits = 1 << log_n, direction == "forward", GROUP_BITS[group]
    tile = min(ntt.DEFAULT_TILE, n)
    chunks, tiles = tile // LANES, n // tile
    stages = forward_stages(n) if forward else inverse_stages(n)
    sweeps = ntt._rows_plan(n, tile, forward, group_bits)

    # every stage of stride < tile, once, in the transform's order
    plans = [entry for _, _, plan in sweeps for entry in plan]
    assert [_stride(kind, step) for kind, step, _ in plans] == [st.stride for st in stages if st.stride < tile]
    # a sweep's chunk stages are those of strides 128 * 2^low .. 128 * 2^(low + bits - 1)
    for low, bits, plan in sweeps:
        assert sorted(step for kind, step, _ in plan if kind == "chunks") == list(range(low, low + bits))
        assert bits <= group_bits
    # the lane stages' twiddle rows, in order
    lane_rows = [at for kind, _, at in plans if kind == "lanes"]
    assert lane_rows == list(range(len(lane_rows)))

    # the scalars, word by word as the kernels read them
    ctx = make_context(mm.DEFAULT_Q, n)
    table, table_sh = (ctx.psi_brv, ctx.psi_brv_shoup) if forward else (ctx.psi_inv_brv, ctx.psi_inv_brv_shoup)
    scalars = np.asarray(ntt._host_tables(ctx, forward, tile).scalars)
    assert list(scalars[: ntt.HEAD]) == [ctx.q, ctx.n_inv, ctx.n_inv_shoup]
    want = {}

    def read(k, tw):  # entry tw of the twiddle table: w at scalars[k[0]], shoup(w) at scalars[k[1]]
        for i, v in ((k[0], table[tw]), (k[1], table_sh[tw])):
            assert i not in want, f"scalars[{i}] is read twice"
            want[i] = v

    at = ntt.HEAD  # the inter-tile stages, in pass order: a group's w, then its shoup(w)
    for st in (st for st in stages if st.stride >= tile):
        groups = n // (2 * st.stride)
        for g in range(groups):
            read((at + g, at + groups + g), st.tw_lo + g)
        at += 2 * groups
    tw_lo = {st.stride: st.tw_lo for st in stages}
    per_tile = chunks * (chunks.bit_length() - 1)  # a tile's chunk pairs, two words each
    for kind, e, at in plans:  # then tile by tile each chunk pair, w then shoup(w)
        if kind != "chunks":
            continue
        stride = LANES << e
        for t in range(tiles):
            for j in (j for j in range(chunks) if not j >> e & 1):
                k = at + t * per_tile + 2 * ntt._pair_rank(j, e)
                read((k, k + 1), tw_lo[stride] + (t * chunks + j) * LANES // (2 * stride))
    assert sorted(want) == list(range(ntt.HEAD, len(scalars)))  # nothing left over
    np.testing.assert_array_equal(scalars[sorted(want)], [want[i] for i in sorted(want)])


#: (batch, n) -> (bb, sb) at the chip benchmark's shapes, pinned: a change
#: to these blocks is a change to what the benchmark measures
BENCHMARK_BLOCKS = {(6144, 256): (512, 128), (3072, 256): (512, 128), (64, 65536): (32, 32)}


@pytest.mark.parametrize("n", [256, 65536])
@pytest.mark.parametrize("batch", [1, 7, 13, 64, 700, 3072, 6144])
def test_rows_blocks_tile_the_batch(batch, n):
    tile = min(ntt.DEFAULT_TILE, n)
    group = 1 << max(bits for _, bits, _ in ntt._rows_plan(n, tile, True, ntt.ROWS_GROUP_BITS))
    bb, sb = ntt._rows_blocks(batch, n, tile, group, None)
    sub = max(ntt.SUBLANES, ntt.ROWS_SUB_WORDS // (group * LANES))  # rows of a whole sub-block
    assert bb % ntt.SUBLANES == 0 and bb % sb == 0
    assert sb * group * LANES <= ntt.ROWS_SUB_WORDS or sb == ntt.SUBLANES
    assert bb <= ntt._round_up(batch, ntt.SUBLANES)
    # whole sub-blocks, unless the block is the whole batch or a whole small transform's
    assert sb == sub or bb == ntt._round_up(batch, ntt.SUBLANES) or ntt._small_whole(n, tile)
    if (batch, n) in BENCHMARK_BLOCKS:
        assert (bb, sb) == BENCHMARK_BLOCKS[batch, n]
