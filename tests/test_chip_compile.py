"""Compile the kernel lane for a described TPU v5e at FHE ring sizes.

Nothing runs: each case lowers and compiles, ahead of time, for one chip
of a `v5e:2x2` topology that JAX describes without the hardware, so
Mosaic refuses here what it would refuse on the chip (unaligned blocks,
unsupported shape casts, too much VMEM).  The topology is described in a
fixture, never at import: only the worker that runs this file loads the
TPU compiler.
"""
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import modmath as mm  # noqa: E402
from repro.core.ntt import make_context  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.modmul import modmul_pallas  # noqa: E402
from repro.kernels import ntt  # noqa: E402
from repro.kernels.ntt import ntt_pallas  # noqa: E402

BATCH = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile(fn, n_args, n, sharding):
    arg = jax.ShapeDtypeStruct((BATCH, n), jnp.uint32, sharding=sharding)
    compiled = jax.jit(fn).lower(*([arg] * n_args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == BATCH * n * 4
    return compiled


@pytest.mark.parametrize("forward", [True, False], ids=["forward", "inverse"])
@pytest.mark.parametrize("log_n", [8, 12, 14, 16, 17])
def test_ntt_compiles_for_v5e(one_chip, log_n, forward):
    n = 1 << log_n
    ctx = make_context(mm.DEFAULT_Q, n)
    _compile(lambda x: ntt_pallas(x, ctx, forward=forward, interpret=False), 1, n, one_chip)


def test_modmul_compiles_for_v5e(one_chip):
    ctx = make_context(mm.DEFAULT_Q, 1 << 16)
    _compile(lambda a, b: modmul_pallas(a, b, ctx, interpret=False), 2, 1 << 16, one_chip)


def test_polymul_compiles_for_v5e(one_chip):
    ctx = make_context(mm.DEFAULT_Q, 1 << 16)
    _compile(lambda a, b: ops.polymul_ntt(a, b, ctx, interpret=False), 2, 1 << 16, one_chip)


#: The lane's kernels by the names its `pallas_call`s give them.
KERNEL_NAME = re.compile(r"^(ntt_tile_(fwd|inv)|ntt_stage_(fwd|inv)|modmul)\.\d+$")


def _lane_names(compiled, scoped: bool) -> set:
    """The names of the compiled program's kernels; every one must be a lane
    kernel, and with `scoped` every op that JAX traced carries a `lane.` scope."""
    kernels = set()
    for line in compiled.as_text().splitlines():
        name = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if name and 'custom_call_target="tpu_custom_call"' in line:
            assert KERNEL_NAME.match(name[1]), line[:120]
            kernels.add(name[1].rsplit(".", 1)[0])
        op_name = re.search(r'op_name="([^"]*)"', line)
        # constants and parameters carry no op of their own: their op_name
        # ends at a jit(...) or has no path at all
        if scoped and op_name and "/" in op_name[1] and not op_name[1].rsplit("/", 1)[1].startswith("jit("):
            assert "/lane." in op_name[1], line[:160]
    return kernels


@pytest.mark.parametrize("forward", [True, False], ids=["forward", "inverse"])
@pytest.mark.parametrize("log_n", [8, 16])
def test_ntt_kernels_are_named_and_scoped_for_v5e(one_chip, log_n, forward):
    n = 1 << log_n
    ctx = make_context(mm.DEFAULT_Q, n)
    compiled = _compile(lambda x: ntt_pallas(x, ctx, forward=forward, interpret=False), 1, n, one_chip)
    way = "fwd" if forward else "inv"
    want = {f"ntt_tile_{way}"} | ({f"ntt_stage_{way}"} if log_n > 13 else set())
    assert _lane_names(compiled, scoped=True) == want


def test_modmul_kernel_is_named_for_v5e(one_chip):
    ctx = make_context(mm.DEFAULT_Q, 1 << 16)
    compiled = _compile(lambda a, b: modmul_pallas(a, b, ctx, interpret=False), 2, 1 << 16, one_chip)
    assert _lane_names(compiled, scoped=False) == {"modmul"}


def test_polymul_kernels_are_named_and_scoped_for_v5e(one_chip):
    ctx = make_context(mm.DEFAULT_Q, 1 << 16)
    compiled = _compile(lambda a, b: ops.polymul_ntt(a, b, ctx, interpret=False), 2, 1 << 16, one_chip)
    assert _lane_names(compiled, scoped=True) == {
        "ntt_tile_fwd", "ntt_stage_fwd", "modmul", "ntt_tile_inv", "ntt_stage_inv",
    }
    assert "/lane.polymul_ntt/" in compiled.as_text()


def _opcodes(text: str) -> list:
    return re.findall(r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(", text, re.M)


def _primitives(jaxpr) -> set:
    """The names of every primitive in `jaxpr` and in the jaxprs nested in
    its equations (jit bodies, kernel bodies)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def _entry_program(ctx, forward, batch, sharding):
    """The program an eager `ntt_pallas(x, ctx)` call runs, lowered: the
    context's modulus and tables are its operands (`ntt.device_tables`)."""
    tile = min(ntt.DEFAULT_TILE, ctx.n)
    tabs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), ntt.device_tables(ctx, forward, tile)
    )
    arg = jax.ShapeDtypeStruct((batch, ctx.n), jnp.uint32, sharding=sharding)
    return ntt._transform.lower(arg, tabs, forward=forward, tile=tile, batch_block=None, interpret=False)


@pytest.mark.parametrize("forward,batch", [(True, 6144), (False, 3072)], ids=["forward", "inverse"])
def test_small_n_is_one_kernel_on_its_input_for_v5e(one_chip, forward, batch):
    """At the ML-DSA shapes the whole program is the kernel on the (batch, 256)
    input as it is: no relayout before or after it, no copy for an alias, and
    the modulus and twiddles are its operands (scalars and lane rows)."""
    ctx = make_context(mm.DEFAULT_Q, 256)
    compiled = _entry_program(ctx, forward, batch, one_chip).compile()
    text = compiled.as_text()
    assert sorted(_opcodes(text)) == ["custom-call", "parameter", "parameter", "parameter"], _opcodes(text)
    way = "fwd" if forward else "inv"
    assert _lane_names(compiled, scoped=True) == {f"ntt_tile_{way}"}
    assert re.search(rf"%ntt_tile_{way}\.\d+ = u32\[{batch},256\]", text)
    assert "input_output_alias" not in text
    assert compiled.memory_analysis().alias_size_in_bytes == 0


#: Two towers of the CKKS configuration (CraterLake's 28-bit words, N = 2^16).
CKKS_TOWERS = (268042241, 265420801)


@pytest.mark.parametrize("forward", [True, False], ids=["forward", "inverse"])
def test_towers_share_one_program_for_v5e(one_chip, forward):
    """Two moduli at n = 2^16 lower to one program, word for word, and it
    compiles: each tower's modulus and tables are operands, not constants."""
    n = 1 << 16
    lowered = [_entry_program(make_context(q, n), forward, BATCH, one_chip) for q in CKKS_TOWERS]
    text = [lo.as_text() for lo in lowered]
    assert text[0] == text[1]
    compiled = lowered[0].compile()
    way = "fwd" if forward else "inv"
    assert _lane_names(compiled, scoped=True) == {f"ntt_tile_{way}", f"ntt_stage_{way}"}
    assert compiled.memory_analysis().output_size_in_bytes == BATCH * n * 4
    # the conditional between the tile kernel's stages (`ntt._stage_boundary`)
    # is built for interpret mode only: neither the compiled program nor the
    # kernel body traced for it holds one
    assert "conditional" not in _opcodes(compiled.as_text())
    ctx = make_context(CKKS_TOWERS[0], n)
    tabs = ntt.device_tables(ctx, forward, ntt.DEFAULT_TILE)
    x = jax.ShapeDtypeStruct((BATCH, n), jnp.uint32)
    body = {
        interpret: _primitives(
            jax.make_jaxpr(
                lambda a, t: ntt._transform(a, t, forward=forward, tile=ntt.DEFAULT_TILE, batch_block=None, interpret=interpret)
            )(x, tabs).jaxpr
        )
        for interpret in (False, True)
    }
    assert "pallas_call" in body[False] and "cond" not in body[False]
    assert "cond" in body[True]


@pytest.mark.parametrize("forward", [True, False], ids=["forward", "inverse"])
def test_tile_pass_works_on_the_batch_major_input_for_v5e(one_chip, forward):
    """At 64 x 65536 the fused intra-tile pass takes and returns the (batch, n)
    array as it lies in HBM, so it needs no relayout of its own, and the
    kernel body traced for the chip holds no conditional."""
    n = 1 << 16
    ctx = make_context(CKKS_TOWERS[0], n)
    compiled = _entry_program(ctx, forward, BATCH, one_chip).compile()
    text = compiled.as_text()
    way = "fwd" if forward else "inv"
    (line,) = [ln for ln in text.splitlines() if re.match(rf"\s*(?:ROOT )?%ntt_tile_{way}\.\d+ = ", ln)]
    assert re.search(rf"%ntt_tile_{way}\.\d+ = u32\[{BATCH},{n}\]", line), line[:160]
    # operands: the scalars, the block's rows, the lane twiddle rows
    operands = re.findall(r"u32\[[\d,]*\]", line.split("operand_layout_constraints=")[1].split("frontend_attributes")[0])
    assert operands[1] == f"u32[{BATCH},{n}]", operands
    tabs = ntt.device_tables(ctx, forward, ntt.DEFAULT_TILE)
    x = jax.ShapeDtypeStruct((BATCH, n), jnp.uint32)
    body = _primitives(
        jax.make_jaxpr(
            lambda a, t: ntt._transform(a, t, forward=forward, tile=ntt.DEFAULT_TILE, batch_block=None, interpret=False)
        )(x, tabs).jaxpr
    )
    assert "pallas_call" in body and "cond" not in body
