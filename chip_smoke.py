"""Smoke run of the Pallas NTT kernel lane on one TPU chip.

    python chip_smoke.py [--seed 0]

Drives the kernel lane through its user entry points
(`repro.kernels.ops.ntt` / `intt` / `polymul_ntt` and the `pallas`
`NttBackend`) with `interpret=False`, at FHE ring sizes, and compares
every output bit-exactly with the host reference (`core.ntt`):

  a) forward + inverse NTT, N=4096,  batch 64 (fused single-tile path)
  b) forward + inverse NTT, N=65536, batch 64 (two-regime path)
  c) polymul_ntt,           N=65536, batch 64
  d) pallas NttBackend,     N=65536, batch 64, both directions

Each phase prints its shapes, compile seconds and one warm wall time.
That time is a smoke reading, not a benchmark.  The last line is
`{"ok": true, "device": {...}}`; it is printed only when every phase
matched.  The script exits non-zero without it when JAX finds no TPU, and
lets any exception end the run.

The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
or else to `.jax_cache/` beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
BATCH = 64


def _timed_compile(fn, *args):
    """Compile `fn` for `args`; return (compiled, compile seconds, warm seconds)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))  # first run, untimed
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    return compiled, compile_s, time.perf_counter() - t0


def _check(label: str, got, exp) -> None:
    import numpy as np

    got = np.asarray(got)
    if got.shape != exp.shape or not np.array_equal(got, exp):
        bad = int(np.sum(got != exp)) if got.shape == exp.shape else "shape"
        raise SystemExit(f"{label}: MISMATCH vs host reference ({bad} words differ)")


def _phase_ntt(tag: str, n: int, rng) -> None:
    import jax
    import numpy as np

    from repro.core import modmath as mm
    from repro.core import ntt as ntt_core
    from repro.kernels import ops

    ctx = ntt_core.make_context(mm.DEFAULT_Q, n)
    x = rng.integers(0, ctx.q, (BATCH, n)).astype(np.uint32)
    y = rng.integers(0, ctx.q, (BATCH, n)).astype(np.uint32)
    for name, fn, arg, ref in (
        ("ntt", lambda a: ops.ntt(a, ctx, interpret=False), x, ntt_core.ntt_forward_np),
        ("intt", lambda a: ops.intt(a, ctx, interpret=False), y, ntt_core.ntt_inverse_np),
    ):
        compiled, c_s, w_s = _timed_compile(fn, jax.device_put(arg))
        _check(f"phase {tag} {name} N={n}", compiled(jax.device_put(arg)), ref(arg, ctx))
        print(f"phase {tag}: ops.{name} shape=({BATCH}, {n}) uint32 bit_exact=True "
              f"compile_s={c_s:.3f} smoke_wall_s={w_s:.6f}", flush=True)


def _phase_polymul(tag: str, n: int, rng) -> None:
    import jax
    import numpy as np

    from repro.core import modmath as mm
    from repro.core import ntt as ntt_core
    from repro.kernels import ops

    ctx = ntt_core.make_context(mm.DEFAULT_Q, n)
    a = rng.integers(0, ctx.q, (BATCH, n)).astype(np.uint32)
    b = rng.integers(0, ctx.q, (BATCH, n)).astype(np.uint32)
    fn = lambda a, b: ops.polymul_ntt(a, b, ctx, interpret=False)  # noqa: E731
    compiled, c_s, w_s = _timed_compile(fn, jax.device_put(a), jax.device_put(b))
    exp = ntt_core.polymul_negacyclic_np(a, b, ctx)
    _check(f"phase {tag} polymul_ntt N={n}", compiled(jax.device_put(a), jax.device_put(b)), exp)
    print(f"phase {tag}: ops.polymul_ntt shape=2x({BATCH}, {n}) uint32 bit_exact=True "
          f"compile_s={c_s:.3f} smoke_wall_s={w_s:.6f}", flush=True)


def _phase_backend(tag: str, n: int, rng) -> None:
    import numpy as np

    from repro.core import modmath as mm
    from repro.kernels.backend import get_backend

    pallas = get_backend("pallas", interpret=False)
    ref = get_backend("reference")
    x = rng.integers(0, mm.DEFAULT_Q, (BATCH, n)).astype(np.uint32)
    for forward in (True, False):
        t0 = time.perf_counter()
        pallas.ntt(x, forward=forward)  # compiles on the first call
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = pallas.ntt(x, forward=forward)  # numpy in, numpy out
        w_s = time.perf_counter() - t0
        name = "forward" if forward else "inverse"
        _check(f"phase {tag} pallas backend {name} N={n}", got, ref.ntt(x, forward=forward))
        print(f"phase {tag}: get_backend('pallas').ntt {name} shape=({BATCH}, {n}) uint32 "
              f"bit_exact=True first_call_s={first_s:.3f} "
              f"smoke_wall_s={w_s:.6f} (host transfers included)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of every input")
    args = ap.parse_args(argv)

    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    warm = os.path.isdir(cache) and any(os.scandir(cache))
    print(f"compile cache: {'warm' if warm else 'empty'} at start", flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX default device is {device['platform']})",
              file=sys.stderr)
        return 1

    import numpy as np

    sys.path.insert(0, str(ROOT / "src"))
    rng = np.random.default_rng(args.seed)
    _phase_ntt("a", 4096, rng)
    _phase_ntt("b", 65536, rng)
    _phase_polymul("c", 65536, rng)
    _phase_backend("d", 65536, rng)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
