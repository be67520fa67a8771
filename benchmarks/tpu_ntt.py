"""TPU NTT lane over the unified `NttBackend` harness.

Two kinds of rows:

  * structural roofline terms per mapping choice (no TPU attached, so
    the three terms derive from the lowered kernel + analytic HBM
    traffic — the same methodology as the model dry-run).  The paper's
    key metric — row activations, i.e. HBM tile touches — maps to
    `hbm_passes`: the fused intra-tile kernel does the first log(T)
    stages in ONE pass; each inter-tile stage adds one more.  These are
    deterministic arithmetic, so they gate like any other lane.
  * backend rows through `repro.kernels.backend`: a bit-exact
    {reference, pim-sim, pallas} differential (the same assert the
    tests run, proving the benchmarked kernels are the real ones), the
    PIM lane's modeled `BankTimer` latency (deterministic -> gated),
    and wall-clock annotations for the host lanes (noisy -> ungated).

`--json BENCH_tpu.json` commits the sweep as an artifact with the same
document shape as the other lanes (`scripts/perf_check.py` gates it).
Wall-clock here runs in interpret mode off-TPU (functional, not
indicative).
"""
import argparse
import json

import numpy as np

from repro.core import modmath as mm
from repro.core.ntt import make_context
from repro.core.pim_config import PimConfig
from repro.kernels.backend import available_backends, get_backend

PEAK_FLOPS = 197e12
HBM_BW = 819e9


def structural_terms(n: int, batch: int, tile: int):
    """(hbm_passes, bytes_moved, modmul_count) for one batched NTT."""
    tile = min(tile, n)
    stages = int(np.log2(n))
    intra = min(int(np.log2(tile)), stages)
    inter = stages - intra
    passes = 1 + inter  # paper: one "row activation" per tile per pass
    words = batch * n
    bytes_moved = passes * 2 * words * 4  # read + write per pass
    butterflies = batch * (n // 2) * stages
    return passes, bytes_moved, butterflies


def run(emit):
    from repro.kernels.ntt import DEFAULT_TILE

    batch = 64  # bank-level parallelism analogue
    for n in [2**12, 2**14, 2**16, 2**17]:
        for tile in [1024, 8192, 65536]:
            if tile > n:
                continue
            passes, bts, bfs = structural_terms(n, batch, tile)
            # 1 butterfly = 1 Shoup modmul (~10 uint32 VPU ops via 16-bit
            # limbs) + add/sub: ~16 elementwise ops -> flops-equivalent.
            vpu_ops = bfs * 16
            t_mem = bts / HBM_BW
            t_comp = vpu_ops / PEAK_FLOPS
            ai = vpu_ops / bts
            emit(
                f"tpu_ntt/N={n}/tile={tile}",
                t_mem * 1e6,
                f"hbm_passes={passes};AI={ai:.1f}ops/B;"
                f"bound={'memory' if t_mem > t_comp else 'compute'}",
            )
    # single-buffer analogue: stage-at-a-time (no fusion) = log N passes
    n = 2**14
    naive_passes = int(np.log2(n))
    fused_passes, _, _ = structural_terms(n, batch, DEFAULT_TILE)
    emit(
        "tpu_ntt/fusion_win",
        0.0,
        f"stagewise={naive_passes}passes;row-centric={fused_passes}passes;"
        f"x{naive_passes / fused_passes:.1f}_traffic_reduction",
    )


def correctness_check(emit):
    """Tiny run to prove the benchmarked kernel is the real one; the row
    names the platform it ran on and whether the kernel was interpreted."""
    ctx = make_context(mm.DEFAULT_Q, 4096)
    x = np.random.default_rng(0).integers(
        0, mm.DEFAULT_Q, (2, 4096)).astype(np.uint32)
    pallas = get_backend("pallas")
    if not pallas.available():
        emit("tpu_ntt/kernel_check", 0.0, "skipped=jax-unavailable")
        return
    import jax

    from repro.kernels.ntt import ntt_pallas, resolve_interpret

    got = np.asarray(ntt_pallas(x, ctx, forward=True, tile=1024))
    exp = get_backend("reference").ntt(x, forward=True)
    assert np.array_equal(got, exp)
    mode = "interpret" if resolve_interpret(None) else "compiled"
    emit("tpu_ntt/kernel_check", 0.0,
         f"platform={jax.devices()[0].platform};{mode};pallas==reference")


def backend_rows(emit, quick: bool = True, cfg: PimConfig | None = None):
    """Differential + latency rows through the `NttBackend` registry.

    The differential asserts BIT-EXACT equality of every available
    backend against the reference, forward and inverse, before any
    number is emitted — a failed cross-check must kill the benchmark,
    not publish wrong rows.  The pim-sim rows carry the deterministic
    `BankTimer`-modeled latency as `us_per_call` (gated); host
    wall-clock goes into ungated annotations (interpret-mode numbers
    mean nothing across machines).
    """
    import time

    cfg = cfg or PimConfig()
    sizes = [1024, 4096] if quick else [1024, 4096, 16384]
    batch = 2
    backends = available_backends()
    for b in backends:
        if b.name == "pim-sim":
            b.cfg = cfg
    names = [b.name for b in backends]
    rng = np.random.default_rng(0)
    ref = get_backend("reference")
    for n in sizes:
        x = rng.integers(0, mm.DEFAULT_Q, (batch, n)).astype(np.uint32)
        exp_f = ref.ntt(x, forward=True)
        exp_i = ref.ntt(exp_f, forward=False)
        assert np.array_equal(exp_i, x), "reference round-trip broke"
        for b in backends:
            t0 = time.perf_counter()
            got_f = b.ntt(x, forward=True)
            got_i = b.ntt(exp_f, forward=False)
            wall_us = (time.perf_counter() - t0) / (2 * batch) * 1e6
            assert np.array_equal(got_f, exp_f), (b.name, n, "forward")
            assert np.array_equal(got_i, exp_i), (b.name, n, "inverse")
            modeled = b.modeled_latency_ns(n, forward=True)
            if modeled is not None:
                emit(f"tpu_ntt/backend/{b.name}/N={n}", modeled / 1e3,
                     f"modeled=BankTimer;wall_us={wall_us:.1f}")
            else:
                emit(f"tpu_ntt/backend/{b.name}/N={n}", 0.0,
                     f"wall_us={wall_us:.1f}")
        emit(f"tpu_ntt/backend/differential/N={n}", 0.0,
             f"bit_equal={'+'.join(names)};batch={batch}x2dir")


def main(argv=None) -> int:
    from benchmarks.run import SCHEMA_VERSION, bench_meta, emit as print_emit

    ap = argparse.ArgumentParser(
        description="TPU NTT lane over the unified NttBackend harness")
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (the smoke/CI leg)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the sweep as a JSON artifact")
    args = ap.parse_args(argv)

    cfg = PimConfig()
    points = []

    def emit(name, us_per_call, derived=""):
        # wall-clock annotations print but stay out of the committed
        # artifact: a diff in BENCH_tpu.json must mean a model change,
        # never host noise
        clean = ";".join(p for p in derived.split(";")
                         if not p.startswith("wall_us="))
        points.append({"name": name, "us_per_call": us_per_call,
                       "derived": clean})
        print_emit(name, us_per_call, derived)

    print("name,us_per_call,derived")
    run(emit)
    correctness_check(emit)
    backend_rows(emit, quick=args.quick, cfg=cfg)

    if args.json:
        doc = {
            "benchmark": "tpu_ntt",
            "schema_version": SCHEMA_VERSION,
            "meta": bench_meta(cfg, seeds={"data": 0}),
            "quick": bool(args.quick),
            "points": points,
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
