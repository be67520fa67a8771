"""FHE-flavoured demo: RLWE ciphertext-style polynomial products, batched
across banks (PIM) / across the batch axis (TPU).

The paper's target workload: polynomial multiplication in
Z_q[X]/(X^N + 1) via eq. (1), with bank-level parallelism — "FHE
applications can naturally run multiple NTT functions using multiple
banks" (§VI-A).

The demo now goes one level up the FHE stack as well: a real RNS-CKKS
ciphertext multiply (`repro.he.RlweCtMulOp`) compiled to a multi-tower
gang plan — one residue tower per bank — with the per-tower timing
breakdown the row-centric mapping produces.

    PYTHONPATH=src python examples/fhe_polymul.py --n 4096 --batch 8 --towers 4
"""
import argparse
import time

import jax
import numpy as np

import repro.he as he
from repro.core import modmath as mm
from repro.core import ntt
from repro.core.pim_config import PimConfig
from repro.kernels import ops
from repro.pimsys import PimSession, PolymulOp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=8, help="independent products (banks)")
    ap.add_argument("--nb", type=int, default=4, help="atom buffers per bank")
    ap.add_argument("--towers", type=int, default=4,
                    help="RNS towers for the ciphertext multiply")
    args = ap.parse_args()
    q = mm.DEFAULT_Q
    ctx = ntt.make_context(q, args.n)
    rng = np.random.default_rng(0)
    a = rng.integers(0, q, (args.batch, args.n)).astype(np.uint32)
    b = rng.integers(0, q, (args.batch, args.n)).astype(np.uint32)

    # -- PIM path: one product per bank; latency = single bank (parallel) --
    sess = PimSession(PimConfig(num_buffers=args.nb))
    r = sess.run(sess.compile(PolymulOp(args.n)), a[0], b[0], ctx=ctx)
    out0, timing = r.value, r.timing
    expect0 = ntt.polymul_negacyclic_np(a[0], b[0], ctx)
    assert np.array_equal(out0, expect0)
    print(f"[pim] polymul N={args.n}, Nb={args.nb}: {timing.us:.1f} us/bank, "
          f"{args.batch} banks in parallel -> {timing.us:.1f} us total "
          f"({timing.stats['act']} activations/bank, "
          f"phases={ {k: round(v / 1e3, 1) for k, v in timing.phase_ns.items()} } us)")

    # -- HE path: one RNS-CKKS ciphertext multiply, tower-per-bank --------
    he_sess = PimSession(PimConfig(num_channels=2, num_banks=4,
                                   param_cache_entries=16))
    plan = he_sess.compile(he.RlweCtMulOp(n=args.n, towers=args.towers))
    basis = he.basis_for(plan.op)
    ct_a, ct_b = he.random_ct(basis, 1), he.random_ct(basis, 2)
    rh = he_sess.run(plan, ct_a, ct_b)
    assert np.array_equal(rh.value, he.ct_mul_reference(basis, ct_a, ct_b))
    th = rh.timing
    print(f"[he] ct_mul N={args.n}, L={args.towers} towers on {th.banks} "
          f"banks: {th.latency_ns / 1e3:.1f} us "
          f"(x{th.speedup:.2f} vs one bank, eff {th.efficiency:.2f})")
    print(f"[he]   phases: "
          f"{ {k: round(v / 1e3, 1) for k, v in th.phase_ns.items()} } us")
    per_tower = "  ".join(
        f"t{i}@{done / 1e3:.1f}us" for i, done in enumerate(th.tower_done_ns))
    print(f"[he]   per-tower completion: {per_tower}")

    # -- TPU path: batch over the VPU, same math --------------------------
    t0 = time.perf_counter()
    got = np.asarray(ops.polymul_ntt(a, b, ctx))
    dt = time.perf_counter() - t0
    for i in range(args.batch):
        assert np.array_equal(got[i], ntt.polymul_negacyclic_np(a[i], b[i], ctx))
    print(f"[tpu] batch={args.batch} polymul == oracle ({dt:.2f}s first-call "
          f"wall time on {jax.devices()[0].platform}, compile included)")
    print("fhe_polymul OK")


if __name__ == "__main__":
    main()
