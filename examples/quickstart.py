"""Quickstart: one NTT, three ways.

  1. reference (numpy oracle)
  2. NTT-PIM functional + cycle-level simulation (the paper's system)
  3. TPU Pallas kernel (row-centric mapping, interpret mode on CPU)

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import modmath as mm
from repro.core import ntt
from repro.core.pim_config import PimConfig
from repro.kernels.ntt import ntt_pallas, resolve_interpret
from repro.pimsys import NttOp, PimSession

N = 2048
Q = mm.DEFAULT_Q


def main():
    rng = np.random.default_rng(0)
    ctx = ntt.make_context(Q, N)
    poly = rng.integers(0, Q, N).astype(np.uint32)

    # 1. reference
    ref = ntt.ntt_forward_np(poly, ctx)

    # 2. PIM: compile once, then one run gives functional output + timing
    sess = PimSession(PimConfig(num_buffers=4))
    plan = sess.compile(NttOp(N, forward=True))
    r = sess.run(plan, poly, ctx=ctx)
    assert np.array_equal(r.value, ref), "PIM functional mismatch!"
    print(f"[pim] N={N}: {len(plan.commands)} DRAM commands, "
          f"{r.timing.us:.2f} us simulated on one HBM2E bank "
          f"({r.timing.stats['act']} row activations, Nb=4), "
          f"energy ~{r.timing.energy_nj():.1f} nJ")

    # 3. TPU kernel (batched = bank-level parallelism)
    batch = np.stack([poly] * 8)
    got_tpu = np.asarray(ntt_pallas(batch, ctx, forward=True))
    assert np.array_equal(got_tpu[0], ref), "Pallas kernel mismatch!"
    mode = "interpreted on CPU" if resolve_interpret(None) else "compiled on TPU"
    print(f"[tpu] N={N} x batch=8: Pallas row-centric kernel == oracle ({mode})")

    # polynomial multiplication (the FHE use-case, eq. 1)
    b = rng.integers(0, Q, N).astype(np.uint32)
    prod = np.asarray(__import__("repro.kernels.ops", fromlist=["polymul_ntt"])
                      .polymul_ntt(poly, b, ctx))
    school = ntt.schoolbook_negacyclic(poly, b, Q)
    assert np.array_equal(prod, school)
    print(f"[fhe] negacyclic polymul via NTT == schoolbook ({N} coeffs)")
    print("quickstart OK")


if __name__ == "__main__":
    main()
