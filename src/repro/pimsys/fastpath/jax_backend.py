"""Optional jax backend for the fastpath bus chain.

The only sequential recurrence in the evaluator is the speculative bus
chain (everything else is elementwise / exact-max gathers), so the jax
backend swaps exactly that seam: a jitted `jax.lax.scan` in float64
(x64 scoped via the `jax.enable_x64(True)` context so importing the backend
never mutates process-global jax config).
`lax.scan` is a strict left fold — the same add-by-add semantics as
`np.cumsum` — so results remain bit-identical to the interpreted
engine (asserted by `tests/test_fastpath_props.py` when jax is
importable).  This mirrors the kernels lane
(`src/repro/kernels/ntt.py`): scan for the sequential skeleton, fused
elementwise math around it, and keeps the two backends behind one
`evaluate_gang(..., backend=)` signature.

Import is lazy and gated: environments without the jax toolchain never
touch this module (`backend="numpy"` is the default everywhere).
"""
from __future__ import annotations

import numpy as np

try:  # pragma: no cover - exercised only where jax is installed
    import jax
    import jax.numpy as jnp

    HAS_JAX = True
except Exception:  # pragma: no cover
    jax = None
    jnp = None
    HAS_JAX = False

__all__ = ["HAS_JAX", "jax_chain"]


if HAS_JAX:

    @jax.jit
    def _scan_chain(b0, inc):
        def step(carry, x):
            nxt = carry + x
            return nxt, nxt

        _, vals = jax.lax.scan(step, b0, inc)
        return vals


def jax_chain(b0: float, pn_blk: np.ndarray, n: int,
              t_bus: float) -> np.ndarray:
    """`_numpy_chain` semantics on the jax backend: returns the
    ``[b0, s_1, B_1, ...]`` chain as a float64 numpy array."""
    if not HAS_JAX:  # pragma: no cover
        raise RuntimeError(
            "fastpath backend='jax' requested but jax is not importable; "
            "use backend='numpy'")
    K = len(pn_blk)
    inc = np.empty(2 * K * n)
    inc[0::2] = np.repeat(pn_blk, n)
    inc[1::2] = t_bus
    # x64 is scoped, never flipped globally: importing (or using) this
    # backend must not change dtype defaults for unrelated jax code in
    # the same process (jit re-traces under the scoped config)
    with jax.enable_x64(True):
        vals = np.asarray(_scan_chain(jnp.float64(b0), jnp.asarray(inc)))
    out = np.empty(1 + 2 * K * n)
    out[0] = b0
    out[1:] = vals
    return out
