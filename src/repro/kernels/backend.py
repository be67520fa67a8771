"""Unified NTT execution backends (`NttBackend`).

Three implementations of the SAME transform contract sit behind one
interface so they can be differentially tested against each other and
benchmarked through one harness (`benchmarks/tpu_ntt.py`):

  reference  numpy stage loop (`core.ntt`) — the ground truth.
  pim-sim    the paper's row-centric PIM bank: functional execution on
             `FunctionalBank` via `mapping.pim_ntt`, with the modeled
             `BankTimer` latency available for table3-style PIM-vs-TPU
             rows.
  pallas     the jax/pallas TPU kernel lane (`kernels.ntt.ntt_pallas`):
             compiled on a TPU, interpreted on the CPU backend (each
             tile a (rows, 128) slab, or batch-major below n = 1024;
             see `kernels/ntt.py`); gated on
             jax being importable so the package (and this module)
             stay usable without it.

Contract (shared by all three): uint32 arrays over the last axis,
`forward=True` is natural in -> bit-reversed out, `forward=False` is
bit-reversed in -> natural out scaled by 1/N — exactly the
`core.ntt.ntt_forward_np` / `ntt_inverse_np` conventions.

`get_backend(name)` / `available_backends()` are the registry the
benchmark and the differential tests drive.
"""
from __future__ import annotations

import abc

import numpy as np

from repro.core import modmath as mm
from repro.core import ntt as ntt_core
from repro.core.pim_config import PimConfig

DEFAULT_Q = mm.DEFAULT_Q


class NttBackend(abc.ABC):
    """One NTT execution lane behind the shared transform contract."""

    name: str = "?"
    summary: str = ""

    def __init__(self) -> None:
        self._ctxs: dict[tuple[int, int], ntt_core.NttContext] = {}

    # -- shared helpers ------------------------------------------------------
    def context(self, q: int, n: int) -> ntt_core.NttContext:
        """Cached `NttContext` per (q, n) — table setup is the expensive
        part of small transforms and must not pollute timing loops."""
        key = (q, n)
        ctx = self._ctxs.get(key)
        if ctx is None:
            ctx = self._ctxs[key] = ntt_core.make_context(q, n)
        return ctx

    def available(self) -> bool:
        """Whether this lane can run in the current environment."""
        return True

    def modeled_latency_ns(self, n: int, forward: bool = True) -> float | None:
        """Architecture-model latency for one size-n transform, if this
        backend has one (the PIM lane's `BankTimer` cycles); None means
        only wall-clock timing applies."""
        return None

    # -- the transform -------------------------------------------------------
    @abc.abstractmethod
    def _ntt_2d(self, x: np.ndarray, ctx: ntt_core.NttContext,
                forward: bool) -> np.ndarray:
        """Transform a (batch, n) uint32 array over the last axis."""

    def ntt(self, x: np.ndarray, q: int = DEFAULT_Q,
            forward: bool = True) -> np.ndarray:
        """Negacyclic NTT over the last axis of a (n,) or (batch, n)
        uint32 array; see the module docstring for the orientation
        contract."""
        x = np.asarray(x, np.uint32)
        if x.ndim not in (1, 2):
            raise ValueError(f"expected (n,) or (batch, n), got {x.shape}")
        n = x.shape[-1]
        if n & (n - 1) or n <= 0:
            raise ValueError("n must be a power of two")
        ctx = self.context(q, n)
        batched = x.ndim == 2
        out = self._ntt_2d(x if batched else x[None, :], ctx, forward)
        out = np.asarray(out, np.uint32)
        return out if batched else out[0]


class ReferenceBackend(NttBackend):
    name = "reference"
    summary = "numpy stage loop (core.ntt) — ground truth"

    def _ntt_2d(self, x, ctx, forward):
        fn = ntt_core.ntt_forward_np if forward else ntt_core.ntt_inverse_np
        return fn(x, ctx)


class PimSimBackend(NttBackend):
    """The paper's row-centric bank: functional `FunctionalBank`
    execution plus the `BankTimer` cycle model for latency rows."""

    name = "pim-sim"
    summary = "row-centric PIM bank (mapping.pim_ntt + BankTimer model)"

    def __init__(self, cfg: PimConfig | None = None) -> None:
        super().__init__()
        self.cfg = cfg or PimConfig()
        self._lat: dict[tuple[int, bool], float] = {}

    def _ntt_2d(self, x, ctx, forward):
        from repro.core.mapping import pim_ntt

        return np.stack([
            pim_ntt(row, ctx, self.cfg, forward=forward)[0] for row in x
        ])

    def modeled_latency_ns(self, n: int, forward: bool = True) -> float | None:
        key = (n, forward)
        ns = self._lat.get(key)
        if ns is None:
            from repro.pimsys.session import NttOp, PimSession

            sess = PimSession(self.cfg)
            ns = sess.run(sess.compile(NttOp(n, forward=forward))).timing.ns
            self._lat[key] = ns
        return ns


class PallasBackend(NttBackend):
    """The jax/pallas TPU kernel lane: compiled on a TPU, interpreted on
    the CPU backend, and an error on any other (`resolve_interpret`);
    `interpret` overrides the choice."""

    name = "pallas"
    summary = "jax/pallas tiled kernel (kernels.ntt.ntt_pallas)"

    def __init__(self, interpret: bool | None = None) -> None:
        super().__init__()
        self.interpret = interpret

    def available(self) -> bool:
        try:
            import jax  # noqa: F401
        except Exception:
            return False
        return True

    def _ntt_2d(self, x, ctx, forward):
        from repro.kernels.ntt import ntt_pallas

        out = ntt_pallas(x, ctx, forward=forward, interpret=self.interpret)
        return np.asarray(out)


_REGISTRY = {
    ReferenceBackend.name: ReferenceBackend,
    PimSimBackend.name: PimSimBackend,
    PallasBackend.name: PallasBackend,
}

BACKEND_NAMES = tuple(_REGISTRY)


def get_backend(name: str, **kwargs) -> NttBackend:
    """Instantiate a backend by registry name ('reference', 'pim-sim',
    'pallas'); raises ValueError for unknown names with the list of
    known ones in the message."""
    try:
        cls = _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown NTT backend {name!r}; known: {sorted(_REGISTRY)}"
        ) from None
    return cls(**kwargs)


def available_backends(**kwargs) -> list[NttBackend]:
    """Every registered backend that can run here, registry order."""
    out = []
    for name in _REGISTRY:
        b = get_backend(name)
        if b.available():
            out.append(b)
    return out
