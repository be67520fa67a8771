"""The check catches what it is there to catch.  A run is driven on the CPU
with the timed path broken underneath (the chip look is skipped), and the
control, the reference with its last reduction left out, is judged as a run
judges the program; each has to come out not correct.  A fault between chips
cannot arise: every cell runs on one chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_testing import tiny

import control  # noqa: E402
import harness  # noqa: E402

SEED = 2**31 + 23
REAL = dict(harness.OPS)


def _unchanged(name):
    return lambda *args: args[0]


def _half_batch(name):
    def op(*args):
        ctx = args[-1]
        h = args[0].shape[0] // 2
        done = REAL[name](*(a[:h] for a in args[:-1]), ctx)
        return jnp.concatenate([done, args[0][h:]])
    return op


def _one_word_altered(name):
    def op(*args):
        ctx = args[-1]
        out = REAL[name](*args)
        return out.at[-1, 0].set((out[-1, 0] + 1) % np.uint32(ctx.q))
    return op


# arrivals fast enough that batches fill, so a fault in half of the padded
# batch reaches requests and not only padding
CELLS = {
    "mldsa65.verify_steady": {"rate": 4000.0},
    "mldsa65.verify_overload": {"rate": 4000.0},
}


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _one_word_altered],
                         ids=["state_unchanged", "half_batch_left_out", "answer_altered"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    cell = tiny(harness.load_cell(name), **CELLS[name])
    for op in {op.name for op in cell.ops}:
        monkeypatch.setitem(harness.OPS, op, fault(op))
    res = harness.run(cell, SEED, 0.2, False, 0.0, jax.devices()[0])
    assert not res["correct"]
    assert res["failed"] >= 1
    assert sum(c["value"] for k, c in res["checks"].items() if k != "batches_unchecked") > 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(name):
    cell = tiny(harness.load_cell(name), **CELLS[name])
    res = control.control_checks(cell, SEED)
    assert not res["correct"]
    words = {k: c["value"] for k, c in res["checks"].items() if k != "batches_unchecked"}
    assert any(v > 0 for v in words.values())
