"""Whole runs of the sync cells on the CPU at a tiny size (no chip check):
the program passes its comparison; the timed path broken underneath, or the
control put in its place, fails it."""

import jax
import numpy as np
import pytest

import bench.entries.robust_sync as entry
from bench import compare
from bench.reference import robust as ref
from bench.tests.tiny_cells import SEED, run_tiny, smoke_widths, tiny  # noqa: F401

CELLS = ["sync.n25.rfa", "sync.n25.cm"]


def _altered(sync):
    """The aggregate changed at one coordinate where it is produced."""
    def broken(msgs, agg, **kw):
        out, info = sync(msgs, agg, **kw)
        leaves, treedef = jax.tree_util.tree_flatten(out)
        scale = jax.numpy.max(jax.numpy.abs(leaves[0]))
        leaves[0] = leaves[0].reshape(-1).at[0].add(1e-3 * scale).reshape(leaves[0].shape)
        return jax.tree_util.tree_unflatten(treedef, leaves), info
    return broken


def _half_rows(sync):
    """Half of the workers' messages left out, the rule run on the rest."""
    def broken(msgs, agg, **kw):
        half = jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], msgs)
        return sync(half, agg, **kw)
    return broken


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [None, "altered", "half_rows"])
def test_sync_run_correct_only_when_sound(smoke_widths, monkeypatch, cell, fault):
    if fault:
        make = {"altered": _altered, "half_rows": _half_rows}[fault]
        monkeypatch.setattr(entry, "robust_gradient_sync", make(entry.robust_gradient_sync))
    res = run_tiny(cell)
    assert res["attempted"] >= 1
    assert res["correct"] is (fault is None), res["checks"]
    assert set(res["metrics"]) == {"sync_ms", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_sync_control_fails(smoke_widths, cell):
    """The reference at three bf16 passes, put in the program's place."""
    found = tiny(cell)
    cell_ = entry.Cell(found["config"], found["traffic"], SEED, 1)
    cell_.setup()
    xs = entry.flatten(cell_.msgs)
    limit = found["traffic"]["limits"]["rel_err"]
    keys = [jax.random.fold_in(cell_.call_key, i) for i in sorted(cell_.sample)]
    want = ref.aggregate(xs, keys, cell_.rule)
    for key, w in zip(keys, want):
        got = np.asarray(ref.aggregate_jnp(jax.numpy.asarray(xs), key, cell_.rule))
        assert compare.rel_err(got, w) > limit
