"""Whole runs of the train cell on the CPU at a tiny size (no chip check):
the program passes its comparison; the timed path broken underneath, or the
fp8 control put in its place, fails it."""

import pytest

import bench.entries.train_step as entry
from bench import compare
from bench.reference import mamba2 as ref
from bench.tests.tiny_cells import SEED, run_tiny, smoke_widths, tiny  # noqa: F401

CELL = "train.mamba2-130m.1chip"


def _state_unchanged(make):
    """A step that returns its state unchanged (the loss still reported)."""
    def broken(*a, **kw):
        step_fn, sh = make(*a, **kw)

        def step(params, opt_state, worker_m, key, batch):
            metrics = step_fn(params, opt_state, worker_m, key, batch)[3]
            return params, opt_state, worker_m, metrics
        return step, sh
    return broken


def _half_tokens(loss_fn):
    """Half of the batch's tokens left out, the mean taken over the rest."""
    def broken(params, cfg, batch):
        labels = batch["labels"]
        half = labels.at[..., labels.shape[-1] // 2:].set(-100)
        return loss_fn(params, cfg, dict(batch, labels=half))
    return broken


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_tokens"])
def test_train_run_correct_only_when_sound(smoke_widths, monkeypatch, fault):
    if fault == "state_unchanged":
        monkeypatch.setattr(entry, "make_train_step", _state_unchanged(entry.make_train_step))
    if fault == "half_tokens":
        from repro.models import transformer as tfm
        monkeypatch.setattr(tfm, "loss_fn", _half_tokens(tfm.loss_fn))
    res = run_tiny(CELL)
    assert res["attempted"] >= 1
    assert res["correct"] is (fault is None), res["checks"]
    assert set(res["metrics"]) == {"train_step_ms", "setup_s"}


def test_train_control_fails(smoke_widths):
    """The reference with fp8 contractions, put in the program's place."""
    found = tiny(CELL)
    cell = entry.Cell(found["config"], found["traffic"], SEED, 1)
    cell.setup()
    cell.release()
    got = compare.train_numbers(cell.reference(mm=ref.mm_fp8), cell.reference())
    limits = found["traffic"]["limits"]
    assert any(got[k] > limits[k] for k in limits), got
