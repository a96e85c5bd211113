"""The comparisons that decide ``correct``, each a number held to a limit.

``rel_err``: the widest gap between the program's output and the
reference's, over the largest magnitude of the reference (sync cells).

``worst_leaf_gap``: for per-leaf norms of a tree (the first gradient the
optimizer gets, the parameters' change over the first steps), the gap
between the program's norm and the reference's, not the norm of their
difference, over the reference's norm of that leaf or of the median leaf,
whichever is larger; the worst leaf counts (train cells).

``loss_gap``: the widest relative gap between the program's and the
reference's loss over the first steps (train cells).
"""

from __future__ import annotations

import numpy as np


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != reference {want.shape}")
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def worst_leaf_gap(prog, ref, keep=None) -> tuple:
    """(gap, leaf index) of the worst leaf; ``keep`` masks the leaves that
    count."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return float("inf"), -1
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / scale
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def moving_leaves(ref_grad_norms) -> np.ndarray:
    """Leaves whose reference gradient is above a thousandth of the median
    leaf's; the others move under Adam by round-off alone."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g > 1e-3 * np.median(g)


def loss_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def train_numbers(got: dict, want: dict) -> dict:
    """The train cells' three numbers from per-step losses, per-leaf norms of
    the first aggregated gradient and of the parameters' change."""
    keep = moving_leaves(want["grad_norms"])
    return {"loss_gap": loss_gap(got["losses"], want["losses"]),
            "grad_gap": worst_leaf_gap(got["grad_norms"], want["grad_norms"])[0],
            "update_gap": worst_leaf_gap(got["delta_norms"], want["delta_norms"], keep)[0]}
