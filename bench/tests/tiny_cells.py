"""Cells of the benchmark cut to a size the CPU runs in seconds: the
program's smoke widths for mamba2-130m (2 layers, d_model 256, state 32,
head 32, chunk 16, vocab 512, float32), 7 workers with 2 Byzantine for the
sync, 32-token sequences for training. Only the tests use these."""

from __future__ import annotations

import argparse
import copy

import pytest

from bench import run as harness

SEED = 2 ** 31 + 12345


def tiny(cell: str) -> dict:
    found = copy.deepcopy(harness.resolve(cell))
    cfg = found["config"]
    cfg.update(d_model=256, n_layer=2, vocab_size=512, dtype="float32")
    cfg["ssm_cfg"].update(d_state=32, headdim=32, chunk_size=16)
    if "workers" in cfg:
        cfg.update(workers=7, byzantine=2)
    if "seq_len" in found["traffic"]:
        found["traffic"].update(seq_len=32, n_seqs=8)
    return found


@pytest.fixture
def smoke_widths(monkeypatch):
    """Make the entries build the program's smoke-width mamba2 config."""
    from repro.configs import smoke_config
    import bench.entries.robust_sync as rs

    monkeypatch.setattr(rs, "get_config", smoke_config)


def run_tiny(cell: str, seconds: float = 0.5) -> dict:
    args = argparse.Namespace(workload=cell, seed=SEED, seconds=seconds, trace=0)
    return harness.run(args, require_tpu=False, found=tiny(cell))
