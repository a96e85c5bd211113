"""The example trainer's entry point (examples/train_llm_byzantine.py) and
chip_smoke.py's refusal to run without a TPU, on the CPU at a tiny size."""

import importlib.util
import math
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_example_main_returns_finite_losses(monkeypatch):
    """``main(argv)`` builds the mesh, places and donates the state, trains
    and returns one loss per step."""
    example = _load(ROOT / "examples" / "train_llm_byzantine.py")
    monkeypatch.setattr(example, "use_compile_cache", lambda: None)
    losses = example.main(["--preset", "cpu", "--steps", "3",
                           "--seq-len", "32", "--batch", "4"])
    assert len(losses) == 3
    assert all(math.isfinite(x) for x in losses)


def test_chip_smoke_refuses_without_tpu():
    smoke = _load(ROOT / "chip_smoke.py")
    with pytest.raises(SystemExit, match="needs a TPU"):
        smoke.main([])


def test_chip_smoke_phases_pass_at_tiny_size():
    """The one-chip phases and their reference checks, run on the CPU at a
    tiny size (the check for ``tpu_custom_call`` lives in ``main``)."""
    smoke = _load(ROOT / "chip_smoke.py")
    texts = smoke.phase_train(
        steps=3, example_argv=["--preset", "cpu", "--seq-len", "32",
                               "--batch", "4"])
    texts += smoke.phase_sync(d=2 ** 12)
    assert [what for what, _ in texts] == [
        "train step", "cm sync", "tm sync", "rfa sync", "cclip sync",
        "krum sync"]
