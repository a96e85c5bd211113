"""Readings behind the limits that decide ``correct``, for many seeds in one
process on the chip.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --modes program,control[,half_tokens] [--out calib.jsonl]

Modes, each giving the cell's own numbers (``bench/compare.py``):

  program      the program's timed path against the reference, as a run
               reads it (sync cells: a short window at the cell's load)
  control      the reference put in the program's place, computed one step
               below the configuration's precision: the sync's float32 at
               ``high`` (three bf16 passes) for its ``highest``; for the
               bfloat16 model, every contraction with fp8 (e4m3) operands
  half_tokens  (train cells) the reference with the later half of every
               sequence left out of the loss: half the batch's tokens gone,
               the mean taken over the rest

The benchmark's own runs never run this. Each line of output is one JSON
object: cell, seed, mode and the numbers.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, run as harness  # noqa: E402

# the control answers the sampled call and one more, as many as a run compares
CONTROL_CALL = 16


def sync_modes(cell, modes) -> dict:
    import jax
    import jax.numpy as jnp
    from bench.reference import robust as ref

    out = {}
    if "control" in modes:
        leaves = jax.tree_util.tree_leaves(cell.msgs)
        xs = jnp.concatenate([x.reshape(x.shape[0], -1) for x in leaves], axis=1)
        calls = sorted(cell.sample | {CONTROL_CALL})
        ctrl = [jax.device_get(ref.aggregate_jnp(xs, jax.random.fold_in(cell.call_key, i),
                                                 cell.rule)) for i in calls]
        del xs
    harness.drive(cell, 3.0, jax.profiler.TraceAnnotation)
    cell.release()
    gc.collect()
    if "program" in modes:
        out["program"] = {name: v for name, v, _ in cell.check()}
    if "control" in modes:
        want = ref.aggregate(cell.xs, [jax.random.fold_in(cell.call_key, i) for i in calls],
                             cell.rule)
        out["control"] = {"rel_err": max(compare.rel_err(g, w) for g, w in zip(ctrl, want))}
    return out


def train_modes(cell, modes) -> dict:
    from bench.reference import mamba2 as ref

    cell.release()
    gc.collect()
    want = cell.reference()
    out = {}
    if "program" in modes:
        out["program"] = compare.train_numbers(cell.readings(), want)
    if "control" in modes:
        out["control"] = compare.train_numbers(cell.reference(mm=ref.mm_fp8), want)
    if "half_tokens" in modes:
        out["half_tokens"] = compare.train_numbers(cell.reference(token_fraction=0.5), want)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,control")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import importlib

    import jax

    found = harness.resolve(args.workload)
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    chips = found["cell"]["chips"]
    harness.check_devices(jax, chips, peaks)
    harness.use_cache(jax)
    entry = importlib.import_module(f"bench.entries.{found['traffic']['entry']}")
    modes = args.modes.split(",")
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = entry.Cell(found["config"], found["traffic"], seed, chips)
        cell.setup()
        if cell.unit_metric == "sync_ms":
            res = sync_modes(cell, modes)
        else:
            res = train_modes(cell, modes)
        for mode, numbers in res.items():
            line = json.dumps({"cell": args.workload, "seed": seed, "mode": mode, **numbers})
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(line + "\n")
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
