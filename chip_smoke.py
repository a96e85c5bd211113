"""Smoke run of the main path on a TPU: the robust train step and the paper's
n=25 packed gradient sync, each checked against a float32 reference.

    python chip_smoke.py              # one chip: phases `train` and `sync`
    python chip_smoke.py --chips 4    # four chips: phase `four` only

One process holds the chip(s) and starts no children. Each phase prints its
findings on lines of its own (compile seconds, peak device memory, first
step-time readings, reference comparisons); every check that fails raises,
so the exit code is non-zero. The last line of standard output is one JSON
object naming the device, printed only when every phase passed. Without a
TPU the script fails before any phase runs.

Phases:
  train  The example trainer (examples/train_llm_byzantine.py) at the
         published mamba2-130m widths in bf16, seq 256 x global batch 8,
         rfa + bucketing (s=2), worker momentum 0.9, adamw, on the 1x1 mesh
         (W=1). Step 0's loss is compared with ``tfm.loss_fn`` on float32
         copies of the same params and batch.
  sync   The paper's deployment: n=25 workers, 5 Byzantine, non-iid rows,
         a [25, 2^24] fp32 stack. ``packed_aggregate`` with the Pallas
         kernels for cm, tm, rfa, cclip and krum, each with bucketing s=2,
         against ``RobustAggregator`` on the same stack and key, run in
         fp32 on the host's CPU backend.
  four   (--chips 4) The worker axis over four chips: the example trainer
         on a (data=4, model=1) mesh, and one ``robust_gradient_sync`` of
         the per-worker momenta for cm and rfa through the sharded kernel
         route with the param-sharded egress, against ``RobustAggregator``
         on the same [4, n] stack gathered to the host's CPU backend.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

from repro.core.aragg import RobustAggregator  # noqa: E402
from repro.core.momentum import cclip_radius  # noqa: E402
from repro.distributed.packing import packed_aggregate  # noqa: E402
from repro.distributed.robust_sync import robust_gradient_sync  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402

example = importlib.import_module("train_llm_byzantine")

# The train step runs in bf16 and the reference in fp32 at "highest" matmul
# precision. bf16 keeps 8 significant bits (relative rounding 2^-9), and the
# step-0 loss is a mean over 8 x 256 tokens of fp32 log-softmax values, so
# its relative error stays well under 1%.
TRAIN_LOSS_RTOL = 1e-2
TRAIN_EXAMPLE_ARGS = ["--arch", "mamba2-130m", "--preset", "full",
                      "--seq-len", "256", "--batch", "8",
                      "--agg", "rfa", "--mixing", "bucketing"]
TRAIN_STEPS = 10
FOUR_STEPS = 5

# Both sides of a sync comparison are fp32; they differ in summation order
# (blocked kernel passes and the Gram route against plain jnp reductions
# over up to 2^24 terms), not in precision. Allowed: max |got - want| <=
# SYNC_RTOL * max |want|. The reference runs on the host's CPU backend: on
# a v5e, XLA's fp32 dot at "highest" precision over a 2^24-long contraction
# was off by 1.3e-3 (relative) in the Gram matrix, against 3.8e-6 for the
# Pallas Gram kernel, and that alone moved the RFA aggregate by 1.3e-4.
SYNC_RTOL = 1e-4
SYNC_N, SYNC_F, SYNC_D = 25, 5, 2 ** 24
SYNC_RULES = (
    ("cm", {}),
    ("tm", {"n_trim": SYNC_F}),
    ("rfa", {}),
    ("cclip", {"tau": cclip_radius(0.9)}),
    ("krum", {"n_byzantine": SYNC_F}),
)


def report(phase: str, **findings) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in findings.items()),
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def peak_bytes() -> int | str:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def program_bytes(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"args={m.argument_size_in_bytes} temps={m.temp_size_in_bytes} "
            f"out={m.output_size_in_bytes} alias={m.alias_size_in_bytes}")


def reference_loss(cfg, params, batch) -> float:
    """``tfm.loss_fn`` on float32 copies, at the highest matmul precision."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p, b: tfm.loss_fn(p, cfg32, b)[0])(p32, batch)
        return float(loss)


def host_reference(agg: RobustAggregator, xs, key):
    """``agg(xs, key)`` in fp32 on the host's CPU backend (see SYNC_RTOL)."""
    xs, key = jax.device_put((xs, key), jax.devices("cpu")[0])
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda x, k: agg(x, key=k))(xs, key)


def compare(phase: str, name: str, got, want) -> str | None:
    """Report ``got`` against the reference; return what failed, if any."""
    got = np.asarray(jax.device_get(got), np.float32)
    want = np.asarray(jax.device_get(want), np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    tol = SYNC_RTOL * scale
    report(phase, rule=name, max_abs_err=err, ref_max_abs=scale,
           rel_err=err / scale, tol=tol)
    if err > tol:
        return f"{name}: max |got - want| = {err} > {tol}"
    return None


# ------------------------------------------------------------------ phases
def run_trainer(phase: str, steps: int, example_argv) -> tuple:
    """Build the example trainer, check step 0 against the fp32 reference,
    compile, train ``steps`` steps. Returns (trainer, compiled text)."""
    tr = example.Trainer(example.parse_args(example_argv))
    report(phase, arch=tr.cfg.name, params=tr.cfg.param_count(),
           dtype=tr.cfg.dtype, workers=tr.n_workers,
           mesh=dict(tr.mesh.shape))
    _, batch0 = tr.batch_at(0)
    ref = reference_loss(tr.cfg, tr.params, batch0)
    compiled = tr.compile()
    report(phase, compile_s=round(tr.compile_s, 2), program=program_bytes(compiled))
    t0 = time.perf_counter()
    losses = tr.train(steps, log_every=1)
    wall = time.perf_counter() - t0
    report(phase, steps=steps, wall_s=round(wall, 3),
           mean_step_s_first_reading=round(wall / steps, 4),
           peak_bytes_in_use=peak_bytes())
    report(phase, memory_stats=jax.devices()[0].memory_stats())
    diff = abs(losses[0] - ref)
    report(phase, step0_loss=losses[0], ref_fp32_loss=ref, abs_diff=diff,
           tol=TRAIN_LOSS_RTOL * abs(ref), last_loss=losses[-1])
    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(diff <= TRAIN_LOSS_RTOL * abs(ref),
          f"step-0 loss {losses[0]} vs fp32 reference {ref}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return tr, compiled.as_text()


def phase_train(steps: int = TRAIN_STEPS, example_argv=TRAIN_EXAMPLE_ARGS) -> list:
    _, text = run_trainer("train", steps, example_argv)
    return [("train step", text)]


def make_stack(key, n: int, f: int, d: int) -> jnp.ndarray:
    """[n, d] fp32: honest rows with worker-dependent means (non-iid) and
    ``f`` Byzantine rows shifted far away."""
    means = jnp.concatenate([jnp.linspace(-1.0, 1.0, n - f),
                             jnp.full((f,), 10.0)])
    return jax.random.normal(key, (n, d), jnp.float32) + means[:, None]


def phase_sync(n: int = SYNC_N, f: int = SYNC_F, d: int = SYNC_D) -> list:
    xs = jax.jit(make_stack, static_argnums=(1, 2, 3))(
        jax.random.PRNGKey(0), n, f, d)
    key = jax.random.PRNGKey(1)
    report("sync", workers=n, byzantine=f, d=d, stack_bytes=xs.nbytes)
    texts, failed = [], []
    for name, kw in SYNC_RULES:
        agg = RobustAggregator.from_spec(name, mixing="bucketing", s=2, **kw)
        fn = jax.jit(lambda x, k, agg=agg: packed_aggregate(
            x, agg, key=k, use_kernels=True))
        t0 = time.perf_counter()
        compiled = fn.lower(xs, key).compile()
        compile_s = time.perf_counter() - t0
        got = compiled(xs, key).block_until_ready()
        t0 = time.perf_counter()
        compiled(xs, key).block_until_ready()
        call_s = time.perf_counter() - t0
        want = host_reference(agg, xs, key)
        report("sync", rule=name, compile_s=round(compile_s, 2),
               call_s_first_reading=round(call_s, 4),
               program=program_bytes(compiled), peak_bytes_in_use=peak_bytes())
        failed.append(compare("sync", name, got, want))
        texts.append((f"{name} sync", compiled.as_text()))
    failed = [f for f in failed if f]
    check(not failed, "; ".join(failed))
    return texts


def phase_four(steps: int = FOUR_STEPS,
               example_argv=TRAIN_EXAMPLE_ARGS) -> list:
    tr, text = run_trainer("four", steps, example_argv)
    check("all-to-all" in text, "four-chip train step has no all-to-all")
    texts = [("four-chip train step", text)]

    W = tr.n_workers
    for leaf in jax.tree_util.tree_leaves(tr.worker_m):
        shards = leaf.addressable_shards
        rows = sorted(s.index[0].indices(W)[:2] for s in shards)
        check(len({s.device for s in shards}) == W
              and rows == [(i, i + 1) for i in range(W)],
              f"worker_m leaf {leaf.shape} is not one worker row per device")
    report("four", worker_m_rows_per_device=1, devices=W)

    params_sh = jax.tree_util.tree_map(lambda p: p.sharding, tr.params)
    stack = jnp.concatenate(
        [x.reshape(W, -1) for x in jax.tree_util.tree_leaves(tr.worker_m)],
        axis=1)
    key = jax.random.PRNGKey(3)
    failed = []
    for name in ("cm", "rfa"):
        agg = RobustAggregator.from_spec(name, mixing="bucketing", s=2)
        fn = jax.jit(lambda m, k, agg=agg: robust_gradient_sync(
            m, agg, key=k, mesh=tr.mesh, out_shardings=params_sh)[0])
        t0 = time.perf_counter()
        compiled = fn.lower(tr.worker_m, key).compile()
        compile_s = time.perf_counter() - t0
        out = compiled(tr.worker_m, key)
        got = jnp.concatenate([x.reshape(-1).astype(jnp.float32)
                               for x in jax.tree_util.tree_leaves(out)])
        want = host_reference(agg, stack, key)
        sync_text = compiled.as_text()
        report("four", rule=name, compile_s=round(compile_s, 2),
               all_to_all="all-to-all" in sync_text,
               program=program_bytes(compiled))
        failed.append(compare("four", name, got, want))
        texts.append((f"four-chip {name} sync", sync_text))
    failed = [f for f in failed if f]
    check(not failed, "; ".join(failed))
    return texts


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found {dev.platform}")
    if jax.device_count() != args.chips:
        raise SystemExit(f"asked for {args.chips} chip(s); JAX found "
                         f"{jax.device_count()}")
    cache_dir = use_compile_cache()
    report("setup", device_kind=dev.device_kind, count=jax.device_count(),
           jax=jax.__version__, compile_cache=cache_dir)

    texts = phase_four() if args.chips == 4 else phase_train() + phase_sync()
    for what, text in texts:
        check("tpu_custom_call" in text, f"{what}: no Pallas kernel compiled")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
