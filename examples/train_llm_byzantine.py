"""End-to-end driver: Byzantine-robust training of a ~100M-param LLM.

Uses the framework's full distributed stack — the generic pattern-scanned
transformer (here the mamba2-130m assigned architecture at its real size,
or any --arch), the distributed train step with robust gradient sync
replacing the mean all-reduce, worker momentum, checkpointing, and the
synthetic heterogeneous token pipeline (per-worker bigram "dialects").

Runs on whatever devices exist, one worker per device: the mesh is
``(data=n_devices, model=1)``, so one chip trains with W=1 and a four-chip
host with W=4. Params, optimizer state, worker momentum and each batch are
placed with the train step's shardings, and the state is donated to the
step. ``--preset cpu`` shrinks the widths for a CPU run; ``--preset full``
keeps the published config, dtype included.

    PYTHONPATH=src python examples/train_llm_byzantine.py --steps 200 --preset cpu
    PYTHONPATH=src python examples/train_llm_byzantine.py --arch mamba2-130m --preset full
"""

from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.configs.base import ByzConfig, InputShape
from repro.data.synthetic import make_token_stream
from repro.distributed.steps import batch_shardings, make_train_step
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, n_workers
from repro.models import transformer as tfm
from repro.optim import make_optimizer
from repro.training.checkpoint import save_checkpoint


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--preset", choices=["cpu", "full"], default="cpu")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--agg", default="rfa")
    ap.add_argument("--mixing", default="bucketing")
    ap.add_argument("--ckpt-dir", default=None,
                    help="save params and optimizer state here at the end")
    return ap.parse_args(argv)


class Trainer:
    """The example's model, mesh, placed training state and batch source."""

    def __init__(self, args: argparse.Namespace):
        self.cfg = cfg = (smoke_config(args.arch) if args.preset == "cpu"
                          else get_config(args.arch))
        self.mesh = mesh = make_host_mesh(jax.device_count(), 1)
        self.n_workers = W = n_workers(mesh)
        byz = ByzConfig(aggregator=args.agg, mixing=args.mixing, s=2,
                        worker_momentum=0.9, delta=0.1)
        step_fn, sh = make_train_step(cfg, byz, mesh, lr=args.lr,
                                      optimizer="adamw")
        shape = InputShape("train", args.seq_len, args.batch, "train")
        batch_sh = batch_shardings(cfg, shape, mesh)
        state_sh = (sh["params"], sh["opt_state"], sh["worker_m"])
        self.step = jax.jit(
            step_fn,
            in_shardings=state_sh + (sh["replicated"], batch_sh),
            out_shardings=state_sh + (sh["replicated"],),
            donate_argnums=(0, 1, 2),
        )

        opt_init, _ = make_optimizer("adamw", lr=args.lr)
        self.params = jax.jit(functools.partial(tfm.init_params, cfg),
                              out_shardings=sh["params"])(jax.random.PRNGKey(0))
        self.opt_state = jax.jit(opt_init, out_shardings=sh["opt_state"])(
            self.params)
        self.worker_m = jax.jit(
            lambda p: jax.tree_util.tree_map(
                lambda x: jnp.zeros((W,) + x.shape, jnp.float32), p)
            if sh["worker_m"] else {},
            out_shardings=sh["worker_m"])(self.params)

        # heterogeneous per-worker token streams (non-iid "dialects")
        streams = make_token_stream(jax.random.PRNGKey(1), n_workers=W,
                                    seq_len=args.seq_len,
                                    n_seqs_per_worker=64,
                                    vocab=cfg.vocab_size)

        @functools.partial(jax.jit, out_shardings=(sh["replicated"], batch_sh))
        def batch_at(t):
            k = jax.random.fold_in(jax.random.PRNGKey(2), t)
            idx = jax.random.randint(k, (W, args.batch // W), 0,
                                     streams.shape[1])
            seqs = jnp.take_along_axis(streams, idx[..., None], axis=1)
            seqs = seqs.reshape(args.batch, -1)
            return k, {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}

        self.batch_at = batch_at
        self.compiled = None
        self.compile_s = None

    def compile(self):
        """Compile the train step once for the placed state; returns it."""
        t0 = time.perf_counter()
        self.compiled = self.step.lower(
            self.params, self.opt_state, self.worker_m, *self.batch_at(0)
        ).compile()
        self.compile_s = time.perf_counter() - t0
        return self.compiled

    def train(self, steps: int, log_every: int = 20) -> list:
        """Run ``steps`` steps of the compiled step; returns the losses."""
        if self.compiled is None:
            self.compile()
        losses = []
        t0 = time.perf_counter()
        for t in range(steps):
            k, batch = self.batch_at(t)
            self.params, self.opt_state, self.worker_m, metrics = self.compiled(
                self.params, self.opt_state, self.worker_m, k, batch)
            losses.append(metrics["loss"])
            if t % log_every == 0 or t == steps - 1:
                print(f"step {t:5d}  loss {float(metrics['loss']):.4f}  "
                      f"({time.perf_counter() - t0:.1f}s)")
        return [float(x) for x in losses]


def main(argv=None) -> list:
    """Train as the command line says; returns the per-step losses."""
    args = parse_args(argv)
    use_compile_cache()
    tr = Trainer(args)
    print(f"arch={tr.cfg.name} params={tr.cfg.param_count():,} "
          f"dtype={tr.cfg.dtype} workers={tr.n_workers} "
          f"devices={jax.device_count()}x{jax.devices()[0].device_kind} "
          f"agg={args.agg}+{args.mixing}")
    tr.compile()
    print(f"compiled train step in {tr.compile_s:.1f}s")
    losses = tr.train(args.steps)
    if args.ckpt_dir:
        path = save_checkpoint(args.ckpt_dir, args.steps,
                               {"params": tr.params, "opt": tr.opt_state})
        print(f"checkpoint -> {path}")
    return losses


if __name__ == "__main__":
    main()
