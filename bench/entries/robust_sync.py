"""Entry ``robust_sync``: the packed robust gradient sync, called as the
train step calls it (``robust_gradient_sync(..., engine="packed")`` on the
cell's host mesh), on a worker-message tree shaped like the configured
model's parameters.

One message tree per run, drawn from the seed (``generate.alie_messages``).
Call i of the window aggregates it under the key ``fold_in(key, i)``, so
the bucketing permutation changes from call to call. One call drawn from
the seed among the first ``SAMPLE_FROM`` and the window's last call are
kept and compared, once the window has closed, with the float64 host
reference (``reference.robust.aggregate``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, cost, generate
from bench.reference import robust as ref
from repro.configs import get_config
from repro.core.aragg import RobustAggregator
from repro.distributed.robust_sync import robust_gradient_sync
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tfm

SAMPLE_FROM = 16


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file, with the sizes
    the file states and every width checked against it."""
    base = get_config(cfg["program_config"])
    mcfg = dataclasses.replace(base, n_layers=cfg["n_layer"],
                               vocab_size=cfg["vocab_size"], dtype=cfg["dtype"])
    ssm = cfg["ssm_cfg"]
    stated = {"d_model": cfg["d_model"], "ssm_state": ssm["d_state"],
              "ssm_head_dim": ssm["headdim"], "ssm_expand": ssm["expand"],
              "conv_kernel": ssm["d_conv"], "ssm_chunk": ssm["chunk_size"],
              "tie_embeddings": cfg["tie_embeddings"], "norm_eps": cfg["norm_eps"]}
    for k, v in stated.items():
        if getattr(mcfg, k) != v:
            raise ValueError(f"program config {k}={getattr(mcfg, k)} != {v}")
    return mcfg


def flatten(tree) -> np.ndarray:
    """Leaves [W, ...] -> host [W, n] float32, leaves in tree order."""
    leaves = [np.asarray(jax.device_get(x), np.float32) for x in jax.tree_util.tree_leaves(tree)]
    w = leaves[0].shape[0]
    return np.concatenate([x.reshape(w, -1) for x in leaves], axis=1)


class Cell:
    unit_metric = "sync_ms"

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed, self.chips = cfg, traffic, seed, chips
        self.rule = dict(traffic["rule"], s=cfg["bucket_s"])
        rng = np.random.default_rng(seed)
        self.sample = {int(rng.integers(SAMPLE_FROM))}
        self.kept = {}

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        cfg = self.cfg
        self.workers = W = cfg["workers"]
        shapes = jax.eval_shape(
            lambda: tfm.init_params(program_config(cfg), jax.random.PRNGKey(0)))
        self.n_params = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
        key = generate.seed_key(self.seed, impl="rbg")
        self.msgs = jax.jit(lambda k: generate.alie_messages(
            k, shapes, W, cfg["byzantine"]))(key)
        self.call_key = jax.random.fold_in(generate.seed_key(self.seed), 1)
        agg = RobustAggregator.from_spec(self.rule["name"], mixing="bucketing",
                                         s=self.rule["s"])
        mesh = make_host_mesh(self.chips, 1)

        def sync_call(msgs, key, i):
            return robust_gradient_sync(msgs, agg, key=jax.random.fold_in(key, i),
                                        mesh=mesh, engine="packed")[0]

        self.fn = jax.jit(sync_call).lower(self.msgs, self.call_key, 0).compile()
        self.hlo = [self.fn.as_text()]
        jax.block_until_ready(self.fn(self.msgs, self.call_key, 0))

    # ------------------------------------------------------------ window
    def dispatch(self, i: int):
        with jax.profiler.TraceAnnotation("sync_call"):
            out = self.fn(self.msgs, self.call_key, i)
        if i in self.sample:
            self.kept[i] = out
        self.last = (i, out)
        return out

    def block(self, handle) -> None:
        jax.block_until_ready(handle)

    def release(self) -> None:
        """After the window: keep the sampled answers and the messages on the
        host, free everything on the device."""
        i, out = self.last
        self.kept[i] = out
        self.kept = {i: np.concatenate([np.asarray(x, np.float32).reshape(-1)
                                        for x in jax.tree_util.tree_leaves(o)])
                     for i, o in self.kept.items()}
        self.xs = flatten(self.msgs)
        del self.msgs, self.fn, self.last

    # ------------------------------------------------------------ checks
    def check(self) -> list:
        """[(name, value, limit)] of the numbers that decide ``correct``."""
        calls = sorted(self.kept)
        want = ref.aggregate(self.xs, [jax.random.fold_in(self.call_key, i) for i in calls],
                             self.rule)
        worst = max(compare.rel_err(self.kept[i], w) for i, w in zip(calls, want))
        return [("rel_err", worst, self.traffic["limits"]["rel_err"])]

    def costs(self) -> dict:
        W, s, n = self.workers, self.rule["s"], self.n_params
        flops, nbytes = cost.sync_call_cost(self.rule["name"], W, s, n)
        return {"flops_per_unit": flops, "bytes_per_unit": nbytes,
                "kernels": cost.sync_kernel_costs(self.rule["name"], W, s, n)}
