"""Entry ``train_step``: the robust train step as the example trainer builds
it, from the program's public functions, with the benchmark's own weights
and token streams drawn from the seed.

Mesh ``make_host_mesh(chips, 1)`` (one worker per chip), ``make_train_step``
with the configuration's recipe (robust rule + bucketing, worker momentum,
AdamW), state placed with the step's shardings and donated. The feed gives
each worker its own non-iid stream; step t takes sequence ``t mod n_seqs``
of a per-worker order drawn from the seed, so the first steps see rows
that all differ.

Set-up compiles the step and drives the very same compiled step and feed
through its first ``CHECK_STEPS`` steps, reading what the comparison needs
on the way: each step's loss, the first gradient the optimizer got (from
its first moment after one step) and the parameters' change. Once the
window has closed and the state is freed, ``reference.mamba2.train_steps``
follows those steps from the same weights and batches in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, cost, generate
from bench.entries.robust_sync import program_config
from bench.reference import mamba2 as ref
from repro.configs.base import ByzConfig, InputShape
from repro.distributed.steps import batch_shardings, make_train_step
from repro.launch.mesh import make_host_mesh, n_workers
from repro.optim import make_optimizer

CHECK_STEPS = 3


class Cell:
    unit_metric = "train_step_ms"

    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed, self.chips = cfg, traffic, seed, chips
        self.recipe = cfg["train"]

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        cfg, tr, rc = self.cfg, self.traffic, self.recipe
        mcfg = program_config(cfg)
        mesh = make_host_mesh(self.chips, 1)
        self.workers = W = n_workers(mesh)
        if W != tr["workers"]:
            raise ValueError(f"traffic wants {tr['workers']} workers, mesh has {W}")
        rule = rc["rule"]
        byz = ByzConfig(aggregator=rule["name"], mixing="bucketing", s=rule["s"],
                        worker_momentum=rc["worker_momentum"])
        step_fn, sh = make_train_step(mcfg, byz, mesh, lr=rc["lr"], optimizer="adamw")
        B, S = W * tr["seqs_per_worker"], tr["seq_len"]
        self.tokens_per_step = B * S
        batch_sh = batch_shardings(mcfg, InputShape("train", S, B, "train"), mesh)
        state_sh = (sh["params"], sh["opt_state"], sh["worker_m"])
        step = jax.jit(step_fn, in_shardings=state_sh + (sh["replicated"], batch_sh),
                       out_shardings=state_sh + (sh["replicated"],),
                       donate_argnums=(0, 1, 2))

        init = jax.jit(lambda k: ref.init_params(cfg, k), out_shardings=sh["params"])
        params = init(generate.seed_key(self.seed, impl="rbg"))
        self._check_layout(params, sh["params_shape"])
        opt_init, _ = make_optimizer("adamw", lr=rc["lr"])
        opt_state = jax.jit(opt_init, out_shardings=sh["opt_state"])(params)
        worker_m = jax.jit(lambda p: jax.tree_util.tree_map(
            lambda x: jnp.zeros((W,) + x.shape, jnp.float32), p),
            out_shardings=sh["worker_m"])(params)

        data_key = generate.seed_key(self.seed)
        streams = jax.jit(lambda k: generate.token_streams(
            k, W, tr["n_seqs"], S, cfg["vocab_size"], tr["noise_p"]))(
                jax.random.fold_in(data_key, 1))
        order = jax.vmap(lambda k: jax.random.permutation(k, tr["n_seqs"]))(
            jax.random.split(jax.random.fold_in(data_key, 2), W))
        sync_key = jax.random.fold_in(data_key, 3)
        per = tr["seqs_per_worker"]

        def _batch(t):
            # worker w takes `per` consecutive rows of its own order
            cols = (t * per + jnp.arange(per)) % tr["n_seqs"]
            idx = order[:, cols]                                  # [W, per]
            seqs = jnp.take_along_axis(streams, idx[..., None], axis=1)
            seqs = seqs.reshape(B, S + 1)
            key = jax.random.key_data(jax.random.fold_in(sync_key, t))
            return key, {"tokens": seqs[:, :-1], "labels": seqs[:, 1:]}

        batch_at = jax.jit(_batch, out_shardings=(sh["replicated"], batch_sh))

        self.batch_at = batch_at
        self.step = step.lower(params, opt_state, worker_m, *batch_at(0)).compile()
        self.hlo = [self.step.as_text()]

        # the first steps, through the window's own call and feed
        self.params0 = jax.device_get(params)
        p0 = jax.tree_util.tree_map(jnp.copy, params)
        norms = jax.jit(ref.leaf_norms)
        b1 = rc["beta1"]
        self.batches, self.keys, losses = [], [], []
        state = (params, opt_state, worker_m)
        for t in range(CHECK_STEPS):
            key, batch = batch_at(t)
            self.batches.append(jax.device_get(batch))
            self.keys.append(jax.device_get(key))
            *state, metrics = self.step(*state, key, batch)
            losses.append(metrics["loss"])
            if t == 0:
                self.grad_norms = np.asarray(norms(state[1].m), np.float64) / (1 - b1)
        delta = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
        self.delta_norms = np.asarray(norms(delta(state[0], p0)), np.float64)
        self.losses = [float(x) for x in losses]
        del p0
        self.state = state
        self.t = CHECK_STEPS

    @staticmethod
    def _check_layout(params, want) -> None:
        got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
        exp = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want)
        if got != exp:
            raise ValueError(f"benchmark weights {got} do not match the program's {exp}")

    # ------------------------------------------------------------ window
    def dispatch(self, i: int):
        with jax.profiler.TraceAnnotation("batch"):
            key, batch = self.batch_at(self.t)
        with jax.profiler.TraceAnnotation("dispatch"):
            *self.state, metrics = self.step(*self.state, key, batch)
        self.t += 1
        return metrics["loss"]

    def block(self, handle) -> None:
        jax.block_until_ready(handle)

    def release(self) -> None:
        del self.state, self.step, self.batch_at

    # ------------------------------------------------------------ checks
    def reference(self, mm=ref.mm_highest, token_fraction: float = 1.0) -> dict:
        params0 = jax.device_put(self.params0)
        keys = [jax.random.wrap_key_data(k) for k in self.keys]
        return ref.train_steps(params0, self.batches, keys, self.cfg, self.recipe,
                               self.workers, mm=mm, token_fraction=token_fraction)

    def readings(self) -> dict:
        return {"losses": self.losses, "grad_norms": self.grad_norms,
                "delta_norms": self.delta_norms}

    def check(self) -> list:
        got = compare.train_numbers(self.readings(), self.reference())
        lim = self.traffic["limits"]
        return [(k, v, lim[k]) for k, v in got.items()]

    def costs(self) -> dict:
        flops = cost.train_step_flops(self.cfg, self.tokens_per_step)
        return {"flops_per_unit": flops}
