"""Distributed step factories executed on a 1-device mesh (numerics), plus
sharding-rule unit tests. The 256/512-device lowering is covered by the
dry-run (repro.launch.dryrun), which owns the placeholder-device env var."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, smoke_config
from repro.configs.base import ByzConfig, InputShape
from repro.distributed.sharding import batch_spec, infer_param_spec
from repro.distributed.steps import input_specs, make_serve_step, make_train_step
from repro.launch.mesh import make_host_mesh, n_workers
from repro.models import transformer as tfm
from repro.optim import make_optimizer
from repro.telemetry.profiling import SCOPES, SYNC_PHASES


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


def _small_shape(kind="train"):
    return InputShape("test", seq_len=32, global_batch=4, kind=kind)


def test_input_specs_train():
    cfg = smoke_config("tinyllama-1.1b")
    specs = input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert specs["tokens"].shape == (256, 4096)
    assert specs["labels"].dtype == jnp.int32


def test_input_specs_vlm_prefix():
    cfg = smoke_config("internvl2-2b")
    specs = input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert "prefix_embeds" in specs
    assert specs["prefix_embeds"].shape[2] == cfg.d_model


def test_input_specs_audio_codebooks():
    cfg = smoke_config("musicgen-medium")
    specs = input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert specs["tokens"].shape == (256, cfg.n_codebooks, 4096)


def test_input_specs_decode():
    cfg = smoke_config("qwen2.5-14b")
    specs = input_specs(cfg, INPUT_SHAPES["decode_32k"])
    assert set(specs) == {"token"}
    assert specs["token"].shape == (128,)


def test_train_step_executes_and_learns(mesh):
    """One real train step on the tiny mesh: loss finite, params move."""
    cfg = smoke_config("tinyllama-1.1b")
    byz = ByzConfig(aggregator="rfa", mixing="bucketing", s=2,
                    worker_momentum=0.9)
    shape = _small_shape()
    with mesh:
        step_fn, sh = make_train_step(cfg, byz, mesh, lr=1e-2)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        opt_init, _ = make_optimizer("sgdm")
        opt_state = opt_init(params)
        worker_m = jax.tree_util.tree_map(
            lambda x: jnp.zeros((n_workers(mesh),) + x.shape, jnp.float32), params
        ) if sh["worker_m"] else {}
        toks = jax.random.randint(jax.random.PRNGKey(1),
                                  (shape.global_batch, shape.seq_len), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        p0 = jax.tree_util.tree_leaves(params)[0].copy()
        params, opt_state, worker_m, metrics = step_fn(
            params, opt_state, worker_m, jax.random.PRNGKey(2), batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert not jnp.allclose(jax.tree_util.tree_leaves(params)[0], p0)


def test_train_step_mean_baseline_matches_robust_with_mean(mesh):
    """aggregator=mean + mixing=none takes the fast all-reduce path; its
    gradient equals the robust path with a Mean aggregator."""
    cfg = smoke_config("mamba2-130m")
    shape = _small_shape()
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (shape.global_batch, shape.seq_len), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    outs = {}
    for name, byz in {
        "fast": ByzConfig(aggregator="mean", mixing="none", worker_momentum=0.0),
        "robust": ByzConfig(aggregator="rfa", mixing="none", worker_momentum=0.0),
    }.items():
        with mesh:
            step_fn, sh = make_train_step(cfg, byz, mesh, lr=1e-2)
            params = tfm.init_params(cfg, jax.random.PRNGKey(0))
            opt_init, _ = make_optimizer("sgdm")
            new_p, *_ , m = step_fn(params, opt_init(params), {},
                                    jax.random.PRNGKey(2), batch)
            outs[name] = new_p
    # with 1 worker, RFA degenerates to that worker's gradient == the mean
    for a, b in zip(jax.tree_util.tree_leaves(outs["fast"]),
                    jax.tree_util.tree_leaves(outs["robust"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-3, atol=2e-3)


def test_sharding_overrides_land_in_train_step(mesh):
    """gemma-7b carries a per-arch sharding override (the ROADMAP hillclimb
    lever): the tied embed is forced to P("data", "model") instead of the
    inferred rule. Assert the override survives the whole config ->
    make_train_step pipeline and actually lands in the step shardings."""
    from repro.distributed.sharding import overrides_from_config, param_shardings

    cfg = smoke_config("gemma-7b")
    assert overrides_from_config(cfg) == {"^embed$": P("data", "model")}

    byz = ByzConfig(aggregator="rfa", mixing="bucketing", s=2)
    with mesh:
        _, sh = make_train_step(cfg, byz, mesh, lr=1e-2)
    assert sh["params"]["embed"].spec == P("data", "model")
    # and it is the override that put it there — the inferred rule differs
    params_shape = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    plain = param_shardings(params_shape, mesh, fsdp=cfg.fsdp)
    assert plain["embed"].spec != sh["params"]["embed"].spec
    # non-override leaves are untouched by the override machinery
    for path in plain:
        if path != "embed":
            same = jax.tree_util.tree_map(lambda a, b: a == b,
                                          plain[path], sh["params"][path])
            assert all(jax.tree_util.tree_leaves(same)), path
    # configs without overrides decode to an empty mapping
    assert overrides_from_config(smoke_config("tinyllama-1.1b")) == {}


def test_serve_step_executes(mesh):
    cfg = smoke_config("qwen2.5-14b")
    shape = InputShape("test_decode", seq_len=64, global_batch=2, kind="decode")
    with mesh:
        serve, cache_shape, cache_sh = make_serve_step(cfg, mesh, shape)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shape)
        tok = jnp.zeros((2,), jnp.int32)
        logits, new_cache = serve(params, cache, tok, jnp.asarray(0, jnp.int32))
        assert logits.shape == (2, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))


# ------------------------------------------------------------ sharding rules
class _FakeMesh:
    def __init__(self, axes):
        self.axis_names = tuple(axes)
        import numpy as _np
        class _D:  # minimal stand-in with .shape
            pass
        self.devices = _D()
        self.devices.shape = tuple(axes.values())


def test_infer_param_spec_model_axis():
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = infer_param_spec("lm_head", (512, 4096), mesh)
    assert spec == P(None, "model")  # largest divisible dim gets model


def test_infer_param_spec_blocks_skips_period_axis():
    mesh = _FakeMesh({"data": 16, "model": 16})
    spec = infer_param_spec("blocks/0/ff/w_up", (22, 512, 2048), mesh)
    assert spec[0] is None  # scan period axis never sharded
    assert "model" in spec


def test_infer_param_spec_fsdp():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = infer_param_spec("blocks/0/ff/w_up", (22, 8192, 4096), mesh, fsdp=True)
    assert "model" in spec
    assert ("pod", "data") in spec or "data" in spec


def test_batch_spec_worker_axes():
    mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert batch_spec(mesh) == P(("pod", "data"))
    mesh1 = _FakeMesh({"data": 16, "model": 16})
    assert batch_spec(mesh1) == P("data")


def test_prefill_last_only_shapes(mesh):
    """Serving prefill emits only next-token logits (EXPERIMENTS §Perf it. 2)."""
    from repro.distributed.steps import make_prefill_step
    cfg = smoke_config("tinyllama-1.1b")
    with mesh:
        prefill = make_prefill_step(cfg, mesh)  # last_only default
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.zeros((2, 16), jnp.int32)
        logits = prefill(params, {"tokens": toks})
        assert logits.shape == (2, 1, cfg.vocab_size)
        full = make_prefill_step(cfg, mesh, last_only=False)
        logits_full = full(params, {"tokens": toks})
        assert logits_full.shape == (2, 16, cfg.vocab_size)
        # last_only slice == last position of the full logits
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(logits_full[:, -1]),
                                   rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------- name scopes
@pytest.fixture(scope="module")
def step_op_names(mesh):
    """op_name metadata of the robust train step compiled at smoke widths,
    per rule: RFA takes the Gram route, CM the coordinatewise one. The one
    worker of this mesh leaves CM's median a copy that XLA folds away, so
    the CM sync is also compiled alone, over 4 workers."""
    import re

    from repro.core.aragg import RobustAggregator
    from repro.distributed.packing import packed_robust_sync

    def op_names(compiled):
        return re.findall(r'op_name="([^"]*)"', compiled.as_text())

    cfg = smoke_config("mamba2-130m")
    batch = {k: jax.ShapeDtypeStruct((1, 32), jnp.int32) for k in ("tokens", "labels")}
    out = {}
    for rule in ("rfa", "cm"):
        byz = ByzConfig(aggregator=rule, mixing="bucketing", s=2, worker_momentum=0.9)
        step_fn, sh = make_train_step(cfg, byz, mesh, optimizer="adamw")
        out[rule] = op_names(jax.jit(step_fn).lower(
            sh["params_shape"], sh["opt_shape"], sh["wm_shape"],
            jax.random.PRNGKey(0), batch).compile())
    agg = RobustAggregator.from_spec("cm", mixing="bucketing", s=2)
    tree = {"a": jax.ShapeDtypeStruct((4, 3, 40), jnp.float32)}
    out["cm_sync"] = op_names(jax.jit(lambda g, k: packed_robust_sync(g, agg, key=k)[0])
                              .lower(tree, jax.random.PRNGKey(0)).compile())
    return out


@pytest.mark.parametrize("scope", SCOPES)
def test_train_step_carries_scope(step_op_names, scope):
    """Every scope the program declares reaches the compiled step's
    metadata (the sync's ``mix`` and ``kernel`` only on the CM route)."""
    names = [n for names in step_op_names.values() for n in names]
    assert any(scope in n for n in names), scope


def test_train_step_forward_and_backward_told_apart(step_op_names):
    grad = [n for n in step_op_names["rfa"] if "/step/grad/" in n]
    assert any("transpose(" in n for n in grad)
    assert any("transpose(" not in n for n in grad)
    assert any("transpose(jvp(model/unembed))" in n for n in grad)


@pytest.mark.parametrize("rule", ["rfa", "cm"])
def test_sync_phases_only_inside_step_sync(step_op_names, rule):
    """``telemetry/`` names the sync's phases and nothing else, so the
    readers that select by it see the sync alone; the phase names stay."""
    assert SYNC_PHASES == ("pack", "mix", "gram", "coeff", "kernel", "combine", "unpack")
    tele = [n for n in step_op_names[rule] if "telemetry/" in n]
    assert tele and all("step/sync/telemetry/" in n for n in tele)
    assert not [s for s in SCOPES if "telemetry/" in s and not s.startswith("telemetry/")]
