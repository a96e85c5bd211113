"""Compile the sync kernels for a described TPU v5e chip, with no chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described but not attached. That catches what interpret mode cannot: blocks
not aligned to the tiling, kernels that need more VMEM than a core has.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bucket_mix import bucket_mix
from repro.kernels.cclip_combine import cclip_combine
from repro.kernels.cclip_fused import cclip_fused_iter
from repro.kernels.cwise_median import cwise_median
from repro.kernels.pairwise_gram import pairwise_gram
from repro.kernels.trimmed_mean import cwise_trimmed_mean
from repro.kernels.weiszfeld_norms import residual_norms

D = 1 << 20  # a gradient slice of realistic length
N_TRIM = 5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


# kernel name -> (W, sharding) -> (jitted kernel, args, keyword args)
KERNELS = {
    "gram": lambda W, s: (pairwise_gram, (_f32((W, D), s),), {}),
    "median": lambda W, s: (cwise_median, (_f32((W, D), s),), {}),
    "trimmed_mean": lambda W, s: (cwise_trimmed_mean, (_f32((W, D), s),),
                                  {"n_trim": N_TRIM}),
    "mix": lambda W, s: (bucket_mix,
                         (_f32(((W + 1) // 2, W), s), _f32((W, D), s)), {}),
    "norms_coeffs": lambda W, s: (residual_norms,
                                  (_f32((W, D), s), _f32((W,), s)), {}),
    "norms_center": lambda W, s: (residual_norms, (_f32((W, D), s),),
                                  {"center": _f32((D,), s)}),
    "cclip_fused": lambda W, s: (cclip_fused_iter,
                                 (_f32((W, D), s), _f32((D,), s),
                                  _f32((W,), s)), {}),
    "cclip_combine": lambda W, s: (cclip_combine,
                                   (_f32((W, D), s), _f32((D,), s),
                                    _f32((W,), s)), {}),
}


@pytest.mark.parametrize("W", [25, 53])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_sync_kernel_compiles_for_v5e(one_chip, name, W):
    kernel, args, kwargs = KERNELS[name](W, one_chip)
    compiled = kernel.lower(*args, interpret=False, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the kernels each rule's packed sync runs, by the names the benchmark's
# trace reduction finds them under (bench/reduce.py `named`)
SYNC_KERNELS = {"rfa": ("pairwise_gram", "bucket_mix"),
                "cm": ("bucket_mix", "cwise_median")}


@pytest.mark.parametrize("rule", sorted(SYNC_KERNELS))
def test_n25_sync_kernels_keep_their_names(one_chip, monkeypatch, rule):
    """The W=25 packed sync compiled for a v5e holds an instruction named
    after each Pallas kernel its rule uses (the kernel's own ``name``)."""
    from bench import reduce
    from repro.core.aragg import RobustAggregator
    from repro.distributed.packing import packed_robust_sync
    from repro.kernels import ops

    # the kernels ask the backend, which is the CPU here, for interpret mode
    monkeypatch.setattr(ops, "_interp", lambda interpret=None: bool(interpret))
    agg = RobustAggregator.from_spec(rule, mixing="bucketing", s=2)
    tree = {"a": _f32((25, 3, 1000), one_chip), "b": _f32((25, 777), one_chip)}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda g, k: packed_robust_sync(g, agg, key=k)[0]).lower(
        tree, key).compile().as_text()
    (instrs,) = reduce.scopes_from_hlo([text]).values()
    for kernel in SYNC_KERNELS[rule]:
        assert any(reduce.named(kernel)([0, i, 0, 0, "", ""]) for i in instrs), kernel


def _quarter_tree(sharding):
    """The n=25 messages of the benchmark's sync cells: one [25, ...] fp32
    leaf per parameter of a quarter of mamba2-130m (6 layers, 12570 rows of
    the embedding), whose ``in_proj`` rows are 3352 wide, not whole tiles."""
    import json
    from pathlib import Path

    from bench.entries.robust_sync import program_config
    from repro.models import transformer as tfm

    cfg = json.loads((Path(__file__).resolve().parents[1] / "bench" / "configs"
                      / "n25-mamba2-130m-quarter.json").read_text())
    shapes = jax.eval_shape(
        lambda: tfm.init_params(program_config(cfg), jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(
        lambda x: _f32((cfg["workers"],) + x.shape, sharding), shapes)


def _ops(text, opcodes):
    """[(instruction, op_name)] of the HLO instructions with these opcodes."""
    import re

    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$", line)
        if not m:
            continue
        head = m.group(2).split(", metadata=")[0]
        if any(f" {op}(" in head for op in opcodes):
            scope = re.search(r'op_name="([^"]*)"', line)
            found.append((m.group(1), scope.group(1) if scope else "", head))
    return found


@pytest.mark.parametrize("rule", sorted(SYNC_KERNELS))
def test_n25_sync_pack_has_no_relayout_loop(one_chip, monkeypatch, rule):
    """The packed n=25 sync of the quarter tree compiled for a v5e has no
    ``while`` outside the coefficient phase (the pack once relaid
    ``in_proj`` by a loop over the 25 worker rows), and nothing pads or
    scatters the packed buffer before the gram or mix kernel reads it."""
    from repro.core.aragg import RobustAggregator
    from repro.distributed.packing import packer_for, packed_robust_sync
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interp", lambda interpret=None: bool(interpret))
    agg = RobustAggregator.from_spec(rule, mixing="bucketing", s=2)
    tree = _quarter_tree(one_chip)
    n_pad = packer_for(tree).n_pad
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    text = jax.jit(lambda g, k: packed_robust_sync(g, agg, key=k)[0]).lower(
        tree, key).compile().as_text()
    loops = [op for op in _ops(text, ("while",))
             if "telemetry/coeff" not in op[1]]
    assert not loops, loops
    pads = [op for op in _ops(text, ("pad", "scatter"))
            if ("telemetry/gram" in op[1] or "telemetry/mix" in op[1])
            and f",{n_pad}]" in op[2]]
    assert not pads, pads
    assert "pack_rows" in text


@pytest.mark.parametrize("R,C", [(768, 3352), (64, 8192)],
                         ids=["in_proj", "widest"])
@pytest.mark.parametrize("W", [1, 25, 53])
def test_pack_rows_compiles_for_v5e(one_chip, W, R, C):
    """The pack kernel at ``in_proj``'s rows (3352 wide, lane-padded to
    3456) of one mamba2-130m layer and at the widest rows it takes, on a
    fresh and on an aliased buffer."""
    from repro.distributed.packing import kernel_rows, lane_width
    from repro.kernels.pack_rows import pack_rows, supports

    assert supports(R, C)
    assert C < 8192 or not supports(R, C + 1)  # the widest rows taken
    seg = R * lane_width(C)
    shape = (kernel_rows(W), 2 * seg)

    def two_leaves(x, y):
        buf = pack_rows(x, shape, off=0, seg=seg, interpret=False)
        return pack_rows(y, buf, off=seg, seg=seg, interpret=False)

    x = _f32((W, R, C), one_chip)
    text = jax.jit(two_leaves).lower(x, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
