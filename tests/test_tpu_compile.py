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
