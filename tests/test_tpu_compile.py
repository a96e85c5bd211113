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
