"""Pallas TPU kernels for the robust-aggregation hot spots (DESIGN.md §3).

The paper's server-side cost is dominated by streaming the ``[W, d]``
stacked worker gradients (d up to 10^12 / n_chips): the Gram stats phase
(Krum/RFA/CCLIP), the coordinate-wise median, the Weiszfeld/CCLIP inner
iterations, and the Algorithm-1 mixing itself. Each is a one- or two-pass
streaming kernel with explicit BlockSpec VMEM tiling; pure-jnp test
oracles live in ``ref.py`` and the jit'd public API in ``ops.py``.

Each kernel's ``interpret=None`` resolves through ``ops._interp``: the
Pallas interpreter on CPU (Mosaic does not lower there), Mosaic on a TPU.
Every contraction asks for ``Precision.HIGHEST``: Mosaic's default rounds
fp32 operands to bf16, which is visible in an fp32 aggregate.
"""

from repro.kernels.bucket_mix import bucket_mix
from repro.kernels.cclip_combine import cclip_combine
from repro.kernels.cclip_fused import cclip_fused_iter
from repro.kernels.cwise_median import cwise_median
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pairwise_gram import pairwise_gram
from repro.kernels.selection_network import selection_program
from repro.kernels.trimmed_mean import cwise_trimmed_mean
from repro.kernels.weiszfeld_norms import residual_norms

__all__ = [
    "bucket_mix",
    "cclip_combine",
    "cclip_fused_iter",
    "cwise_median",
    "cwise_trimmed_mean",
    "flash_attention",
    "pairwise_gram",
    "residual_norms",
    "selection_program",
]
