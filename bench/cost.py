"""Operations and bytes the algorithms need, computed from shapes.

These are the numerators of ``mfu.*`` and of each kernel's roofline share.
They count what the algorithm requires, not what an implementation happens
to do: W worker rows rather than the 8-row sublane tiles they are padded to,
the parameter count rather than the block-padded buffer, and no recomputed
operations. A change that removes padding or fuses passes therefore reads
as a higher share.
"""

from __future__ import annotations

import math

F32 = 4


def mamba2_dims(cfg: dict) -> dict:
    """Derived Mamba-2 sizes from a configuration file's keys."""
    ssm = cfg["ssm_cfg"]
    d = cfg["d_model"]
    din = ssm["expand"] * d
    n = ssm["d_state"]
    p = ssm["headdim"]
    return {"d": d, "din": din, "n": n, "p": p, "h": din // p,
            "k": ssm["d_conv"], "q": ssm["chunk_size"],
            "layers": cfg["n_layer"], "vocab": cfg["vocab_size"]}


def mamba2_leaf_sizes(cfg: dict) -> dict:
    """Parameter count of each leaf (one layer's leaves times the depth)."""
    m = mamba2_dims(cfg)
    d, din, n, h, k, L = m["d"], m["din"], m["n"], m["h"], m["k"], m["layers"]
    conv_ch = din + 2 * n
    return {
        "embed": m["vocab"] * d,
        "norm1": L * d,
        "in_proj": L * d * (2 * din + 2 * n + h),
        "conv_w": L * conv_ch * k,
        "conv_b": L * conv_ch,
        "A_log": L * h,
        "D": L * h,
        "dt_bias": L * h,
        "norm_scale": L * din,
        "out_proj": L * din * d,
        "final_norm": d,
    }


def mamba2_params(cfg: dict) -> int:
    return sum(mamba2_leaf_sizes(cfg).values())


def mamba2_forward_flops_per_token(cfg: dict) -> int:
    """Forward multiply-adds x 2 per token: the projections, the causal
    convolution, the chunked SSD (scores, intra-chunk outputs, chunk states
    and the carried-in state, with chunk length ``chunk_size``) and the tied
    unembedding. Norms, activations and the embedding gather are left out."""
    m = mamba2_dims(cfg)
    d, din, n, h, p, k, q = (m["d"], m["din"], m["n"], m["h"], m["p"],
                             m["k"], m["q"])
    per_layer = (
        2 * d * (2 * din + 2 * n + h)   # in_proj
        + 2 * k * (din + 2 * n)         # depthwise causal convolution
        + 2 * q * n                     # C.B scores within the chunk
        + 2 * q * h * p                 # intra-chunk outputs
        + 2 * h * p * n                 # chunk-boundary states
        + 2 * h * p * n                 # carried-in state to outputs
        + 2 * din * d                   # out_proj
    )
    return m["layers"] * per_layer + 2 * d * m["vocab"]


def train_step_flops(cfg: dict, tokens: int) -> int:
    """Forward and backward (twice the forward), no recompute."""
    return 3 * mamba2_forward_flops_per_token(cfg) * tokens


def sync_call_cost(rule: str, workers: int, s: int, n_params: int) -> tuple:
    """(flops, bytes) the whole sync needs: read the W messages once and
    write the aggregate once; the rule's arithmetic on top."""
    nbytes = (workers + 1) * n_params * F32
    return sum(f for f, _ in sync_kernel_costs(rule, workers, s, n_params).values()), nbytes


def sync_kernel_costs(rule: str, workers: int, s: int, n_params: int) -> dict:
    """Kernel name -> (flops, bytes) per sync call, for the kernels the
    packed engine runs on one device for ``rule`` with bucketing ``s``.

    ``gram``: the [W, W] Gram of the W rows, read once.
    ``mix``: M [m, W] applied to the W rows: RFA's combine (m = 1) or the
    bucketing mix ahead of the median (m = ceil(W / s)).
    ``median``: the coordinatewise median over the m bucket rows, read once,
    one row written; comparisons are not counted as operations."""
    W, n = workers, n_params
    m = math.ceil(W / s)
    if rule == "rfa":
        return {"gram": (2 * W * W * n, W * n * F32),
                "mix": (2 * W * n, (W + 1) * n * F32)}
    if rule == "cm":
        return {"mix": (2 * m * W * n, (W + m) * n * F32),
                "median": (0, (m + 1) * n * F32)}
    raise ValueError(f"no kernel costs for rule {rule!r}")


def roofline_s(flops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
