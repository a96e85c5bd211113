"""``bench/cost.py`` against counts made by hand."""

import json
from pathlib import Path

import pytest

from bench import cost

ROOT = Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_mamba2_130m_parameters():
    # per layer: in_proj 768*3352, conv 1792*4 + 1792, A_log/D/dt_bias 3*24,
    # gated norm 1536, out_proj 1536*768, norm1 768; embedding 50280*768
    per_layer = 768 * 3352 + 1792 * 5 + 3 * 24 + 1536 + 1536 * 768 + 768
    assert per_layer == 3_765_320
    assert cost.mamba2_params(_cfg("mamba2-130m")) == 24 * per_layer + 50280 * 768 + 768
    assert cost.mamba2_params(_cfg("mamba2-130m")) == _cfg("mamba2-130m")["params"] == 128_983_488
    quarter = _cfg("n25-mamba2-130m-quarter")
    assert cost.mamba2_params(quarter) == 6 * per_layer + 12570 * 768 + 768 == quarter["params"]


def test_mamba2_130m_flops_per_token():
    per_layer = (2 * 768 * 3352          # in_proj
                 + 2 * 4 * 1792          # causal conv
                 + 2 * 64 * 128          # scores in a 64-token chunk
                 + 2 * 64 * 24 * 64      # intra-chunk outputs
                 + 2 * 2 * 24 * 64 * 128  # chunk states, carried-in state
                 + 2 * 1536 * 768)       # out_proj
    hand = 24 * per_layer + 2 * 768 * 50280
    assert cost.mamba2_forward_flops_per_token(_cfg("mamba2-130m")) == hand == 281_751_552
    assert cost.train_step_flops(_cfg("mamba2-130m"), 2048) == 3 * hand * 2048


def test_sync_bytes_count_25_rows_not_32():
    n = 32_246_448
    flops, nbytes = cost.sync_call_cost("rfa", 25, 2, n)
    assert nbytes == 26 * n * 4
    k = cost.sync_kernel_costs("rfa", 25, 2, n)
    assert k["gram"] == (2 * 25 * 25 * n, 25 * n * 4)
    assert k["mix"] == (2 * 25 * n, 26 * n * 4)
    assert flops == k["gram"][0] + k["mix"][0]
    cm = cost.sync_kernel_costs("cm", 25, 2, n)
    assert cm["mix"] == (2 * 13 * 25 * n, 38 * n * 4)
    assert cm["median"] == (0, 14 * n * 4)


def test_roofline_bound():
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert cost.roofline_s(0, 819e9, peak) == (1.0, "bytes")
    assert cost.roofline_s(197e12, 1, peak) == (1.0, "flops")
    with pytest.raises(ValueError):
        cost.sync_kernel_costs("krum", 25, 2, 10)
