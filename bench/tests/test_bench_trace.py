"""The trace reduction and the per-layer readers, on a recorded TPU v5e
trace of three RFA syncs of the quarter tree (window, ops with their
modules and name scopes, host spans), committed as a fixture."""

import json
from pathlib import Path

import pytest

from bench import cost, reduce, run as harness

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CALLS = 3


@pytest.fixture(scope="module")
def trace():
    return reduce.Trace.from_json((HERE / "fixtures" / "sync_rfa_trace.json").read_text())


@pytest.fixture(scope="module")
def ctx(trace):
    cfg = json.loads((ROOT / "bench/configs/n25-mamba2-130m-quarter.json").read_text())
    n = cost.mamba2_params(cfg)
    flops, nbytes = cost.sync_call_cost("rfa", 25, 2, n)
    costs = {"flops_per_unit": flops, "bytes_per_unit": nbytes,
             "kernels": cost.sync_kernel_costs("rfa", 25, 2, n)}
    peaks = json.loads((ROOT / "bench/peaks.json").read_text())
    return harness.Context(trace, reduce.window_ns(trace) / 1e9, CALLS, costs,
                           peaks["kinds"]["TPU v5 lite"], 1)


def test_union_merges_nested_and_clips():
    assert reduce.union_ns([(0, 10), (2, 5), (8, 12), (20, 30)]) == 22
    assert reduce.union_ns([(0, 10), (20, 30)], clip=(5, 25)) == 10
    assert reduce.union_ns([]) == 0


def test_idle_share(trace):
    idle = reduce.idle_share(trace)
    assert 0.0 < idle < 0.02
    busy = reduce.busy_ns(trace, 0)
    assert idle == pytest.approx(1 - busy / reduce.window_ns(trace))


def test_kernel_time_by_name(trace):
    per_call = {k: reduce.busy_ns(trace, 0, reduce.named(k)) / 1e6 / CALLS
                for k in ("pairwise_gram", "bucket_mix", "cwise_median")}
    assert per_call["pairwise_gram"] == pytest.approx(10.92, abs=0.01)
    assert per_call["bucket_mix"] == pytest.approx(10.27, abs=0.01)
    assert per_call["cwise_median"] == 0
    assert not reduce.named("bucket_mix")([0, "bucket_mix_other.1", 0, 1, "", ""])


def test_telemetry_attribution(trace):
    by_instr = {o[1]: o[5] for o in trace.ops}
    assert "telemetry/gram" in by_instr["pairwise_gram.1"]
    assert "telemetry/combine" in by_instr["bucket_mix.1"]
    pack = {o[1] for o in trace.ops if reduce.pack_ops(o, trace.programs)}
    assert {"while.8", "copy.27"} <= pack          # XLA's unscoped relayouts
    assert "pairwise_gram.1" not in pack and "bucket_mix.1" not in pack
    program = reduce.busy_ns(trace, 0, lambda o: o[4] in trace.programs)
    phases = sum(reduce.busy_ns(trace, 0, reduce.in_scope(f"telemetry/{p}"))
                 for p in reduce.PHASES if p != "pack")
    packed = reduce.busy_ns(trace, 0, lambda o: reduce.pack_ops(o, trace.programs))
    assert packed + phases == pytest.approx(program, rel=1e-3)


def test_breakdown(trace):
    top = reduce.top_ops(trace)
    assert len(top) == 10 and top[0][0] == "while.8"
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = reduce.idle_gaps(trace)
    assert 0 < len(gaps) <= 10
    assert all(name in reduce.HOST_SPANS + ("other",) for name, _ in gaps)


def test_json_roundtrip(trace):
    again = reduce.Trace.from_json(trace.to_json())
    assert again == trace


def test_scopes_from_hlo():
    text = ('HloModule jit_step, entry_computation_layout={}\n'
            '  %a.1 = f32[2]{0} add(%x, %y), metadata={op_name="jit(step)/telemetry/pack/add"}\n'
            '  ROOT %b = f32[2]{0} copy(%a.1)\n')
    assert reduce.scopes_from_hlo([text]) == {"jit_step": {"a.1": "jit(step)/telemetry/pack/add"}}


@pytest.mark.parametrize("metric,lo,hi", [
    ("device_idle.sync", 0.0, 2.0),
    ("mfu.sync", 2.5, 4.0),
    ("pack_ms.sync", 85.0, 95.0),
    ("gram_roofline", 30.0, 45.0),
    ("mix_roofline", 35.0, 45.0),
])
def test_sync_readers_on_recorded_trace(ctx, metric, lo, hi):
    value = harness.read_metrics(ctx, [{"name": metric, "unit": "%",
                                        "reader": ROOT / f"bench/metrics/{metric}.py"}])
    assert lo < value[metric]["value"] < hi


def test_reader_finds_nothing_returns_nothing(ctx):
    got = harness.read_metrics(ctx, [{"name": "median_roofline", "unit": "%",
                                      "reader": ROOT / "bench/metrics/median_roofline.py"}])
    assert got == {}
