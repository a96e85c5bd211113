"""The train step's scope readers on a hand-built trace: known scopes,
nested ops, an op of another program, another chip, an op XLA hoisted out
of every step scope and an unscoped op nested in a scoped loop."""

from pathlib import Path

import pytest

from bench import reduce, run as harness, scopes

ROOT = Path(__file__).resolve().parents[2]
STEP, MS = "jit_step_fn", 1e6
UNITS = 2


def _op(instr, start, dur, scope, module=STEP, dev=0):
    return [dev, instr, start * MS, dur * MS, module, scope]


OPS = [
    _op("while.1", 0, 100, "jit(step_fn)/step/grad/vmap(jvp())/while"),
    _op("fusion.1", 10, 30, "jit(step_fn)/step/grad/vmap(jvp())/while/body/closed_call/ssd/chunk/mul"),
    _op("fusion.2", 50, 20, "jit(step_fn)/step/grad/vmap(jvp())/while/body/closed_call/ssd/state/dot_general"),
    _op("fusion.3", 100, 40, "jit(step_fn)/step/grad/vmap(jvp(model/unembed))/dot_general"),
    _op("fusion.4", 140, 60, "jit(step_fn)/step/grad/vmap(transpose(jvp(model/unembed)))/dot_general"),
    _op("while.2", 200, 200, "jit(step_fn)/step/grad/vmap(transpose(jvp()))/while"),
    _op("copy-done.3", 270, 10, ""),  # unscoped, inside while.2: counted with it
    _op("fusion.5", 210, 50, "jit(step_fn)/step/grad/vmap(transpose(jvp()))/while/body/closed_call/ssd/chunk/mul"),
    _op("fusion.6", 400, 30, "jit(step_fn)/step/momentum/mul"),
    _op("pairwise_gram.1", 430, 70, "jit(step_fn)/step/sync/telemetry/gram/jit(pairwise_gram)/pairwise_gram/pallas_call"),
    _op("fusion.7", 500, 100, "jit(step_fn)/step/optimizer/add"),
    _op("copy.1", 600, 20, ""),
    _op("fusion.1", 620, 10, "", module="jit__batch"),
    _op("iota.8", 630, 10, "jit(step_fn)/ssd/chunk/jit(tril)/iota"),
    _op("fusion.9", 0, 500, "jit(step_fn)/step/optimizer/add", dev=1),
]

# ms per step on chip 0
WANT = {"fwd_ms.train": (100 + 40) / UNITS, "bwd_ms.train": (60 + 200) / UNITS,
        "ssd_scan_ms.train": (30 + 20 + 50 + 10) / UNITS, "unembed_ms.train": (40 + 60) / UNITS,
        "momentum_ms.train": 30 / UNITS, "optimizer_ms.train": 100 / UNITS,
        "unscoped_ms.train": (20 + 10) / UNITS}


def _ctx(ops):
    trace = reduce.Trace((0.0, 1000 * MS), ops, [["window", 0.0, 1000 * MS]], [STEP])
    return harness.Context(trace, 1.0, UNITS, {}, {}, 1)


def _read(ctx, metric):
    got = harness.read_metrics(ctx, [{"name": metric, "unit": "ms",
                                      "reader": ROOT / f"bench/metrics/{metric}.py"}])
    return got.get(metric, {}).get("value")


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_hand_built_trace(metric):
    assert _read(_ctx(OPS), metric) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_on_unscoped_program_reads_nothing(metric):
    """The step as it was before it named its phases: the sync's scopes
    alone. Every reader returns nothing and none raises."""
    bare = [o[:5] + [o[5].replace("step/", "").replace("ssd/", "").replace("model/", "")]
            for o in OPS]
    assert _read(_ctx(bare), metric) is None


def test_step_closes_on_busy_time():
    ctx = _ctx(OPS)
    parts = sum(WANT[m] for m in WANT if m not in ("ssd_scan_ms.train", "unembed_ms.train"))
    sync = reduce.scope_ms_per_unit(ctx, "step/sync")
    program = reduce.busy_ns(ctx.trace, 0, lambda o: o[4] in ctx.trace.programs) / MS / UNITS
    assert parts + sync == pytest.approx(program)


def test_sync_phases_match_the_program():
    from repro.telemetry.profiling import SYNC_PHASES

    assert reduce.PHASES == SYNC_PHASES
