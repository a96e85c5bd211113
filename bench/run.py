"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration
file, its traffic file (``bench/traffic/<traffic>.json``), the entry that
drives the program (``bench/entries/<entry>.py``) and each per-layer
metric's reader (``bench/metrics/<metric>.py``) are all found by name, so a
new cell, configuration or metric is new files plus new entries there.

A run: set-up (device check, compile cache, the entry's weights, inputs,
compilation, warm-up and, for training, the first steps the check follows),
then a closed-loop window of ``--seconds`` on the host clock that ends on
``block_until_ready`` of the last unit's output, then the peak device
memory, then the comparison with the plain reference that decides
``correct``. With ``--trace 0`` the profiler is off and the metrics are the
cell's end-to-end ones; with ``--trace 1`` the window runs under the
profiler and the metrics are the per-layer ones read from its trace, with a
``breakdown``. The last line of standard output is one JSON object; the
numbers compared are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, or on a chip
whose kind has no row in ``bench/peaks.json``, the run exits non-zero and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
PIPELINE_DEPTH = 2


class SpecError(Exception):
    """The benchmark's files do not describe the cell asked for."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(name: str) -> dict:
    """Everything a cell needs, found by name: its BENCHMARK.json entry,
    configuration, traffic, entry module path, and the end-to-end and
    per-layer metrics it reports (each per-layer metric with its reader)."""
    spec = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if cell["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config {cell['config']!r}")
    config = _load_json(ROOT / configs[cell["config"]]["file"])
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    if not (BENCH / "entries" / f"{traffic['entry']}.py").is_file():
        raise SpecError(f"missing bench/entries/{traffic['entry']}.py")

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [dict(m, reader=BENCH / "metrics" / f"{m['name']}.py")
             for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in e2e_names else [])]
    for m in layer:
        if not m["reader"].is_file():
            raise SpecError(f"missing bench/metrics/{m['name']}.py")
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer}


def check_devices(jax, chips: int, peaks: dict) -> dict:
    """The device as JAX reports it; refuses anything but enough TPUs of a
    kind in the peaks table."""
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SpecError(f"needs a TPU; JAX found {dev.platform}")
    if len(devs) < chips:
        raise SpecError(f"the cell asks for {chips} chips; JAX found {len(devs)}")
    if dev.device_kind not in peaks["kinds"]:
        raise SpecError(f"no peaks for device kind {dev.device_kind!r} in bench/peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": chips}


def use_cache(jax) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, so a cell's second run compiles nothing."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def drive(cell, seconds: float, span) -> tuple:
    """Closed loop for ``seconds``: dispatch units back to back, at most
    ``PIPELINE_DEPTH`` in flight, then block on the last. Returns (units
    completed, window seconds on the host clock)."""
    pending = collections.deque()
    i = 0
    t0 = time.perf_counter()
    while True:
        pending.append(cell.dispatch(i))
        i += 1
        if len(pending) > PIPELINE_DEPTH:
            with span("wait"):
                cell.block(pending.popleft())
        if time.perf_counter() - t0 >= seconds:
            break
    with span("wait"):
        cell.block(pending[-1])
    return i, time.perf_counter() - t0


def peak_bytes(jax, chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


class Context:
    """What a per-layer metric's reader gets: the normalised trace (or
    None), the window, the units done in it, the entry's costs, the chip's
    peaks and the chip count."""

    def __init__(self, trace, window_s, units, costs, peak, chips):
        self.trace, self.window_s, self.units = trace, window_s, units
        self.costs, self.peak, self.chips = costs, peak, chips


def read_metrics(ctx: Context, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = _load_module(m["reader"], f"bench_metric_{m['name']}").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, require_tpu: bool = True, found: dict = None) -> dict:
    """One run of one cell; returns the result line's object. Tests pass
    ``require_tpu=False`` and a ``found`` of their own (a small config)."""
    found = found or resolve(args.workload)
    import jax

    peaks = _load_json(BENCH / "peaks.json")
    chips = found["cell"]["chips"]
    if require_tpu:
        device = check_devices(jax, chips, peaks)
        use_cache(jax)
    else:
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips}
    peak = peaks["kinds"].get(device["kind"])

    entry = importlib.import_module(f"bench.entries.{found['traffic']['entry']}")
    cell = entry.Cell(found["config"], found["traffic"], args.seed, chips)
    cell.setup()
    setup_s = time.perf_counter() - T_START

    span = jax.profiler.TraceAnnotation
    if args.trace:
        tracedir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tracedir)
    try:
        with span("window"):
            units, window_s = drive(cell, args.seconds, span)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    device["memory_peak_bytes"] = peak_bytes(jax, chips)
    hlo, costs = cell.hlo, cell.costs()
    cell.release()

    result = {"attempted": units, "failed": 0}
    if args.trace:
        from bench import reduce

        xplane = next(Path(tracedir).rglob("*.xplane.pb"))
        trace = reduce.from_xplane(str(xplane), hlo)
        shutil.rmtree(tracedir, ignore_errors=True)
        ctx = Context(trace, window_s, units, costs, peak, chips)
        result["metrics"] = read_metrics(ctx, found["per_layer"])
        device["busy_s"] = reduce.mean_busy_ns(trace) / 1e9
        device["window_s"] = reduce.window_ns(trace) / 1e9
        result["breakdown"] = {"device_ops": reduce.top_ops(trace),
                               "idle_gaps": reduce.idle_gaps(trace)}
    else:
        e2e = {"setup_s": setup_s, cell.unit_metric: 1e3 * window_s / units}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in found["end_to_end"] if m["name"] in e2e}

    numbers = cell.check()
    result["correct"] = all(v <= lim for _, v, lim in numbers)
    result["device"] = device
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in numbers}
    for name, v, lim in numbers:
        print(f"check {name} = {v!r} (limit {lim!r})", file=sys.stderr, flush=True)
    order = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]
    return {k: result[k] for k in order if k in result}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
