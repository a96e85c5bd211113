"""Reduction of a profiler trace to device busy time, per-op time and the
harness's host spans.

A trace is first normalised (``from_xplane``) into a plain ``Trace``: the
device operations of each chip (the "XLA Ops" line of each
``/device:TPU:<i>`` plane) with the program module each ran in and the
name scope XLA recorded for it, and the host spans
the harness itself wrote (``window``, ``batch``, ``dispatch``,
``sync_call``, ``wait``). All times are nanoseconds on the trace's clock.
The same ``Trace`` round-trips through JSON (``to_json``/``from_json``), so
the CPU tests run every reduction on a committed recorded trace.

Device ops nest (a ``while`` op spans the ops of its body), so time is
always taken as the length of a union of intervals, never as a sum.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

HOST_SPANS = ("window", "batch", "dispatch", "sync_call", "wait")

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: List[list]      # [device, instruction, start, duration, module, scope]
    spans: List[list]    # [name, start, duration]
    programs: List[str] = dataclasses.field(default_factory=list)  # timed modules

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        d["window"] = tuple(d["window"])
        return cls(**d)


def scopes_from_hlo(texts: Iterable[str]) -> dict:
    """{module: {instruction: op_name}} from compiled HLO texts."""
    out = {}
    for text in texts:
        lines = text.splitlines()
        m = _MODULE.match(lines[0]) if lines else None
        if not m:
            continue
        table = out.setdefault(m.group(1), {})
        for line in lines:
            hit = _INSTR.match(line)
            if hit:
                table[hit.group(1)] = hit.group(2)
    return out


def _module_of(name: str) -> str:
    return name.split("(")[0]


def from_xplane(path: str, hlo_texts: Sequence[str] = ()) -> Trace:
    """Normalise an ``.xplane.pb`` written by ``jax.profiler``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    scopes = scopes_from_hlo(hlo_texts)
    ops, spans = [], []
    for plane in data.planes:
        dev = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if dev:
            d = int(dev.group(1))
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted(([d, _module_of(e.name), e.start_ns, e.duration_ns]
                           for e in lines["XLA Modules"].events), key=lambda m: m[2]) \
                if "XLA Modules" in lines else []
            if "XLA Ops" in lines:
                ops += _attribute(d, lines["XLA Ops"].events, mods, scopes)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in ln.events if e.name in HOST_SPANS]
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError(f"{path}: no 'window' span in the trace")
    start, dur = win[-1][1], win[-1][2]
    return Trace((start, start + dur), ops, spans, sorted(scopes))


def _instr(name: str) -> str:
    return name.split(" = ")[0].strip().lstrip("%")


def _attribute(dev, events, mods, scopes) -> list:
    """[dev, instruction, start, dur, module, scope] for each op event; the
    module is the launch whose interval holds the op's start."""
    out, j = [], 0
    for e in sorted(events, key=lambda e: e.start_ns):
        while j + 1 < len(mods) and mods[j + 1][2] <= e.start_ns:
            j += 1
        module = ""
        if mods and mods[j][2] <= e.start_ns <= mods[j][2] + mods[j][3]:
            module = mods[j][1]
        instr = _instr(e.name)
        scope = scopes.get(module, {}).get(instr, "")
        out.append([dev, instr, e.start_ns, e.duration_ns, module, scope])
    return out


# ------------------------------------------------------------- intervals
def union_ns(intervals: Iterable[Tuple[float, float]],
             clip: Optional[Tuple[float, float]] = None) -> float:
    """Length of the union of [start, end) intervals, clipped to ``clip``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if clip:
            s, e = max(s, clip[0]), min(e, clip[1])
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _ops(trace: Trace, dev: int, pred: Callable[[list], bool] = None):
    return [(o[2], o[2] + o[3]) for o in trace.ops
            if o[0] == dev and (pred is None or pred(o))]


def busy_ns(trace: Trace, dev: int, pred=None) -> float:
    """Time within the window in which an op (matching ``pred``) ran."""
    return union_ns(_ops(trace, dev, pred), clip=trace.window)


def window_ns(trace: Trace) -> float:
    return trace.window[1] - trace.window[0]


def device_ids(trace: Trace) -> list:
    return sorted({o[0] for o in trace.ops}) or [0]


def mean_busy_ns(trace: Trace, pred=None) -> float:
    ids = device_ids(trace)
    return sum(busy_ns(trace, d, pred) for d in ids) / len(ids)


def idle_share(trace: Trace) -> float:
    """1 - busy / window, averaged over the chips."""
    return 1.0 - mean_busy_ns(trace) / window_ns(trace)


def in_scope(prefix: str) -> Callable[[list], bool]:
    """Ops whose XLA name scope contains ``prefix`` (e.g. ``telemetry/``)."""
    return lambda o: prefix in o[5]


PHASES = ("pack", "mix", "gram", "coeff", "kernel", "combine", "unpack")


def pack_ops(o: list, programs: Sequence[str]) -> bool:
    """Ops of a timed sync program that belong to the pack: those under
    ``telemetry/pack`` and those with no phase scope at all. XLA's layout
    copies for the pack's reshapes (leaf relayouts, the row-by-row copy
    loop) carry no name scope, and a sync program has no other work."""
    if o[4] not in programs:
        return False
    return "telemetry/pack" in o[5] or not any(f"telemetry/{p}" in o[5] for p in PHASES)


def named(kernel: str) -> Callable[[list], bool]:
    """Ops whose instruction is ``kernel`` or ``kernel.<n>``."""
    pat = re.compile(rf"{re.escape(kernel)}(\.\d+)?")
    return lambda o: pat.fullmatch(o[1]) is not None


# ------------------------------------------------------------- breakdown
def _label(o: list) -> str:
    phase = re.search(r"telemetry/(\w+)", o[5])
    return f"{o[1]} [{phase.group(1)}]" if phase else o[1]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[[name, seconds]] of the ``n`` ops that took most device time on chip
    0 (summed per instruction, within the window)."""
    tot = {}
    for o in trace.ops:
        if o[0] != 0:
            continue
        s, e = max(o[2], trace.window[0]), min(o[2] + o[3], trace.window[1])
        if e > s:
            tot[_label(o)] = tot.get(_label(o), 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[[host span, seconds]] of the ``n`` longest device-idle gaps on chip
    0 in the window, each named by the harness span that overlaps it most
    (``other`` where none does)."""
    merged = []
    for s, e in sorted(_ops(trace, 0)):
        s, e = max(s, trace.window[0]), min(e, trace.window[1])
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    edges = [trace.window[0]] + [x for iv in merged for x in iv] + [trace.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [s for s in trace.spans if s[0] != "window"]
    out = []
    for gs, ge in gaps[:n]:
        best, name = 0.0, "other"
        for sname, ss, sd in spans:
            ov = min(ge, ss + sd) - max(gs, ss)
            if ov > best:
                best, name = ov, sname
        out.append([name, (ge - gs) / 1e9])
    return out


# ------------------------------------------------------ per-layer helpers
def kernel_roofline(ctx, kernel: str, cost_key: str):
    """Share (%) of a kernel's roofline: the least time its work needs at
    the chip's peaks (``cost.sync_kernel_costs``) over its device time per
    unit on chip 0. None where the trace holds no such kernel."""
    from bench import cost

    t_ns = busy_ns(ctx.trace, 0, named(kernel))
    if t_ns <= 0 or cost_key not in ctx.costs.get("kernels", {}):
        return None
    flops, nbytes = ctx.costs["kernels"][cost_key]
    least_s, _ = cost.roofline_s(flops, nbytes, ctx.peak)
    return 100.0 * least_s / (t_ns / 1e9 / ctx.units)


def scope_ms_per_unit(ctx, prefix: str):
    """Device time (ms) per unit of the ops under an XLA name scope, chip 0."""
    t_ns = busy_ns(ctx.trace, 0, in_scope(prefix))
    return t_ns / 1e6 / ctx.units if t_ns > 0 else None
