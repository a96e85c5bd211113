"""In-graph telemetry engine: device-resident robustness metrics, phase
tracing, and structured run logs.

The paper's central claim — bucketing restores robust-aggregator guarantees
under heterogeneity — is only *observable* through quantities the hot paths
compute anyway: clip fractions and radii (CCLIP), Weiszfeld residuals (RFA),
Krum selection scores, trim masks (TM), and per-bucket dispersion.
Time-coupled attacks (ALIE, IPM, mimic) are diagnosed by watching these
statistics drift across rounds. This package makes them first-class:

  registry.py   metric catalogue: every metric the probes may emit, with
                phase / shape-kind / doc — the JSONL schema is validated
                against it.
  inflight.py   ``InflightMetrics`` — the functional accumulator threaded
                through the jitted hot paths. Metrics are ordinary device
                arrays riding OUT of the graph as extra outputs (no host
                callbacks, no extra collectives on the off path) and are
                drained asynchronously host-side.
  probes.py     the probe math shared by the stacked and packed engines
                (trim masks, per-bucket dispersion, worker deviation).
  profiling.py  ``phase()`` name scopes (``jax.named_scope`` only: HLO
                metadata, no host span) on the sync's pack -> gram -> mix ->
                kernel -> unpack, the train step's and the model's phases
                (``SCOPES``), read from a profiler's device trace; and the
                one-call ``trace_capture`` jax.profiler helper.
  events.py     host-side JSONL structured event log + ring-buffered step
                timing.

Zero-overhead-when-off contract: with ``telemetry=False`` (the default
everywhere) the traced program is IDENTICAL to the pre-telemetry seed —
bit-exact outputs, byte-identical collective budgets. This is machine-
checked by the ``sync_telemetry_off_rfa_bucketing`` analysis target
(``python -m repro.analysis``), which compares the telemetry-off compile
against the committed base budget with ZERO tolerance. See
docs/observability.md.
"""

from repro.telemetry.events import EventLog, RingTimer, validate_event, validate_jsonl
from repro.telemetry.inflight import InflightMetrics
from repro.telemetry.profiling import phase, trace_capture
from repro.telemetry.registry import MetricSpec, catalogue, get_metric, register

__all__ = [
    "EventLog",
    "InflightMetrics",
    "MetricSpec",
    "RingTimer",
    "catalogue",
    "get_metric",
    "phase",
    "register",
    "trace_capture",
    "validate_event",
    "validate_jsonl",
]
