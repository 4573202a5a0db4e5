"""End-to-end commit-path observability (ISSUE 12 + ISSUE 15 tentpoles).

Six pieces, one subsystem:

- ``span``: per-transaction commit-path tracing — sampled txns carry a
  trace context through the wire structs, every role stamps span
  boundaries, the client assembles the exact per-stage breakdown and the
  residue is reported as ``unattributed`` (never silently dropped).
- ``registry``: the unified metrics scrape — every role's counters plus
  tracer/span tallies in one namespaced snapshot, emitted as Prometheus
  text, one JSON line, or a periodic JSONL time-series with explicit
  ``scrape_gap`` records for dead/unreachable roles.
- ``recorder``: the cluster flight recorder — an always-on, bounded
  on-disk ring of metric snapshots with first-class event annotations
  on the same timeline (ratekeeper limiting transitions, recovery
  stages, resolver-queue crossings, admission engage/release, chaos
  fault-to-heal spans, reshard/repack events, scrape gaps).
- ``slo``: rolling-baseline anomaly detection + SLO burn tracking
  (commit p99 / goodput / unknown-result rate) computed incrementally
  from the ring, with warm-up / insufficient-sample honesty flags —
  exported as status JSON ``workload.slo`` and the slo_* counters.
- ``doctor``: deterministic root-cause reports per anomaly window
  (dominant stage + co-occurring annotations + one-line verdict), the
  chaos fault-window attribution table, and the ``--doctor-gate`` CI
  line.
- ``selfcheck``: the CI face — ``python -m foundationdb_tpu.obs`` runs a
  short sim and verifies span completeness, the reconciliation identity,
  and the scrape audit in one JSON line; ``--ab`` measures the 1-in-64
  sampling overhead AND the recorder-armed overhead against the <=2%
  gate (scripts/obs_ab.sh -> OBS_AB.json).

Knobs (README "Observability"): FDB_TPU_OBS (default 0),
FDB_TPU_OBS_SAMPLE (default 64 — sample 1-in-N transactions),
FDB_TPU_RECORDER (ring path — arms the flight recorder on a server.py
controller process), FDB_TPU_RECORDER_INTERVAL (snapshot seconds,
default 5).
"""

from foundationdb_tpu.obs.registry import (
    CHAOS_DOCUMENTED_COUNTERS,
    DOCUMENTED_COUNTERS,
    RECORDER_DOCUMENTED_COUNTERS,
    MetricsPoller,
    MetricsRegistry,
    add_span_sink,
    scrape_deployed,
    scrape_deployed_async,
    scrape_sim,
)
from foundationdb_tpu.obs.doctor import (
    attribute_faults,
    diagnose,
    run_doctor_gate,
)
from foundationdb_tpu.obs.recorder import (
    ANNOTATION_CLASSES,
    TRACE_CATALOG,
    FlightRecorder,
)
from foundationdb_tpu.obs.slo import SloTracker
from foundationdb_tpu.obs.selfcheck import (
    latency_probe,
    run_overhead_ab,
    run_selfcheck,
)
from foundationdb_tpu.obs.span import (
    ENGINE_STAGES,
    MESH_ENGINE_STAGES,
    READ_STAGES,
    SUB_STAGES,
    TXN_STAGES,
    SpanSink,
    TraceContext,
    check_txn_tree,
    obs_env_default,
    obs_sample_default,
    span_sink,
)

__all__ = [
    "ANNOTATION_CLASSES",
    "CHAOS_DOCUMENTED_COUNTERS",
    "DOCUMENTED_COUNTERS",
    "ENGINE_STAGES",
    "MESH_ENGINE_STAGES",
    "FlightRecorder",
    "MetricsPoller",
    "MetricsRegistry",
    "READ_STAGES",
    "RECORDER_DOCUMENTED_COUNTERS",
    "SUB_STAGES",
    "SloTracker",
    "SpanSink",
    "TRACE_CATALOG",
    "TXN_STAGES",
    "TraceContext",
    "add_span_sink",
    "attribute_faults",
    "check_txn_tree",
    "diagnose",
    "latency_probe",
    "obs_env_default",
    "obs_sample_default",
    "run_doctor_gate",
    "run_overhead_ab",
    "run_selfcheck",
    "scrape_deployed",
    "scrape_deployed_async",
    "scrape_sim",
    "span_sink",
]
