"""Cluster-wide consistency checker: replica/DR/region byte-parity audit.

Reference: fdbserver/workloads/ConsistencyCheck.actor.cpp — the upstream
subsystem that walks the shard map and verifies every replica of every
team holds byte-identical data at one read version, served through each
member's OWN read path (never a shared storage peek, which would hide a
divergent serve-side view).

Pieces:
- ``scanner.RangeScanner``  — chunked, paced byte-comparison of one key
  range across N members, with exact first-divergent-key reports.
- ``checker.ConsistencyChecker`` — walks the shard map, resolves team
  membership (including remote-region standbys), tolerates in-flight
  data movement, optionally audits a DR secondary, and aggregates one
  machine-readable divergence report (status JSON ``workload.consistency``,
  trace events per divergence).
- ``python -m foundationdb_tpu.consistency`` — self-contained audit of a
  replicated SimCluster under load; one JSON line (the CI stage).
- ``cli consistencycheck`` — the same walk against a deployed cluster.
"""

from foundationdb_tpu.consistency.checker import (  # noqa: F401
    ConsistencyChecker,
    run_deployed_check,
)
from foundationdb_tpu.consistency.scanner import (  # noqa: F401
    Divergence,
    RangeScanner,
    RatekeeperPacer,
    ScanResult,
    printable,
)
