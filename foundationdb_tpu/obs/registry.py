"""MetricsRegistry: one namespaced scrape of every role's counters.

Every role already exports counters (``get_metrics`` / ``metrics`` /
``get_rates``), but each surface had its own consumer — status JSON reads
a hand-picked subset, the ratekeeper another, the benches a third. The
registry is the single scrape: every role instance's metrics flattened
into ``<role>.<instance>.<metric>`` keys (numbers and booleans only — the
scrape is a metrics plane, not an object dump), plus the tracer's event
counts and the span sink's tallies, emitted as

- Prometheus text exposition (``to_prometheus``): one gauge per metric,
  ``process`` label per instance, ``fdb_tpu_`` prefix;
- one JSON line (``to_json_line``): the CI/tooling form every A/B script
  in this repo already parses;
- a periodic JSONL time-series (``MetricsPoller``): deployed clusters
  append one snapshot per interval for offline dashboards.

The name audit (``audit``) is the registry's hygiene contract, pinned by
tests: every metric leaf is snake_case, and no two sources collide on a
full namespaced key (a collision would silently overwrite one role's
truth with another's).
"""

from __future__ import annotations

import json
import re
from typing import Any, Callable

_SNAKE = re.compile(r"^[a-z][a-z0-9_]*$")

#: status-JSON / README counters that MUST exist in a full-cluster scrape
#: (the metrics-name audit pins these: a rename that orphans a documented
#: counter fails the battery, not a user's dashboard).
DOCUMENTED_COUNTERS = (
    "grv_proxy.grvs_served",
    "grv_proxy.queued",
    "grv_proxy.tag_throttled",
    "grv_proxy.admission_defer_ticks",
    "commit_proxy.txns_committed",
    "commit_proxy.txns_conflicted",
    "commit_proxy.conflict_losses",
    # The known-committed bound told to the tlogs at the acknowledgement,
    # and on each tlog which path moved its bound first (below).
    "commit_proxy.commit_notifies_sent",
    "resolver.batches_resolved",
    "resolver.txns_resolved",
    "resolver.txns_conflicted",
    "resolver.txns_reordered",
    "resolver.txns_cycle_aborted",
    "resolver.wave_batches",
    "commit_proxy.wave_exchanges",
    "resolver.txns_rejected_fail_safe",
    "resolver.overflow_events",
    "resolver.resolve_failures",
    # One batch on the device while the next is packed: how often the
    # overlap engages, and why the pipeline ran empty when it did not.
    "resolver.batches_overlapped",
    "resolver.pipeline_drains.no_successor",
    "resolver.pipeline_drains.headroom",
    "resolver.pipeline_drains.repack",
    "resolver.pipeline_drains.small_batch",
    # Speculative pipelined resolve (FDB_TPU_SPEC_RESOLVE): exported
    # unconditionally (zeros on serial engines) so dashboards can alert
    # on the mis-speculation rate (repaired/dispatched) without a flag
    # check, and the ratekeeper's depth clamp is auditable from the
    # scrape alone.
    "resolver.spec_dispatched",
    "resolver.spec_confirmed",
    "resolver.spec_repaired",
    "resolver.spec_depth",
    "resolver.chain_rolls",
    "resolver.queue.depth",
    # Tiered-dictionary economics (FDB_TPU_DICT_HOT_CAPACITY): exported
    # unconditionally by Resolver.get_metrics (zeros when tiering is off
    # or the engine is not resident) so the doctor's dict_thrash detector
    # and dashboards read one stable namespace.
    "resolver.engine.demotions",
    "resolver.engine.promotions",
    "resolver.engine.cold_tier_keys",
    "resolver.engine.dict_hot_occupancy",
    "resolver.engine.demotion_bytes_per_dispatch",
    "tlog.queue_bytes",
    "tlog.queue_entries",
    "tlog.kc_advances_by_notify",
    "tlog.kc_advances_by_push",
    "storage.version_lag",
    # Read plane + watch registry (foundationdb_tpu/reads/): exported by
    # every storage server, zeros while idle, so a healthy scrape always
    # carries them.
    "storage.watch_count",
    "storage.too_many_watches",
    "storage.watch_fires",
    "storage.reads.dispatches",
    "storage.reads.served",
    "storage.reads.queue_depth",
    "storage.reads.occupancy",
    "storage.reads.per_dispatch",
    "ratekeeper.tps_limit",
    # The budget's use in what it gates (read versions granted a second)
    # and the times the healthy branch raised the ceiling for it.
    "ratekeeper.grv_tps",
    "ratekeeper.ceiling_probes",
    # Recovery MTTR counters (deployed chaos subsystem): exported by BOTH
    # controllers — runtime/cluster.py (sim) and server.py
    # DeployedController — under identical names, zeros before the first
    # recovery (so the audit holds on a healthy cluster too).
    "controller.recovery_count",
    "controller.recovery_lock_s",
    "controller.recovery_salvage_s",
    "controller.recovery_recruit_s",
    "controller.recovery_total_s",
)

#: counters the deployed chaos harness (loadgen/chaos.py) contributes to
#: ITS scrape under the `chaos` role — documented/pinned like the core
#: set, but only expected in chaos-run scrapes (a plain cluster has no
#: chaos harness to export them), so they ride `missing_documented`'s
#: `extra` parameter instead of the always-on tuple.
CHAOS_DOCUMENTED_COUNTERS = (
    "chaos.chaos_faults_injected",
    "chaos.chaos_kills",
    "chaos.chaos_restarts",
    "chaos.chaos_partitions",
    "chaos.chaos_heals",
    "chaos.chaos_pauses",
)

#: counters the flight recorder + SLO tracker (obs/recorder.py, obs/slo.py)
#: contribute to a RECORDER-ARMED scrape — documented/pinned like the
#: chaos set, expected only when a recorder rides the scrape (the doctor
#: gate and recorder selfchecks pass them via `missing_documented(extra=)`).
RECORDER_DOCUMENTED_COUNTERS = (
    "recorder.recorder_snapshots",
    "recorder.recorder_annotations",
    "recorder.recorder_scrape_gaps",
    "recorder.recorder_compactions",
    "recorder.recorder_ring_records",
    "slo.slo_windows",
    "slo.slo_anomaly_windows",
    "slo.slo_incidents",
    "slo.slo_burn_violations",
    "slo.slo_insufficient_windows",
    "slo.slo_warmed_up",
)

#: counters the elastic autoscaler (autoscale/) contributes to a scrape
#: when its control loop is ARMED — scoped like the chaos/recorder sets
#: (a plain cluster has no autoscaler riding the scrape), so autoscale
#: runs pass them via `missing_documented(extra=)`.
AUTOSCALE_DOCUMENTED_COUNTERS = (
    "autoscale.autoscale_windows_observed",
    "autoscale.autoscale_scale_ups",
    "autoscale.autoscale_scale_downs",
    "autoscale.autoscale_suppressed_cooldown",
    "autoscale.autoscale_suppressed_confirm",
    "autoscale.autoscale_suppressed_bounds",
    "autoscale.autoscale_events_total",
)


def _flatten(out: dict, prefix: str, value: Any) -> None:
    """Numbers and booleans keep their key; dicts recurse with dots;
    everything else (strings, lists — e.g. hot_ranges tables) is not a
    metric and is dropped from the scrape."""
    if isinstance(value, bool):
        out[prefix] = int(value)
    elif isinstance(value, (int, float)):
        out[prefix] = value
    elif isinstance(value, dict):
        for k, v in value.items():
            _flatten(out, f"{prefix}.{k}", v)


class MetricsRegistry:
    """Collects (role, instance, metrics-dict) tuples into one snapshot."""

    def __init__(self) -> None:
        # full key -> value; plus the collision log the audit reports.
        self.values: dict[str, float] = {}
        self.collisions: list[str] = []
        self._sources: dict[str, int] = {}  # full key -> add() call seq
        self._add_seq = 0
        # Probes that FAILED this scrape: a dead/unreachable role is an
        # explicit (role, instance, reason) gap record, never a silent
        # hole — MetricsPoller/FlightRecorder turn these into scrape_gap
        # timeline records with the outage duration attached. sources_ok
        # is the complement (who DID answer), for outage-duration
        # bookkeeping across snapshots.
        self.gaps: list[dict] = []
        self.sources_ok: list[tuple[str, str]] = []

    def note_gap(self, role: str, instance: str, reason: str) -> None:
        self.gaps.append(
            {"role": role, "instance": instance, "reason": reason})

    def add(self, role: str, instance: str, metrics: "dict | None") -> None:
        if not metrics:
            return
        self.sources_ok.append((role, instance))
        self._add_seq += 1
        flat: dict[str, float] = {}
        _flatten(flat, role, metrics)
        for key, v in flat.items():
            full = f"{key}#{instance}" if instance else key
            if full in self.values and self._sources[full] != self._add_seq:
                # Two distinct sources produced the SAME namespaced key —
                # one role's truth silently overwrote another's (e.g. two
                # endpoints scraped under one instance name).
                self.collisions.append(full)
            self.values[full] = v
            self._sources[full] = self._add_seq

    def snapshot(self) -> dict:
        """{namespaced key (instance suffix stripped where unique) ->
        value} with per-instance values under ``key#instance``."""
        return dict(sorted(self.values.items()))

    def aggregated(self) -> dict:
        """Instance-summed view ``<role>.<metric> -> value`` (counters
        sum across instances — the status-JSON convention)."""
        agg: dict[str, float] = {}
        for full, v in self.values.items():
            key = full.split("#", 1)[0]
            agg[key] = agg.get(key, 0) + v
        return dict(sorted(agg.items()))

    # -- hygiene -------------------------------------------------------------

    def audit(self) -> list[str]:
        """Name-hygiene problems: non-snake_case leaves, and full-key
        collisions between distinct sources. Empty == clean.

        The ``trace.events.*`` namespace is exempt from the snake_case
        rule: its leaves are TraceEvent TYPE names, which are CamelCase
        by the reference's convention (MasterRecoveryTriggered, ...) —
        they are labels riding the scrape, not metric names."""
        problems = [f"collision: {k}" for k in self.collisions]
        for full in self.values:
            key = full.split("#", 1)[0]
            if key.startswith("trace.events."):
                continue
            for leaf in key.split("."):
                if not _SNAKE.match(leaf):
                    problems.append(f"not snake_case: {full} (leaf {leaf!r})")
                    break
        return problems

    def missing_documented(self, extra: tuple = ()) -> list[str]:
        """Documented counters absent from this scrape (prefix match on
        the aggregated keys). `extra`: additional documented names this
        scrape's scope must also carry (e.g. CHAOS_DOCUMENTED_COUNTERS
        for a chaos-run scrape)."""
        agg = self.aggregated()
        return [c for c in DOCUMENTED_COUNTERS + tuple(extra)
                if c not in agg]

    # -- emission ------------------------------------------------------------

    @staticmethod
    def _prom_name(key: str) -> str:
        return "fdb_tpu_" + re.sub(r"[^a-zA-Z0-9_]", "_", key)

    def to_prometheus(self) -> str:
        """Prometheus text exposition: one gauge per metric key, the
        instance as a ``process`` label."""
        by_name: dict[str, list[tuple[str, float]]] = {}
        for full, v in self.values.items():
            key, _, inst = full.partition("#")
            by_name.setdefault(self._prom_name(key), []).append((inst, v))
        lines = []
        for name in sorted(by_name):
            lines.append(f"# TYPE {name} gauge")
            for inst, v in sorted(by_name[name]):
                label = f'{{process="{inst}"}}' if inst else ""
                lines.append(f"{name}{label} {v}")
        return "\n".join(lines) + "\n"

    def to_json_line(self, **extra) -> str:
        doc = {"metric": "obs_scrape", **extra,
               "metrics": self.aggregated()}
        return json.dumps(doc, sort_keys=True)


def add_span_sink(reg: MetricsRegistry, sink) -> None:
    """Contribute a SpanSink's tallies + timeline counters to a scrape
    (the ``obs`` role): cumulative per-stage sum/count and the raw e2e
    histogram bins. Cumulative-counter form on purpose — the flight
    recorder's consumers (obs/slo.py, obs/doctor.py) diff CONSECUTIVE
    snapshots into per-window histograms, which is the only honest way
    to quote an interval p99 from a running sink."""
    b = sink.breakdown()

    def leaf(stage: str) -> str:
        # loop_busy:<role>, rpc_inbound:<service>.<method>: a registry
        # key nests at dots and its leaves are snake_case.
        return stage.replace(":", "_").replace(".", "_")

    reg.add("obs", "", {
        "txns_seen": b["txns_seen"],
        "txns_sampled": b["txns_sampled"],
        "spans": len(sink.spans),
        "unattributed_ms": b["unattributed_ms"],
        "stage_sum_ms": {
            leaf(name): round(h.sum_ms, 4)
            for name, h in sorted(sink.stage_hists.items())
        },
        "stage_count": {
            leaf(name): h.count
            for name, h in sorted(sink.stage_hists.items())
        },
        "e2e_sum_ms": round(sink.e2e_hist.sum_ms, 4),
        "e2e_count": sink.e2e_hist.count,
        "e2e_bins": {
            f"b{i}": n for i, n in sink.e2e_hist.to_dict()["bins"]
        },
    })


async def scrape_sim(cluster) -> MetricsRegistry:
    """Scrape every role of a SimCluster over its simulated network (the
    status-JSON discipline: an unreachable role's counters are genuinely
    invisible, never read in-process — but never a silent hole either:
    a failed probe is an explicit reg.gaps entry), plus tracer event
    counts and the span sink's tallies."""
    reg = MetricsRegistry()
    spawn = cluster.loop.spawn

    async def safe(fut):
        try:
            return await fut
        except Exception as e:
            return e

    probes: list[tuple[str, str, Any]] = []

    def probe(role: str, ep, coro) -> None:
        probes.append((role, ep.process,
                       spawn(safe(coro), name=f"obs.scrape.{ep.process}")))

    for ep in cluster.grv_proxy_eps:
        probe("grv_proxy", ep, ep.get_metrics())
    for ep in cluster.commit_proxy_eps:
        probe("commit_proxy", ep, ep.get_metrics())
    for ep in cluster.resolver_eps:
        probe("resolver", ep, ep.get_metrics())
    for ep in cluster.tlog_eps:
        probe("tlog", ep, ep.metrics())
    for ep in cluster.storage_eps:
        probe("storage", ep, ep.metrics())
    if cluster.ratekeeper_ep is not None:
        probe("ratekeeper", cluster.ratekeeper_ep,
              cluster.ratekeeper_ep.get_rates())
    ctrl_ep = getattr(cluster, "controller_ep", None)
    if ctrl_ep is not None:
        probe("controller", ctrl_ep, ctrl_ep.get_metrics())
    # Autoscaler rides the scrape in-process when armed (control loop,
    # not a cluster role — it has no endpoint of its own).
    scaler = getattr(cluster, "autoscaler", None)
    if scaler is not None:
        reg.add("autoscale", "", scaler.metrics())
    for role, inst, task in probes:
        m = await task
        if isinstance(m, BaseException):
            reg.note_gap(role, inst, type(m).__name__)
        else:
            reg.add(role, inst, m)

    tracer = getattr(cluster.loop, "tracer", None)
    if tracer is not None:
        reg.add("trace", "", {"events": dict(tracer.counts)})
    sink = getattr(cluster.loop, "span_sink", None)
    if sink is not None:
        add_span_sink(reg, sink)
    return reg


def _deployed_plans(spec: dict) -> list[tuple[str, str, str, str]]:
    plans: list[tuple[str, str, str, str]] = []
    for role, service, method in (
        ("proxy", "grv_proxy", "get_metrics"),
        ("proxy", "commit_proxy", "get_metrics"),
        ("resolver", "resolver", "get_metrics"),
        ("tlog", "tlog", "metrics"),
        ("storage", "storage", "metrics"),
        ("ratekeeper", "ratekeeper", "get_rates"),
        ("controller", "controller", "get_metrics"),
    ):
        for i, addr in enumerate(spec.get(role) or []):
            plans.append((service, f"{service}{i}", addr, method))
    return plans


async def scrape_deployed_async(loop, t, spec: dict,
                                timeout_s: float = 5.0) -> MetricsRegistry:
    """Async deployed scrape: awaitable from INSIDE a running RealLoop
    (the flight recorder's periodic task), probe RPCs time-bounded AND
    concurrent — k black-holed roles cost ONE timeout for the whole
    sweep, not k serial ones, so the recorder's snapshot cadence holds
    through exactly the outages it exists to record."""
    from foundationdb_tpu.server import bounded_rpc, parse_addr

    reg = MetricsRegistry()

    async def probe(service, inst, addr, method):
        ep = t.endpoint(parse_addr(addr), service)
        try:
            return await bounded_rpc(loop, getattr(ep, method)(),
                                     timeout_s, transport=t)
        except Exception as e:  # noqa: BLE001 — a gap record, not a crash
            return e

    plans = _deployed_plans(spec)
    tasks = [loop.spawn(probe(*plan), name=f"obs.scrape.{plan[1]}")
             for plan in plans]
    for (service, inst, _addr, _method), task in zip(plans, tasks):
        m = await task
        if isinstance(m, BaseException):
            reg.note_gap(service, inst, type(m).__name__)
        else:
            reg.add(service, inst, m)
    return reg


def scrape_deployed(loop, t, spec: dict) -> MetricsRegistry:
    """Scrape a deployed cluster over its TCP endpoints (the cli
    ``status`` role table, registry-shaped). Synchronous driver: pumps
    the caller's RealLoop like cli.Shell does; the probe plan and gap
    accounting are scrape_deployed_async's."""
    return loop.run(scrape_deployed_async(loop, t, spec), timeout=120.0)


def scrape_gap_records(reg: MetricsRegistry, t: float,
                       last_ok: dict, armed_at: float) -> list[dict]:
    """THE outage-duration bookkeeping, shared by every scrape-loop
    surface (MetricsPoller.run, the --poll CLI drive, the
    FlightRecorder): update the last-answered stamp of every source
    that DID reply this scrape, then turn each failed probe into one
    scrape_gap record carrying how long that instance has been dark
    (since its last answer, or since the scraper armed)."""
    for src in reg.sources_ok:
        last_ok[src] = t
    out = []
    for g in reg.gaps:
        key = (g["role"], g["instance"])
        since = last_ok.get(key, armed_at)
        out.append({
            "metric": "scrape_gap",
            "t": round(t, 3),
            "role": g["role"],
            "instance": g["instance"],
            "reason": g["reason"],
            "duration_s": round(t - since, 3),
        })
    return out


class MetricsPoller:
    """Periodic JSONL time-series: append one aggregated snapshot per
    interval — the deployed-cluster "scrape loop" (point Prometheus at
    to_prometheus for pull; this is the push/file form for hosts without
    a scraper).

    A dead/unreachable role is never a silent hole in the series: every
    failed probe becomes an explicit ``scrape_gap`` record on the same
    timeline — (role, instance, reason, duration since that instance
    last answered), one per affected probe per snapshot while the outage
    lasts — so an offline reader can tell "role was down" from "poller
    never looked"."""

    def __init__(self, loop, scrape: Callable, path: str,
                 interval_s: float = 5.0):
        self.loop = loop
        self.scrape = scrape  # async () -> MetricsRegistry
        self.path = path
        self.interval_s = interval_s
        self.snapshots_written = 0
        self.gaps_written = 0
        self._armed_at = loop.now
        self._last_ok: dict[tuple, float] = {}  # (role, inst) -> last t

    def gap_records(self, reg: MetricsRegistry, t: float) -> list[dict]:
        """Turn one scrape's probe failures into timeline records (the
        shared scrape_gap_records bookkeeping)."""
        return scrape_gap_records(reg, t, self._last_ok, self._armed_at)

    async def run(self) -> None:
        while True:
            await self.loop.sleep(self.interval_s)
            reg = await self.scrape()
            now = self.loop.now
            lines = [json.dumps(r, sort_keys=True)
                     for r in self.gap_records(reg, now)]
            self.gaps_written += len(lines)
            lines.append(reg.to_json_line(
                t=round(now, 3), seq=self.snapshots_written))
            with open(self.path, "a", encoding="utf-8") as f:
                f.write("\n".join(lines) + "\n")
            self.snapshots_written += 1
