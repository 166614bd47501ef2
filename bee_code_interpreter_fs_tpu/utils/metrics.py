"""Minimal Prometheus-text-format metrics registry (dependency-free).

The reference has no metrics at all (SURVEY.md §5 "Metrics / logging /
observability": "No metrics endpoint, no Prometheus"). This closes that gap
for the control plane: counters, gauges (incl. scrape-time callbacks for pool
depth), and histograms with request-latency buckets, rendered at
``GET /metrics`` by the HTTP server. prometheus_client is not in this
environment, so the text exposition format is emitted directly.
"""

from __future__ import annotations

import threading
from collections.abc import Callable, Iterable

# The exposition format's REQUIRED Content-Type (Prometheus text format
# 0.0.4). A bare "text/plain" makes strict scrapers (and conformance
# checkers) treat the payload as unversioned; GET /metrics serves this.
PROMETHEUS_CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# Buckets tuned for the quantities this service measures: sub-100ms warm-pool
# hits through multi-second TPU cold spawns and minute-scale user code.
DEFAULT_BUCKETS = (
    0.005,
    0.01,
    0.025,
    0.05,
    0.1,
    0.25,
    0.5,
    1.0,
    2.5,
    5.0,
    10.0,
    30.0,
    60.0,
    120.0,
    300.0,
)


def _escape_label(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{key}="{_escape_label(str(value))}"' for key, value in sorted(labels.items())
    )
    return "{" + inner + "}"


def _fmt_value(value: float) -> str:
    if value == float("inf"):
        return "+Inf"
    if float(value).is_integer():
        return str(int(value))
    return repr(float(value))


class Counter:
    def __init__(self, name: str, help_text: str, label_names: tuple[str, ...] = ()):
        self.name = name
        self.help = help_text
        self.label_names = label_names
        self._values: dict[tuple[str, ...], float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = tuple(str(labels.get(n, "")) for n in self.label_names)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def samples(self) -> list[tuple[dict[str, str], float]]:
        """Structured snapshot (label dict, value) — the OTLP export feed."""
        with self._lock:
            items = sorted(self._values.items())
        if not items and not self.label_names:
            items = [((), 0.0)]
        return [(dict(zip(self.label_names, key)), value) for key, value in items]

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} counter"
        for labels, value in self.samples():
            yield f"{self.name}{_fmt_labels(labels)} {_fmt_value(value)}"


class Gauge:
    """A settable gauge; ``callback`` makes it computed at scrape time
    (used for pool depth, where the deque is the source of truth)."""

    def __init__(
        self,
        name: str,
        help_text: str,
        label_names: tuple[str, ...] = (),
        callback: Callable[[], dict[tuple[str, ...], float]] | None = None,
    ):
        self.name = name
        self.help = help_text
        self.label_names = label_names
        self.callback = callback
        self._values: dict[tuple[str, ...], float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels: str) -> None:
        key = tuple(str(labels.get(n, "")) for n in self.label_names)
        with self._lock:
            self._values[key] = float(value)

    def samples(self) -> list[tuple[dict[str, str], float]]:
        """Structured snapshot (label dict, value) — the OTLP export feed.
        Callback gauges compute here, i.e. at scrape/export time."""
        if self.callback is not None:
            items = sorted(self.callback().items())
        else:
            with self._lock:
                items = sorted(self._values.items())
        if not items and not self.label_names:
            items = [((), 0.0)]
        return [(dict(zip(self.label_names, key)), value) for key, value in items]

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} gauge"
        for labels, value in self.samples():
            yield f"{self.name}{_fmt_labels(labels)} {_fmt_value(value)}"


class Histogram:
    def __init__(
        self,
        name: str,
        help_text: str,
        label_names: tuple[str, ...] = (),
        buckets: tuple[float, ...] = DEFAULT_BUCKETS,
    ):
        self.name = name
        self.help = help_text
        self.label_names = label_names
        self.buckets = tuple(sorted(buckets))
        self._counts: dict[tuple[str, ...], list[int]] = {}
        self._sums: dict[tuple[str, ...], float] = {}
        self._totals: dict[tuple[str, ...], int] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, **labels: str) -> None:
        key = tuple(str(labels.get(n, "")) for n in self.label_names)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1

    def samples(self) -> list[tuple[dict[str, str], list[int], float, int]]:
        """Structured snapshot per label set: (labels, cumulative bucket
        counts aligned with `self.buckets`, sum, total count) — the OTLP
        export feed (which converts cumulative to per-bucket counts)."""
        with self._lock:
            keys = sorted(self._counts)
            snapshot = [
                (
                    dict(zip(self.label_names, key)),
                    list(self._counts[key]),
                    self._sums[key],
                    self._totals[key],
                )
                for key in keys
            ]
        return snapshot

    def render(self) -> Iterable[str]:
        yield f"# HELP {self.name} {self.help}"
        yield f"# TYPE {self.name} histogram"
        for labels, counts, total_sum, total in self.samples():
            for bound, count in zip(self.buckets, counts):
                bucket_labels = {**labels, "le": _fmt_value(bound)}
                yield f"{self.name}_bucket{_fmt_labels(bucket_labels)} {count}"
            inf_labels = {**labels, "le": "+Inf"}
            yield f"{self.name}_bucket{_fmt_labels(inf_labels)} {total}"
            yield f"{self.name}_sum{_fmt_labels(labels)} {_fmt_value(total_sum)}"
            yield f"{self.name}_count{_fmt_labels(labels)} {total}"


class MetricsRegistry:
    def __init__(self) -> None:
        self._metrics: list[Counter | Gauge | Histogram] = []
        self._lock = threading.Lock()

    def register(self, metric):
        with self._lock:
            if any(m.name == metric.name for m in self._metrics):
                # Two registrations under one family name would emit
                # duplicate `# HELP`/`# TYPE` headers (forbidden by the
                # exposition format), split the family's sample group, and
                # — if the label sets ever collide — produce duplicate
                # series that fail the whole scrape. Reject at the source:
                # the caller is holding a stale binding.
                raise ValueError(
                    f"metric family {metric.name!r} is already registered"
                )
            self._metrics.append(metric)
        return metric

    def counter(self, name: str, help_text: str, label_names: tuple[str, ...] = ()):
        return self.register(Counter(name, help_text, label_names))

    def gauge(
        self,
        name: str,
        help_text: str,
        label_names: tuple[str, ...] = (),
        callback=None,
    ):
        return self.register(Gauge(name, help_text, label_names, callback))

    def histogram(
        self,
        name: str,
        help_text: str,
        label_names: tuple[str, ...] = (),
        buckets: tuple[float, ...] = DEFAULT_BUCKETS,
    ):
        return self.register(Histogram(name, help_text, label_names, buckets))

    def render(self) -> str:
        """Prometheus text exposition. `# HELP`/`# TYPE` appear exactly once
        per metric family — guaranteed structurally, since register()
        rejects duplicate family names."""
        with self._lock:
            metrics = list(self._metrics)
        lines: list[str] = []
        for metric in metrics:
            lines.extend(metric.render())
        return "\n".join(lines) + "\n"

    def collect(self) -> list[dict]:
        """Structured snapshot of every family for the OTLP exporter:
        [{"name", "type", "help", "samples": ...}] where counter/gauge
        samples are (labels, value) pairs and histogram samples carry
        (labels, cumulative bucket counts, sum, count) plus "buckets"
        (the explicit bounds)."""
        with self._lock:
            metrics = list(self._metrics)
        families: list[dict] = []
        for metric in metrics:
            if isinstance(metric, Histogram):
                families.append(
                    {
                        "name": metric.name,
                        "type": "histogram",
                        "help": metric.help,
                        "buckets": list(metric.buckets),
                        "samples": metric.samples(),
                    }
                )
            else:
                kind = "counter" if isinstance(metric, Counter) else "gauge"
                try:
                    samples = metric.samples()
                except Exception:  # noqa: BLE001 — a callback gauge must
                    # never take the whole export down with it
                    samples = []
                families.append(
                    {
                        "name": metric.name,
                        "type": kind,
                        "help": metric.help,
                        "samples": samples,
                    }
                )
        return families


class ExecutorMetrics:
    """The service's metric set, bound to one CodeExecutor."""

    def __init__(self, registry: MetricsRegistry | None = None) -> None:
        self.registry = registry or MetricsRegistry()
        self.executions = self.registry.counter(
            "code_interpreter_executions_total",
            "Execute requests by outcome (ok/user_error/infra_error).",
            ("outcome",),
        )
        self.warm_hits = self.registry.counter(
            "code_interpreter_warm_runner_executions_total",
            "Executions served by a pre-initialized (warm) sandbox runner.",
        )
        self.recycles = self.registry.counter(
            "code_interpreter_sandbox_recycles_total",
            "Sandboxes recycled back into the pool after a request "
            "(generation turnover via /reset — the TPU lease survived).",
        )
        self.session_executions = self.registry.counter(
            "code_interpreter_session_executions_total",
            "Executions routed to an executor_id session sandbox.",
        )
        self.phase_seconds = self.registry.histogram(
            "code_interpreter_phase_seconds",
            "Per-request phase latency (queue_wait/upload/exec/download).",
            ("phase",),
        )
        self.spawn_seconds = self.registry.histogram(
            "code_interpreter_sandbox_spawn_seconds",
            "Sandbox spawn-to-ready latency by chip-count lane.",
            ("chip_count",),
        )
        self.retry_attempts = self.registry.counter(
            "code_interpreter_retry_attempts_total",
            "Retries performed by the in-repo retry engine, by operation "
            "(spawn/execute). Counts retries, not first attempts.",
            ("operation",),
        )
        self.injected_faults = self.registry.counter(
            "code_interpreter_injected_faults_total",
            "Faults injected by the chaos backend, by fault type. Nonzero "
            "outside a chaos run is a deployment error.",
            ("fault",),
        )
        self.breaker_rejections = self.registry.counter(
            "code_interpreter_breaker_rejections_total",
            "Requests failed fast because a lane's spawn circuit was open.",
            ("chip_count",),
        )
        self.limit_violations = self.registry.counter(
            "code_interpreter_limit_violations_total",
            "Typed sandbox resource-limit violations by chip-count lane and "
            "kind (oom/disk_quota/nproc/cpu_time/output_cap). Deterministic "
            "client overruns, never retried.",
            ("chip_count", "kind"),
        )
        # Batched execution lanes: dispatches by outcome (ok /
        # error_fallback / violation_fallback) and jobs by how they were
        # served (batched, or serial_<reason> when a window under-filled or
        # a batch fault fell back). batched >> serial_* is the subsystem
        # paying for itself; rising fallbacks are the alarm.
        self.batch_dispatches = self.registry.counter(
            "code_interpreter_batch_dispatches_total",
            "Fused multi-job dispatches by outcome (ok = demuxed cleanly; "
            "error_fallback / violation_fallback = batch-level fault, jobs "
            "re-ran serially).",
            ("outcome",),
        )
        self.batch_jobs = self.registry.counter(
            "code_interpreter_batch_jobs_total",
            "Batch-eligible jobs by how they were ultimately served "
            "(batched = rode a fused dispatch; serial_* = fell back to the "
            "serial path, by reason).",
            ("outcome",),
        )
        # Warm-pool autoscaling (services/autoscaler.py): target moves and
        # idle reaps, by lane and direction. A healthy adaptive pool shows
        # up/down/reap all moving with the traffic shape; up with no
        # down/reap means targets ratchet (check the sweep is running).
        self.pool_scale_events = self.registry.counter(
            "code_interpreter_pool_scale_events_total",
            "Warm-pool autoscaler events by chip-count lane and direction "
            "(up = target raised on demand, down = hysteresis step-down, "
            "reap = excess idle warm sandbox disposed).",
            ("chip_count", "direction"),
        )
        self.scheduler_queue_wait = self.registry.histogram(
            "code_interpreter_scheduler_queue_wait_seconds",
            "Seconds a request queued for a sandbox slot before its grant, "
            "by lane, tenant, and priority class.",
            ("chip_count", "tenant", "priority"),
        )
        self.scheduler_grants = self.registry.counter(
            "code_interpreter_scheduler_grants_total",
            "Sandbox-slot grants issued by the fair-share scheduler, by "
            "lane, tenant, and priority class (the fairness observable: "
            "under contention, per-tenant rates track configured weights).",
            ("chip_count", "tenant", "priority"),
        )
        self.scheduler_sheds = self.registry.counter(
            "code_interpreter_scheduler_sheds_total",
            "Requests shed at admission (reason=depth: per-tenant queue "
            "bound; reason=deadline: declared deadline cannot beat the "
            "estimated queue wait).",
            ("chip_count", "tenant", "priority", "reason"),
        )
        # Transfer observability: how many bytes the delta workspace sync
        # actually moved vs. negotiated away. On a session turn with
        # unchanged inputs the skipped counters move and the moved ones
        # don't — that asymmetry IS the feature working.
        byte_buckets = (
            1024.0,
            10240.0,
            102400.0,
            1048576.0,
            10485760.0,
            104857600.0,
            1073741824.0,
        )
        self.transfer_bytes = self.registry.counter(
            "code_interpreter_transfer_bytes_total",
            "Workspace file bytes actually moved between control plane and "
            "sandboxes, by direction (upload/download).",
            ("direction",),
        )
        self.transfer_files = self.registry.counter(
            "code_interpreter_transfer_files_total",
            "Workspace files actually moved, by direction.",
            ("direction",),
        )
        self.transfer_copied_bytes = self.registry.counter(
            "code_interpreter_transfer_copied_bytes_total",
            "Input file bytes a sandbox's own server copied from the storage "
            "directory (also counted as uploaded).",
        )
        self.transfer_copied_files = self.registry.counter(
            "code_interpreter_transfer_copied_files_total",
            "Input files a sandbox's own server copied from the storage "
            "directory (also counted as uploaded).",
        )
        self.transfer_skipped_bytes = self.registry.counter(
            "code_interpreter_transfer_skipped_bytes_total",
            "Workspace file bytes NOT moved thanks to manifest delta "
            "uploads / hash-negotiated downloads, by direction.",
            ("direction",),
        )
        self.transfer_skipped_files = self.registry.counter(
            "code_interpreter_transfer_skipped_files_total",
            "Workspace files skipped by manifest/hash negotiation, "
            "by direction.",
            ("direction",),
        )
        self.transfer_phase_bytes = self.registry.histogram(
            "code_interpreter_transfer_phase_bytes",
            "Bytes moved per Execute per transfer phase (upload/download).",
            ("phase",),
            buckets=byte_buckets,
        )
        # Fleet compile-cache observability: bytes/entries moved by the
        # seed (spawn) and harvest (turnover) halves, negotiation skips,
        # and the per-kernel hit/miss outcome the sandboxes report. A
        # healthy fleet shows harvest bytes ~ once per distinct kernel and
        # hit counters dwarfing miss counters.
        self.compile_cache_bytes = self.registry.counter(
            "code_interpreter_compile_cache_bytes_total",
            "Compile-cache entry bytes actually moved between the fleet "
            "store and sandbox cache dirs, by direction (seed/harvest).",
            ("direction",),
        )
        self.compile_cache_files = self.registry.counter(
            "code_interpreter_compile_cache_files_total",
            "Compile-cache entries actually moved, by direction "
            "(seed/harvest).",
            ("direction",),
        )
        self.compile_cache_skipped_files = self.registry.counter(
            "code_interpreter_compile_cache_skipped_files_total",
            "Compile-cache entries NOT moved thanks to manifest/hash "
            "negotiation (seed: host already held them; harvest: store "
            "already knew them).",
            ("direction",),
        )
        self.compile_cache_conflicts = self.registry.counter(
            "code_interpreter_compile_cache_conflicts_total",
            "Harvest manifests offering DIFFERENT bytes under an entry "
            "name the store already maps (first-write-wins rejection): a "
            "nondeterministic recompile at best, a poisoning attempt at "
            "worst — investigate if this moves.",
        )
        self.compile_cache_kernels = self.registry.counter(
            "code_interpreter_compile_cache_kernels_total",
            "Persistent-compilation-cache lookups reported by sandbox "
            "runners, by outcome (hit = loaded a previously compiled "
            "kernel, miss = had to compile).",
            ("outcome",),
        )
        # Result-memo observability (services/result_memo.py): request
        # outcomes on the memo admission check (hit = served without a
        # sandbox round-trip, miss = executed then recorded, bypass =
        # ineligible), plus the compile-cache-style first-write-wins
        # conflict counter and the keep-alive reuse proof for the shared
        # executor HTTP client.
        self.result_memo_requests = self.registry.counter(
            "code_interpreter_result_memo_requests_total",
            "Pure-declared execute requests by memo outcome (hit = served "
            "from the record with zero sandbox HTTP and zero chip-seconds; "
            "miss = executed and recorded; bypass = declared pure but "
            "ineligible, e.g. session or profiling runs).",
            ("outcome",),
        )
        self.result_memo_conflicts = self.registry.counter(
            "code_interpreter_result_memo_conflicts_total",
            "Declared-pure runs offering DIFFERENT result bytes under a "
            "memo key the store already maps (first-write-wins rejection): "
            "a nondeterministic 'pure' run at best, a poisoning attempt at "
            "worst — investigate if this moves.",
        )
        # Session-durability plane (services/session_store.py): hibernate /
        # restore / migrate outcomes, plus the cost signal the plane exists
        # to kill — chip-seconds spent parked under an idle session. A
        # rising idle counter next to zero hibernates means the idle
        # threshold is mis-tuned (or the kill switch is off on purpose).
        self.session_hibernates = self.registry.counter(
            "code_interpreter_session_hibernates_total",
            "Sessions checkpointed to the durable store with their chip "
            "released, by outcome (hibernate = idle-timer driven; migrate "
            "= fence-driven live migration; failed = snapshot refused or "
            "not admitted — session left parked).",
            ("outcome",),
        )
        self.session_restores = self.registry.counter(
            "code_interpreter_session_restores_total",
            "Hibernated-session wakes by outcome (restored = checkpoint "
            "applied, session_seq continuous; fresh = record refused by "
            "the runner and evicted — session recreated with an honest "
            "seq reset).",
            ("outcome",),
        )
        self.session_migrations = self.registry.counter(
            "code_interpreter_session_migrations_total",
            "Sessions on a host being fenced, by what happened to their "
            "state (saved = live-migrated via snapshot-then-restore-"
            "elsewhere; forced = checkpoint impossible in time, "
            "pre-durability force-close).",
            ("outcome",),
        )
        self.session_idle_chip_seconds = self.registry.counter(
            "code_interpreter_session_idle_chip_seconds_total",
            "Cumulative chip-seconds spent parked under idle executor_id "
            "sessions (chips held, no request in flight) — the cost "
            "hibernation reclaims.",
        )
        # Store-loss resilience (services/state_store.py ResilientStateStore):
        # every degraded-path event, by kind. `outage` fires once per
        # healthy→degraded transition; `degraded_op` counts operations
        # served from replica-local fallbacks (shadow/cache/journal) while
        # the shared store is down; `refused` counts fail-closed refusals
        # (lease mints, session restores); `journal_replay` /
        # `journal_dropped` track the quota-accrual journal's reconciliation
        # on reconnect. Any movement outside a chaos drill is a page.
        self.store_degraded_ops = self.registry.counter(
            "code_interpreter_store_degraded_ops_total",
            "Shared-state-store degraded-path events by kind (outage = "
            "healthy->degraded transition; degraded_op = op served from a "
            "replica-local fallback; refused = fail-closed refusal; "
            "journal_replay / journal_dropped = quota-journal "
            "reconciliation on reconnect).",
            ("event",),
        )
        self.executor_connections_reused = self.registry.counter(
            "executor_connections_reused_total",
            "Executor HTTP dispatches served over an already-established "
            "keep-alive connection in the shared client pool (vs opening "
            "a fresh TCP connection).",
        )
        # Tracing's per-stage latency feed: every sampled span's duration,
        # labeled by span name (a bounded set — http/grpc entry, scheduler
        # wait, transfer phases, executor call, sandbox install/exec/
        # collect), so stage histograms exist even for operators who never
        # open an individual trace.
        self.span_seconds = self.registry.histogram(
            "code_interpreter_span_seconds",
            "Trace-span latency by stage (utils/tracing.py; sampled "
            "requests only).",
            ("span",),
        )
        # Device-health telemetry (services/device_health.py): the wedge
        # counter is the page-an-operator signal — a host whose device plane
        # stopped making progress past every budget. Detection only in this
        # subsystem; the fencing layer consumes it.
        self.device_wedges = self.registry.counter(
            "device_wedge_detected_total",
            "Hosts the device-health probe classified as WEDGED (attach or "
            "device op stalled past its budget plus the wedge threshold), "
            "by chip-count lane. Fires once per transition into wedged.",
            ("chip_count",),
        )
        # Wedge-recovery actuation (the fencing half): every wedged verdict
        # the actuator saw, by lane and what it did about it. outcome=
        # fenced is the loop closing (drain + dispose + replace started);
        # budget_exhausted / breaker_open are the bounded-blast-radius
        # outcomes — the verdict stood but actuation deferred.
        self.device_fences = self.registry.counter(
            "device_fence_total",
            "Wedge-recovery actuations by lane and outcome (fenced = lease "
            "revoked + host drained/disposed/replaced; budget_exhausted = "
            "per-lane actuation cap hit, verdict deferred; breaker_open = "
            "lane cannot spawn replacements, disposal skipped).",
            ("lane", "outcome"),
        )
        self.host_readmitted = self.registry.counter(
            "host_readmitted_total",
            "Fenced lease scopes re-admitted to serving after the "
            "configured consecutive clean-probe streak, by lane.",
            ("lane",),
        )
        self.device_probe_cycle_seconds = self.registry.histogram(
            "code_interpreter_device_probe_cycle_seconds",
            "Wall time of one full device-health probe cycle over every "
            "live sandbox host. A stalled probe daemon is itself visible: "
            "this stops moving while device_probe_last_poll_age_seconds "
            "climbs.",
        )
        # OTLP export observability (utils/otlp.py): drops mean the bounded
        # queue hit backpressure (collector slow/unreachable) — telemetry
        # degraded by design instead of growing the heap.
        self.otlp_exports = self.registry.counter(
            "code_interpreter_otlp_exports_total",
            "OTLP export flushes by signal (traces/metrics) and outcome "
            "(ok/error).",
            ("signal", "outcome"),
        )
        self.otlp_dropped = self.registry.counter(
            "code_interpreter_otlp_dropped_total",
            "Spans dropped at the OTLP exporter's bounded queue "
            "(backpressure): the collector is not keeping up.",
        )
        # Per-tenant usage metering (services/usage.py): the ledger's
        # monotonic counters mirrored as metric families so the billing
        # signal rides the existing scrape + OTLP export paths. Tenant
        # labels share the ledger's own bounded table (`_overflow` past
        # the cap) — the ledger hands this registry the ALREADY-capped
        # label, so metric cardinality can never outgrow the bill.
        self.tenant_usage_seconds = self.registry.counter(
            "code_interpreter_tenant_usage_seconds_total",
            "Per-tenant accrued seconds by resource: chip (chip_count x "
            "device-op wall — the billing signal), device_op (the "
            "un-multiplied op wall), queue_wait (scheduler queue time).",
            ("tenant", "resource"),
        )
        self.tenant_usage_bytes = self.registry.counter(
            "code_interpreter_tenant_usage_bytes_total",
            "Per-tenant transfer bytes actually MOVED (upload/download; "
            "negotiated-away bytes bill nothing) plus compile-cache bytes "
            "the tenant's recompiles produced (kind=compile_cache_new).",
            ("tenant", "kind"),
        )
        self.tenant_usage_requests = self.registry.counter(
            "code_interpreter_tenant_usage_requests_total",
            "Per-tenant requests by outcome (ok/user_error/limit_violation/"
            "infra_error/rejected).",
            ("tenant", "outcome"),
        )
        self.tenant_usage_batch_jobs = self.registry.counter(
            "code_interpreter_tenant_usage_batch_jobs_total",
            "Per-tenant jobs served via a fused batched dispatch.",
            ("tenant",),
        )
        self.tenant_usage_violations = self.registry.counter(
            "code_interpreter_tenant_usage_violations_total",
            "Per-tenant typed limit violations by kind — the abuse-control "
            "feed services/quotas.py reads for its violation quotas and "
            "repeat-offender quarantine.",
            ("tenant", "kind"),
        )
        # Quota enforcement (services/quotas.py): denials at the admission
        # door, by tenant and typed reason (chip_seconds / request_rate /
        # concurrency / quarantined). Tenant labels are the usage ledger's
        # own capped row names (`_overflow` past APP_USAGE_MAX_TENANTS) —
        # enforcement keys off the same rows it bills against, so metric
        # cardinality can never outgrow the ledger table.
        self.quota_denials = self.registry.counter(
            "code_interpreter_quota_denials_total",
            "Requests denied at admission by the quota layer, by tenant "
            "and reason (chip_seconds = sliding-window budget exhausted, "
            "request_rate / concurrency = caps, quarantined = repeat "
            "limit-violation offender shed at the door).",
            ("tenant", "reason"),
        )
        self.tenant_usage_recompiles = self.registry.counter(
            "code_interpreter_tenant_usage_compile_recompiles_total",
            "Per-tenant kernels that had to compile (persistent-cache "
            "misses) in the tenant's runs.",
            ("tenant",),
        )
        # Performance anomaly plane (services/perf_observer.py): the
        # regression counter / state gauge / profile families register in
        # bind_perf ONLY when the observer is live — with the kill switch
        # off, /metrics carries zero perf families (the quota-gauge
        # exposition discipline, byte-for-byte).
        self.perf_regressions: Counter | None = None
        self.perf_profiles: Counter | None = None
        self.perf_state: Gauge | None = None
        self.perf_profile_store: Gauge | None = None
        self.tenant_usage_hbm: Counter | None = None
        self.pool_depth: Gauge | None = None
        self.pool_target: Gauge | None = None
        self.pool_supply: Gauge | None = None
        self.pool_desired_chips: Gauge | None = None
        self.active_sessions: Gauge | None = None
        self.compile_cache_store: Gauge | None = None
        self.breaker_state: Gauge | None = None
        self.scheduler_queue_depth: Gauge | None = None
        self.scheduler_queue_wait_ewma: Gauge | None = None
        self.batch_occupancy: Gauge | None = None
        self.device_health_state: Gauge | None = None
        self.device_probe_last_poll_age: Gauge | None = None
        self.quota_remaining: Gauge | None = None

    def bind_quotas(self, enforcer) -> None:
        """Per-tenant remaining chip-second budget, computed at scrape time
        from the enforcer's sliding windows. Registered only when the quota
        layer is live (the kill switch leaves /metrics without the family —
        pre-quota exposition byte-for-byte). Only tenants with a configured
        budget emit samples; labels share the ledger's `_overflow` cap."""
        if not getattr(enforcer, "enabled", False):
            return
        self.quota_remaining = self.registry.gauge(
            "code_interpreter_quota_remaining_chip_seconds",
            "Per-tenant chip-seconds left in the current sliding quota "
            "window (only tenants with a configured budget; 0 = denied "
            "until the window refills).",
            ("tenant",),
            callback=enforcer.remaining_gauge_samples,
        )

    def bind_perf(self, observer) -> None:
        """The perf observer's metric families. Registered only when the
        plane is live (APP_PERF_OBSERVER_ENABLED=0 leaves /metrics without
        any of them — the kill switch's zero-perf-surfaces promise)."""
        if not getattr(observer, "enabled", False):
            return
        self.perf_regressions = self.registry.counter(
            "perf_regression_total",
            "Drift-detector windows classified REGRESSED (window drift "
            "quantile past baseline * regressed_factor), by chip-count "
            "lane and request phase. Fires once per transition into "
            "regressed — the page-an-operator latency signal.",
            ("lane", "phase"),
        )
        self.perf_profiles = self.registry.counter(
            "code_interpreter_perf_profiles_captured_total",
            "Auto-triggered JAX profile captures harvested into the "
            "profile store, by trigger kind (regression / p99_outlier).",
            ("trigger",),
        )
        self.perf_state = self.registry.gauge(
            "code_interpreter_perf_state",
            "One-hot drift verdict per (lane, phase) latency series "
            "(normal / degraded / regressed).",
            ("lane", "phase", "state"),
            callback=observer.state_gauge_samples,
        )
        self.perf_profile_store = self.registry.gauge(
            "code_interpreter_perf_profile_store",
            "Harvested-profile store occupancy (kind=bytes/entries; "
            "LRU-evicted under the configured caps).",
            ("kind",),
            callback=observer.store_gauge_samples,
        )
        self.tenant_usage_hbm = self.registry.counter(
            "code_interpreter_tenant_usage_hbm_byte_seconds_total",
            "Per-tenant peak device-memory footprint integrated over "
            "device-op wall (peak_hbm_bytes x device_op_seconds): the "
            "memory-hog attribution signal next to chip_seconds.",
            ("tenant",),
        )

    def record_perf_regression(self, *, lane: str, phase: str) -> None:
        if self.perf_regressions is not None:
            self.perf_regressions.inc(lane=lane, phase=phase)

    def record_perf_profile(self, *, reason: str) -> None:
        if self.perf_profiles is not None:
            self.perf_profiles.inc(trigger=reason)

    def record_tenant_usage(
        self,
        tenant: str,
        increments: dict[str, float],
        *,
        outcome: str | None = None,
        violation: str | None = None,
    ) -> None:
        """One ledger increment set mirrored into the tenant_usage_*
        families. `tenant` is the ledger's own capped label (its overflow
        discipline IS the metric cardinality bound)."""

        def amount(name: str) -> float:
            value = increments.get(name, 0.0)
            return float(value) if value and value > 0 else 0.0

        for resource in ("chip", "device_op", "queue_wait"):
            seconds = amount(f"{resource}_seconds")
            if seconds:
                self.tenant_usage_seconds.inc(
                    seconds, tenant=tenant, resource=resource
                )
        for kind, name in (
            ("upload", "upload_bytes"),
            ("download", "download_bytes"),
            ("compile_cache_new", "compile_cache_new_bytes"),
        ):
            moved = amount(name)
            if moved:
                self.tenant_usage_bytes.inc(moved, tenant=tenant, kind=kind)
        hbm = amount("hbm_byte_seconds")
        if hbm and self.tenant_usage_hbm is not None:
            self.tenant_usage_hbm.inc(hbm, tenant=tenant)
        recompiles = amount("compile_cache_recompiles")
        if recompiles:
            self.tenant_usage_recompiles.inc(recompiles, tenant=tenant)
        batch_jobs = amount("batch_jobs")
        if batch_jobs:
            self.tenant_usage_batch_jobs.inc(batch_jobs, tenant=tenant)
        if outcome:
            self.tenant_usage_requests.inc(tenant=tenant, outcome=outcome)
        if violation:
            self.tenant_usage_violations.inc(tenant=tenant, kind=violation)

    def bind_pool(self, pools) -> None:
        """Expose warm-pool depth per chip-count lane, read at scrape time."""

        def sample() -> dict[tuple[str, ...], float]:
            return {(str(lane),): float(len(pool)) for lane, pool in pools.items()}

        self.pool_depth = self.registry.gauge(
            "code_interpreter_pool_depth",
            "Warm sandboxes currently pooled, by chip-count lane.",
            ("chip_count",),
            callback=sample,
        )

    def bind_autoscale(self, executor) -> None:
        """Expose the autoscaler's per-lane verdicts at scrape time:
        pool_target (the dynamic, capacity-clamped lane target),
        pool_supply (non-wedged pooled + in-flight spawns — what actually
        backs the target), and pool_desired_chips (target x the lane's
        chip count; the k8s HPA external-metric feed — `sum()` it for the
        fleet's desired accelerator footprint). All three also ride the
        OTLP metrics export like any family in this registry."""

        def lanes() -> list[int]:
            return sorted(executor._known_lanes())

        def target_sample() -> dict[tuple[str, ...], float]:
            return {
                (str(lane),): float(executor._lane_target(lane))
                for lane in lanes()
            }

        self.pool_target = self.registry.gauge(
            "code_interpreter_pool_target",
            "Warm-pool target per chip-count lane (the autoscaler's "
            "demand-model verdict, clamped by backend capacity; the "
            "static constant with APP_POOL_AUTOSCALE_ENABLED=0).",
            ("chip_count",),
            callback=target_sample,
        )

        def supply_sample() -> dict[tuple[str, ...], float]:
            return {
                (str(lane),): float(
                    executor._pool_supply(lane)
                    + executor._spawning.get(lane, 0)
                )
                for lane in lanes()
            }

        self.pool_supply = self.registry.gauge(
            "code_interpreter_pool_supply",
            "Warm supply backing the lane target: non-wedged pooled "
            "sandboxes plus spawns in flight, by chip-count lane.",
            ("chip_count",),
            callback=supply_sample,
        )

        def desired_chips_sample() -> dict[tuple[str, ...], float]:
            # Deliberately the UNCLAMPED model target: the whole point of
            # an HPA external-metric feed is expressing demand BEYOND the
            # cluster's current capacity — the clamped _lane_target can
            # never exceed what already exists, so a feed built on it
            # would read desired == current forever and never scale the
            # node pool. pool_target (above) stays the clamped operational
            # verdict the warm pool actually aims for.
            return {
                (str(lane),): float(
                    executor.autoscaler.target(lane) * max(1, lane)
                )
                for lane in lanes()
            }

        self.pool_desired_chips = self.registry.gauge(
            "code_interpreter_pool_desired_chips",
            "Chips the autoscaler's demand model currently wants, by "
            "chip-count lane (UNCLAMPED model target x chips; lane 0 "
            "counts one chip-equivalent) — unlike pool_target this may "
            "exceed the backend's declared capacity, which is exactly the "
            "scale-up signal. Sum across lanes = the fleet's desired "
            "accelerator footprint — the external-metric feed for a "
            "Kubernetes HPA scaling the node pool.",
            ("chip_count",),
            callback=desired_chips_sample,
        )

    def bind_sessions(self, sessions) -> None:
        """Expose the live executor_id session count, read at scrape time."""

        def sample() -> dict[tuple[str, ...], float]:
            return {
                (): float(sum(1 for s in sessions.values() if not s.closed))
            }

        self.active_sessions = self.registry.gauge(
            "code_interpreter_active_sessions",
            "Live executor_id sessions (sandboxes parked out of the pool).",
            (),
            callback=sample,
        )

    def bind_compile_cache(self, store) -> None:
        """Expose the fleet compile-cache hot set's size, read at scrape
        time (entries + bytes; both 0 with the kill switch on)."""

        def sample() -> dict[tuple[str, ...], float]:
            return {
                ("entries",): float(store.entry_count()),
                ("bytes",): float(store.total_bytes()),
            }

        self.compile_cache_store = self.registry.gauge(
            "code_interpreter_compile_cache_store",
            "Fleet compile-cache hot set size, by stat (entries/bytes).",
            ("stat",),
            callback=sample,
        )

    def bind_result_memo(self, store) -> None:
        """Expose the result-memo record set's size, read at scrape time
        (entries + bytes; both 0 with the kill switch on)."""

        def sample() -> dict[tuple[str, ...], float]:
            return {
                ("entries",): float(store.entry_count()),
                ("bytes",): float(store.total_bytes()),
            }

        self.result_memo_store = self.registry.gauge(
            "code_interpreter_result_memo_store",
            "Result-memo record set size, by stat (entries/bytes).",
            ("stat",),
            callback=sample,
        )

    def bind_scheduler(self, scheduler) -> None:
        """Expose scheduler queue depth per lane x tenant x priority, read
        at scrape time from the live queues."""

        def sample() -> dict[tuple[str, ...], float]:
            return dict(scheduler.queue_depths())

        self.scheduler_queue_depth = self.registry.gauge(
            "code_interpreter_scheduler_queue_depth",
            "Requests currently queued for a sandbox slot, by lane, "
            "tenant, and priority class.",
            ("chip_count", "tenant", "priority"),
            callback=sample,
        )

        def ewma_sample() -> dict[tuple[str, ...], float]:
            return {
                (str(lane),): value
                for lane, value in scheduler.queue_wait_ewmas().items()
            }

        # Autoscaling hint (ROADMAP follow-up): the same smoothed queue-wait
        # the scheduler's deadline admission uses, exported per lane so an
        # operator can scale the warm pool from queue pressure instead of
        # eyeballing raw histogram quantiles. Updated on each grant.
        self.scheduler_queue_wait_ewma = self.registry.gauge(
            "scheduler_queue_wait_ewma_seconds",
            "Exponentially weighted moving average of sandbox-slot queue "
            "wait, by chip-count lane (the scheduler's own admission "
            "estimator; updated on each grant).",
            ("chip_count",),
            callback=ewma_sample,
        )

        def occupancy_sample() -> dict[tuple[str, ...], float]:
            return {
                (str(lane),): value
                for lane, value in scheduler.batch_occupancies().items()
            }

        # Jobs-per-dispatch over the configured batch ceiling, smoothed:
        # ~1.0 = full batches (every chip of the lane busy per dispatch);
        # persistently low = the window keeps expiring under-filled.
        self.batch_occupancy = self.registry.gauge(
            "code_interpreter_batch_occupancy",
            "EWMA of batched-dispatch fill ratio (jobs coalesced / "
            "APP_BATCH_MAX_JOBS), by chip-count lane.",
            ("chip_count",),
            callback=occupancy_sample,
        )

    def bind_device_health(self, probe) -> None:
        """Expose the probe daemon's classification at scrape time: one-hot
        device_health_state{lane,host,state} per tracked host (lane-level
        host="_overflow" aggregation past the label cap — see
        DeviceHealthProbe.gauge_samples), plus the probe's own liveness
        (seconds since the last completed cycle; a stalled daemon is itself
        observable)."""
        self.device_health_state = self.registry.gauge(
            "device_health_state",
            "Device-health probe classification per lane/host/state "
            "(healthy|busy|recovering|suspect|wedged|draining): 1 on the "
            "host's current state. "
            "Past the host-label cap, series aggregate per lane under "
            'host="_overflow" (value = hosts in that state).',
            ("lane", "host", "state"),
            callback=probe.gauge_samples,
        )

        def poll_age() -> dict[tuple[str, ...], float]:
            return {(): probe.last_poll_age()}

        self.device_probe_last_poll_age = self.registry.gauge(
            "device_probe_last_poll_age_seconds",
            "Seconds since the device-health probe daemon last completed a "
            "full cycle (-1 = never ran). Alert on this climbing past a few "
            "probe intervals: a wedge nobody is probing for is invisible.",
            (),
            callback=poll_age,
        )

    def bind_breakers(self, board) -> None:
        """Expose per-lane breaker state at scrape time
        (0=closed, 1=half-open, 2=open)."""
        from ..services.circuit_breaker import STATE_CODES

        def sample() -> dict[tuple[str, ...], float]:
            return {
                (str(lane),): STATE_CODES[state]
                for lane, state in board.states().items()
            }

        self.breaker_state = self.registry.gauge(
            "code_interpreter_breaker_state",
            "Spawn circuit-breaker state per chip-count lane "
            "(0=closed, 1=half-open, 2=open).",
            ("chip_count",),
            callback=sample,
        )
