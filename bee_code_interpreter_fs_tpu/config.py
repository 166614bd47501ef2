"""Service configuration, overridable via ``APP_``-prefixed environment vars.

Parity notes: mirrors the reference's pydantic-settings `Config` with env
prefix ``APP_`` and its 12 knobs (src/code_interpreter/config.py:18-80):
logging config, listen addrs, TLS material, executor image/resources/pod-spec
hooks, storage path, pool target length, pod name prefix. Added TPU-native
knobs: executor backend selection (local subprocess vs kubernetes), warm-runner
toggle, TPU topology/chip-count defaults, JAX persistent compilation cache
path, and default execution timeout. pydantic-settings is not available in
this environment, so env parsing is implemented directly (JSON for structured
fields, plain strings otherwise).
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any

from pydantic import BaseModel, Field

ENV_PREFIX = "APP_"
REPO_ROOT = Path(__file__).resolve().parent.parent


def jax_cache_dir(environ: dict[str, str] | None = None) -> str:
    """Where every process of this program keeps JAX's persistent compilation
    cache: the caller's JAX_COMPILATION_CACHE_DIR when it is set, else one
    fixed path inside the checkout. The path is part of the cache's key, so
    it is never built from a temp name, a pid or a time — and a directory
    handed in from outside is never wiped."""
    env = os.environ if environ is None else environ
    return env.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_ROOT / ".jax_cache")


def _default_logging_config() -> dict:
    return {
        "version": 1,
        "disable_existing_loggers": False,
        "filters": {
            "request_id": {"()": "bee_code_interpreter_fs_tpu.utils.logs.RequestIdFilter"}
        },
        "formatters": {
            "standard": {
                "format": "%(levelname)s [%(request_id)s] %(name)s: %(message)s"
            }
        },
        "handlers": {
            "default": {
                "class": "logging.StreamHandler",
                "formatter": "standard",
                "filters": ["request_id"],
                "stream": "ext://sys.stdout",
            }
        },
        "root": {"level": "INFO", "handlers": ["default"]},
        "loggers": {
            "bee_code_interpreter_fs_tpu": {"level": "INFO"},
            "aiohttp.access": {"level": "WARNING"},
        },
    }


class Config(BaseModel):
    # -- logging ------------------------------------------------------------
    logging_config: dict = Field(default_factory=_default_logging_config)

    # -- listen addresses ---------------------------------------------------
    http_listen_addr: str = "0.0.0.0:8000"
    grpc_listen_addr: str = "0.0.0.0:50051"

    # -- optional gRPC TLS --------------------------------------------------
    grpc_tls_cert: bytes | None = None
    grpc_tls_cert_key: bytes | None = None
    grpc_tls_ca_cert: bytes | None = None

    # -- executor orchestration --------------------------------------------
    executor_backend: str = "local"  # "local" | "kubernetes"
    executor_image: str = "localhost/tpu-code-executor:local"
    executor_container_resources: dict = Field(default_factory=dict)
    executor_pod_spec_extra: dict = Field(default_factory=dict)
    executor_pod_queue_target_length: int = 5
    executor_pod_name_prefix: str = "tpu-code-executor-"
    # How long a sandbox may take to become REACHABLE (server listening /
    # pod Ready). Warm-up (TPU init) has its own, longer budget below —
    # conflating the two is what broke the round-1 bench.
    executor_pod_ready_timeout: float = 60.0
    # How long a sandbox may take to become WARM (jax imported, libtpu
    # initialized, devices enumerated) after it is reachable. Deliberately
    # very generous: first-ever TPU init on a cold host can take many
    # minutes, and killing a client mid-init can wedge the device for the
    # NEXT client — patience here is cheaper than a kill-retry spiral.
    executor_warm_ready_timeout: float = 600.0

    # -- local backend ------------------------------------------------------
    # Path to the compiled C++ executor server; resolved relative to repo root
    # when not absolute. Empty string → auto-discover.
    executor_binary: str = ""
    local_sandbox_root: str = "/tmp/tpu-code-interpreter/sandboxes"

    # -- storage ------------------------------------------------------------
    file_storage_path: str = "/tmp/tpu-code-interpreter/storage"
    # Delta-based workspace sync (services/transfer.py): skip uploading
    # files a sandbox host's manifest already holds, skip downloading files
    # whose server-reported sha256 is already in content-addressed storage,
    # and resync from GET /workspace-manifest when a sandbox's state is in
    # doubt. Hosts running an old executor binary (no manifest endpoints)
    # are detected per host and transparently get full transfers. Disable
    # to force the legacy full-transfer path everywhere.
    transfer_manifest_enabled: bool = True

    # -- execution ----------------------------------------------------------
    default_execution_timeout: float = 60.0
    max_execution_timeout: float = 600.0

    # -- TPU ----------------------------------------------------------------
    # Warm runner pre-imports jax (initializing libtpu) at sandbox boot so the
    # Execute p50 cold-start excludes TPU init; see executor/runner.py.
    executor_warm_runner: bool = True
    # Recycle the warm device process across sandbox generations: after a
    # successful Execute, POST /reset scrubs the sandbox (workspace wipe,
    # stray-process reaping, runner state restore) and returns it to the
    # pool instead of disposing it — the TPU lease survives, so the next
    # request pops a hot sandbox in milliseconds instead of waiting ~seconds
    # for jax/libtpu re-init (VERDICT r2 #1: the 3.4 s queue_wait). Sandboxes
    # whose runner died or timed out are never recycled. Disable to restore
    # strict one-process-per-Execute disposal (the reference's model).
    executor_reuse_sandboxes: bool = True
    # Every N seconds, probe pooled sandboxes' /healthz and dispose the
    # unresponsive ones (a silently-dead pooled process would otherwise cost
    # the next request a failed attempt first). 0 disables the sweeper.
    pool_health_sweep_interval: float = 30.0
    # -- sessions (executor_id affinity) ------------------------------------
    # Execute requests carrying an executor_id share one live sandbox: its
    # workspace and warm process persist across the session's requests (the
    # upstream bee-code-interpreter's persistent-executor semantics; the -fs
    # fork carried the field but single-use pods made it a no-op). Max
    # concurrent sessions; at the cap new ids get HTTP 429 /
    # RESOURCE_EXHAUSTED. 0 = reference-parity mode: executor_id is accepted
    # and IGNORED (stateless) — set this for legacy clients that thread
    # opaque per-request ids under the old "field is unused" contract, which
    # would otherwise open one throwaway session per request.
    executor_session_max: int = 16
    # A session idle longer than this is closed and its sandbox returned to
    # the pool (or disposed). Kept deliberately short: on a capacity-
    # constrained TPU lane an idle session is parking a chip that stateless
    # requests are queueing for.
    executor_session_idle_timeout: float = 120.0
    # Max seconds a request may queue for a sandbox slot before getting a
    # retryable 429/RESOURCE_EXHAUSTED. The hang this bounds: every slot of
    # a capacity-constrained lane held by ACTIVELY USED sessions, which the
    # idle sweeper (by design) never touches. 0 = wait forever.
    executor_acquire_timeout: float = 300.0
    # -- resilience ----------------------------------------------------------
    # Spawn retry ladder length (calls, not retries): each failed attempt
    # backs off exponentially (0.5s base, 5s cap) with full jitter via
    # utils/retrying.py — the in-repo engine that replaced tenacity.
    executor_spawn_retry_attempts: int = 3
    # Per-chip-count-lane circuit breaker: after this many CONSECUTIVE spawn
    # failures the lane opens and new work fails fast with a retryable
    # error (HTTP 503 + Retry-After / gRPC UNAVAILABLE) instead of
    # burning the acquire budget against a backend that is down.
    breaker_failure_threshold: int = 5
    # Seconds an open lane waits before letting a half-open probe through;
    # one probe success closes the lane, one failure re-opens it.
    breaker_cooldown: float = 30.0
    # -- scheduler (admission control & fair share) --------------------------
    # Every sandbox-slot acquisition goes through services/scheduler.py:
    # per-lane ordered queues with weighted fair queueing across tenants,
    # priority classes, deadline-aware admission, and bounded per-tenant
    # queue depth. The tenant comes from gRPC metadata `x-tenant` / HTTP
    # `X-Tenant` (or the request body); absent = this shared tenant.
    scheduler_default_tenant: str = "shared"
    # Per-tenant WFQ weights, e.g. {"interactive-ui": 4, "batch-jobs": 1}.
    # A tenant absent from the map weighs 1.0. Higher weight = larger share
    # of grants under contention (a weight-3 tenant gets ~3x the slots of a
    # weight-1 tenant while both have backlog).
    scheduler_tenant_weights: dict = Field(default_factory=dict)
    # Max requests ONE tenant may have queued per lane. At the bound new
    # requests shed at arrival with HTTP 429 / gRPC RESOURCE_EXHAUSTED and
    # a computed Retry-After (monotonic in the lane's queue depth) instead
    # of building unbounded backlog behind the 300s acquire budget.
    scheduler_max_queue_depth: int = 64
    # Starvation bound for the `batch` priority class: after this many
    # consecutive `interactive` grants while batch work waits, the next
    # grant goes to batch regardless of class preference.
    scheduler_batch_starvation_limit: int = 8
    # Smoothing factor for the queue-wait / spawn-latency EWMAs that drive
    # deadline-aware admission (higher = reacts faster, noisier).
    scheduler_ewma_alpha: float = 0.2
    # Floor for the per-queued-request Retry-After estimate while the
    # EWMAs are still cold (seconds).
    scheduler_min_retry_after: float = 1.0
    # Max DISTINCT tenant names exported as metric labels; past the cap,
    # further tenants collapse into one `_overflow` label (scheduling still
    # uses the real tenant — only dashboards coarsen). Guards label
    # cardinality against clients minting unbounded tenant names.
    scheduler_max_metric_tenants: int = 256
    # -- batched execution lanes (services/batcher.py) -----------------------
    # Coalesce compatible small jobs from ONE tenant (same lane, priority,
    # env, and limits) into a single multi-chip sandbox dispatch instead of
    # N serial round-trips — the Podracer/Anakin pattern: throughput on a
    # multi-chip lane comes from keeping every chip busy on batched small
    # work. Kill switch: 0 restores the serial path byte-for-byte on the
    # wire (every request runs exactly as before this subsystem existed).
    batching_enabled: bool = True
    # How long the FIRST job of a prospective batch waits for compatible
    # partners before dispatching (the batching window). Small on purpose:
    # the window is pure added latency for the first job, and under real
    # load partners arrive far faster than this.
    batch_window_ms: float = 10.0
    # Max jobs fused into one dispatch (a full batch fires immediately,
    # without waiting out the window). Sized to the lane's chip count in a
    # typical deployment — one job per chip is the sweet spot.
    batch_max_jobs: int = 8
    # -- warm-pool autoscaling (services/autoscaler.py) -----------------------
    # Demand-adaptive lane targets: a per-lane model (arrival-rate EWMA,
    # queue depth, the scheduler's queue-wait/spawn-latency EWMAs) drives
    # each lane's warm-pool target between pool_min_target and
    # pool_max_target, replacing the static executor_pod_queue_target_length
    # constant — scale-up is spawn-ahead (refills start when backlog x
    # spawn-time says demand will outrun supply), scale-down has hysteresis
    # plus an idle reaper that disposes excess warm sandboxes so shared
    # chip capacity migrates to pressured lanes. 0 = the kill switch:
    # static-target behavior byte-for-byte (the constant above rules every
    # lane again; no sweep, no reaping, no scale events). A static target
    # of 0 means "no warm pool" and is always honored verbatim, autoscaled
    # or not.
    pool_autoscale_enabled: bool = True
    # Dynamic-target bounds. The floor keeps a lane minimally warm through
    # quiet periods (one hot sandbox = sub-second first-request latency);
    # the ceiling bounds what a burst may pin in warm processes/chips.
    pool_min_target: int = 1
    pool_max_target: int = 16
    # Cadence of the autoscale sweep (scale-down evaluation, spawn-ahead
    # refill checks, idle reaping). 0 disables the sweep loop — targets
    # then only ever move UP, on arrivals.
    pool_autoscale_interval: float = 2.0
    # Hysteresis: demand must stay below the current target this many
    # seconds before the target starts stepping down (one step per sweep),
    # so a bursty lull between waves doesn't flap the pool.
    pool_scale_down_after: float = 30.0
    # A pooled sandbox must sit idle this long before the reaper may
    # dispose it as excess (pool depth above the lane target). Bounds how
    # long an off-peak lane squats warm chips a pressured lane could use.
    pool_idle_reap_seconds: float = 60.0
    # The queue-wait the autoscaler considers acceptable: while the lane's
    # smoothed grant wait exceeds this, the demand model adds proportional
    # headroom on top of the instantaneous backlog (the queue-wait-driven
    # half of the loop; the PR 3 gauge closed at last). 0 disables the
    # pressure term.
    pool_target_queue_wait: float = 0.5
    # Max CONCURRENT refill spawns per lane: a large target jump (exactly
    # what autoscaling makes possible) otherwise stampedes the backend —
    # every missing sandbox spawning at once against the k8s API / libtpu
    # attach path. fill_pool spawns at most this many at a time and
    # re-arms until the target is met. 0 = uncapped (the historic
    # behavior).
    pool_spawn_burst: int = 4
    # Weight of HIBERNATED-session demand in the autoscale model: each
    # hibernated session whose wake would land in this lane contributes
    # this many warm sandboxes' worth of expected demand (services/
    # session_store.py surfaces the per-lane count). 0.0 (default) keeps
    # the signal visible in /statusz but out of the targets — hibernated
    # supply stays silently-freed capacity, today's behavior. ~0.1 means
    # ten parked sessions justify one warm sandbox held for their wakes.
    pool_hibernated_wake_weight: float = 0.0
    # Deterministic fault-injection plan for chaos runs, e.g.
    # "spawn_fail:0.3,seed:7" (grammar in services/backends/faults.py).
    # Empty = no injection. NEVER set in production.
    executor_fault_spec: str = ""
    # -- tracing (utils/tracing.py) ------------------------------------------
    # Request-scoped distributed traces: W3C `traceparent` accepted at the
    # HTTP edge (`x-traceparent` metadata on gRPC) and propagated through
    # the scheduler, transfer, and into the sandbox executor, whose
    # install/exec/collect phase timings graft back in as child spans.
    # APP_TRACING_ENABLED=0 disables the subsystem entirely (every span
    # factory returns a shared no-op).
    tracing_enabled: bool = True
    # Head-based sampling for traces STARTED here (an incoming traceparent's
    # sampled flag is always respected): 1.0 records everything, 0.0 records
    # nothing while still propagating ids downstream.
    tracing_sample_ratio: float = 1.0
    # Finished spans retained in the in-memory ring (the GET /traces debug
    # surface and the CI failure artifact). Bounded — this is the whole
    # memory story for tracing.
    tracing_ring_capacity: int = 4096
    # Append-only JSONL span export (one span per line); empty = no file
    # exporter. Write failure disables the exporter, never the request.
    tracing_jsonl_path: str = ""
    # Tail-based sampling: traces the head-sampling coin flip REJECTED are
    # still recorded tentatively and kept anyway when they turn out to
    # matter — an error status, a limit.violation event, or a root span
    # slower than tracing_tail_slow_seconds. At low head ratios this is the
    # flight recorder that makes a batched dispatch's one bad request
    # reconstructible after the fact. Only applies to traces STARTED here:
    # an incoming traceparent's flag-00 (unsampled) decision is always
    # respected, per W3C.
    tracing_tail_enabled: bool = True
    # Root-span duration at which an otherwise-unsampled trace is kept
    # (the "slow-p99" keep; a fixed threshold so the decision is
    # deterministic and testable).
    tracing_tail_slow_seconds: float = 5.0
    # -- device-health probing (services/device_health.py) -------------------
    # The probe daemon samples every live sandbox host's GET /device-stats
    # on this cadence and classifies each host healthy/busy/suspect/wedged.
    # 0 disables the daemon entirely (no probe HTTP anywhere).
    device_probe_interval: float = 15.0
    # Per-host HTTP budget for one probe. A host that cannot answer a
    # trivial stats read inside this window counts a probe failure (the
    # unreachable path of the classifier).
    device_probe_timeout: float = 3.0
    # How long a device attach (warm-up: jax import + libtpu init) may
    # legitimately run before the host turns suspect. MUST exceed
    # executor_warm_ready_timeout (600s): that timeout deliberately
    # tolerates a first-ever TPU init of many minutes, and a probe that
    # pages (and, once fencing lands, disposes) a host mid-legitimate-init
    # would recreate the kill-retry spiral the generous warm timeout
    # exists to avoid. The wedge signature is an attach STILL pending
    # long past every legitimate budget.
    device_probe_attach_budget: float = 900.0
    # Grace beyond a device op's own declared timeout before the host turns
    # suspect: the executor kills on timeout itself, so an op outliving
    # timeout + grace means the kill machinery is stuck too.
    device_probe_op_grace: float = 60.0
    # How long a host must stay past its budget (attach, op, or reachability)
    # before suspect escalates to wedged. The wedge verdict fires
    # device_wedge_detected_total and marks the host for the fencing layer —
    # detection only; dispose/fence actuation is the fencing PR's job.
    device_probe_wedge_after: float = 120.0
    # Max distinct hosts exported with their own `host` label on
    # device_health_state; past the cap all series collapse to lane-level
    # (host="_overflow") so a large fleet cannot explode label cardinality.
    device_probe_max_host_labels: int = 64
    # -- wedge recovery: lease fencing & actuation (services/leases.py) ------
    # Kill switch for the ACTUATION half of wedge recovery: with 0, a
    # wedged verdict only marks the host (detection-only, the PR 8
    # behavior) — no lease fencing, no automatic drain/dispose/replace,
    # no recovering state. Detection (the probe daemon) keeps its own
    # switch (device_probe_interval=0).
    device_fence_enabled: bool = True
    # Consecutive CLEAN probe cycles a fenced scope's hardware (the
    # replacement lands on the same chips) must show before its hosts
    # re-admit to the pool; a suspect/wedged relapse resets the streak.
    device_probe_readmit_streak: int = 3
    # Actuation budget: at most this many fence-and-dispose actuations per
    # lane per window. A probe false-positive storm (flapping thresholds,
    # a broken stats route) must degrade to "stop disposing and page",
    # never to mass-disposing a serving lane. Past the cap, wedged verdicts
    # are counted (device_fence_total{outcome="budget_exhausted"}) but not
    # acted on until the window slides. 0 = uncapped.
    device_fence_max_per_window: int = 4
    device_fence_window_seconds: float = 600.0
    # Strict lease-token mode (the PR 13 carried follow-up): when 1, every
    # sandbox boots with APP_LEASE_REQUIRE_TOKEN=1 and its executor 409s
    # any dispatch arriving WITHOUT an x-lease-token once a lease has been
    # recorded — closing the tokenless-compatibility hole for fleets whose
    # control planes are fully rolled onto lease stamping. Default off:
    # old control planes (and manual curl) keep working against new
    # binaries, the PR 13 compatibility contract.
    lease_require_token: bool = False
    # -- performance anomaly plane (services/perf_observer.py) ----------------
    # Kill switch for the whole plane: 0 restores today's behavior
    # byte-for-byte — no latency baselines, no drift verdicts, no
    # device-memory sampling requested from sandboxes, no auto-profiling,
    # /perf and /profiles answer 404, no perf metric families.
    perf_observer_enabled: bool = True
    # Drift-detection window: each (lane, phase) series' samples bucket
    # into windows of this many seconds; a closed window with enough
    # samples is classified normal/degraded/regressed against the EWMA
    # baseline. Small enough that a regression flips a verdict while the
    # incident is still live; large enough that one slow request isn't a
    # "window".
    perf_window_seconds: float = 30.0
    # A window needs at least this many samples to be judged (thinner
    # windows keep the standing verdict — no data is not a regression).
    perf_min_window_samples: int = 8
    # EWMA smoothing for the baseline learned from NORMAL windows (higher
    # = adapts faster to legitimate shifts, forgives slow creep sooner).
    perf_baseline_alpha: float = 0.3
    # Classification bands: a window's drift quantile past
    # baseline*degraded_factor is degraded, past baseline*regressed_factor
    # is regressed (the transition that fires perf_regression_total, the
    # perf.regression span, and the auto-profile trigger).
    perf_degraded_factor: float = 1.5
    perf_regressed_factor: float = 3.0
    # Which window quantile drives drift classification (p95 default: tail
    # regressions are the ones that page, and medians hide bimodal hangs).
    perf_drift_quantile: float = 0.95
    # Absolute slack added under every band: sub-millisecond phases jitter
    # by whole multiples without meaning anything — a "3x regression" on a
    # 0.2ms upload phase is scheduler noise, not an incident.
    perf_min_band_seconds: float = 0.02
    # Series-cardinality bounds: (lane, phase) series past the cap are not
    # tracked; tenant series past their cap collapse into `_overflow` (the
    # scheduler/ledger/device-health discipline).
    perf_max_series: int = 64
    perf_max_tenants: int = 64
    # -- auto-triggered profiling ---------------------------------------------
    # Arm the JAX profiler for the next eligible request on a lane whose
    # drift verdict flipped regressed (or that landed past the cumulative
    # p99 band). 0 keeps the baselines/verdicts but never auto-profiles.
    perf_profile_auto: bool = True
    # A single request slower than cumulative-p99 * this factor arms a
    # profile capture even without a window verdict (the "one request went
    # off a cliff" trigger).
    perf_p99_outlier_factor: float = 2.0
    # Throttle: after a capture is consumed on a lane, new triggers are
    # dropped for this many seconds (a standing regression must not
    # profile every request on the lane).
    perf_profile_min_interval_seconds: float = 60.0
    # Tenants that must NEVER be auto-profiled (JSON list): a profile
    # captures kernel names and timing structure of tenant code, so
    # consent is opt-out per tenant. Client-requested profile=True is
    # unaffected — that is the tenant profiling itself.
    perf_profile_tenant_opt_out: list = Field(default_factory=list)
    # Harvested-profile store (content-addressed, LRU by last access,
    # byte/entry-capped, index persisted across restarts — the
    # compile-cache store discipline). Empty path = a ".profiles" dir
    # under file_storage_path.
    perf_profile_store_path: str = ""
    perf_profile_store_max_bytes: int = 268435456
    perf_profile_store_max_entries: int = 256
    # -- OTLP export (utils/otlp.py) ------------------------------------------
    # OTLP/HTTP JSON collector base URL (spans POST to <endpoint>/v1/traces,
    # metric snapshots to <endpoint>/v1/metrics). Empty = the kill switch:
    # no exporter is created and no export HTTP ever happens.
    otlp_endpoint: str = ""
    # Seconds between export flushes (each flush ships the span batch queued
    # since the last one plus one metrics snapshot).
    otlp_flush_interval: float = 10.0
    # Bounded span queue between the tracer and the wire: when exports fall
    # behind, the NEWEST spans drop and otlp_dropped_total counts them —
    # backpressure must never grow the heap or stall the traced path.
    otlp_max_queue: int = 4096
    # Per-flush HTTP timeout against the collector.
    otlp_timeout: float = 5.0
    # -- sandbox resource governance (services/limits.py) --------------------
    # Kill switch for the whole governance subsystem: 0 restores the
    # pre-governance behavior (no limits payload on requests, no APP_LIMIT_*
    # env on sandboxes, violations impossible).
    sandbox_limits_enabled: bool = True
    # Default per-request budget applied to EVERY execute, e.g.
    # {"cpu_seconds": 120, "nproc": 64, "disk_bytes": 1073741824}. Keys:
    # memory_bytes, cpu_seconds, nproc, nofile, fsize_bytes, disk_bytes,
    # output_bytes. Empty = ungoverned unless a lane/request asks.
    sandbox_default_limits: dict = Field(default_factory=dict)
    # Per-chip-count-lane budget overrides layered over the defaults, keyed
    # by the lane as a string (env vars are JSON):
    # {"0": {"memory_bytes": 2147483648}, "4": {"cpu_seconds": 600}}.
    sandbox_lane_limits: dict = Field(default_factory=dict)
    # Server caps that min-clamp whatever defaults/lane/request produce AND
    # boot every sandbox's APP_LIMIT_* env — the executor re-clamps against
    # them, so a request (or a compromised control plane) can only ever
    # TIGHTEN policy, never loosen it.
    sandbox_limit_caps: dict = Field(default_factory=dict)
    # The executor's stdout/stderr capture cap (APP_MAX_OUTPUT_BYTES, the
    # historic hard-coded 10 MiB): beyond it output is truncated — and
    # truncation is now reported as stdout_truncated/stderr_truncated flags.
    # A request's limits.output_bytes (below this cap) upgrades truncation
    # to an output_cap violation kill.
    sandbox_max_output_bytes: int = 10485760
    # cgroup-v2 HARD enforcement in the executor (memory.max / pids.max
    # from the APP_LIMIT_* caps): where the sandbox host's cgroupfs is
    # writable (pods with a delegated cgroup namespace, root dev hosts)
    # the executor parks its runner group and every cold child inside a
    # kernel-enforced box, so a workload that dodges the rlimits and
    # outruns the sampling watchdog still cannot take the pod down —
    # the in-pod limits story matches what the quota layer promises.
    # Detection is automatic with a clean fallback to rlimits+watchdog on
    # read-only cgroupfs; 0 forces the fallback everywhere (the executor
    # then behaves exactly as before this subsystem).
    sandbox_cgroup_enforce: bool = True
    # -- per-tenant usage metering (services/usage.py) ------------------------
    # Kill switch for the whole metering plane: 0 restores the pre-metering
    # behavior byte-for-byte — no ledger, no journal IO, no attribution
    # fields in Result.phases, no tenant_usage_* metric samples, and
    # GET /usage answers 404.
    usage_metering_enabled: bool = True
    # Where the durable accounting ledger lives (a JSONL journal of
    # cumulative per-tenant counter lines plus a compacted snapshot).
    # Empty = a ".usage" dir beside the workspace-file objects under
    # file_storage_path (the leading dot keeps it out of OBJECT_ID_RE's
    # namespace, like storage's ".tmp" and the compile cache's dir).
    usage_journal_path: str = ""
    # Seconds between journal flushes: a control-plane crash loses at most
    # this much attribution (the restart replays snapshot + journal).
    usage_flush_interval: float = 5.0
    # Max DISTINCT tenants the ledger tracks (and exports as metric
    # labels); past the cap, further tenants' usage accrues to one
    # `_overflow` row — the PR 2/PR 8 cardinality discipline, applied to
    # the billing table (client-minted tenant names must not grow it
    # without bound).
    usage_max_tenants: int = 256
    # Journal size at which a flush compacts: totals rewrite into the
    # snapshot (tmp+rename, atomic) and the journal truncates. Cumulative
    # latest-wins journal lines make replay-after-crash idempotent at any
    # point in this cycle.
    usage_journal_max_bytes: int = 1048576
    # Compaction RETAINS journal lines newer than this many seconds
    # (bounded to half the journal size cap) instead of truncating to
    # empty: each line is a timestamped cumulative sample, and that recent
    # timeline is what the quota layer's sliding windows restore from
    # after a crash — an offender must not earn a fresh budget by crashing
    # the control plane. Set this >= your largest quota window for exact
    # window restores; 0 restores the truncate-to-empty behavior (replay
    # correctness is unaffected either way — retained lines are stale
    # cumulative values the max-merge makes no-ops).
    usage_journal_keep_seconds: float = 7200.0
    # -- per-tenant quota enforcement (services/quotas.py) --------------------
    # Kill switch for the whole quota/abuse-control layer: 0 restores the
    # pre-quota behavior byte-for-byte — no admission checks, no /quotas
    # surface, no quota fields in Result.phases, no quota_* metric samples.
    # Enforcement reads the PR 9 usage ledger, so budgets and violation
    # quotas are inert while APP_USAGE_METERING_ENABLED=0 (rate and
    # concurrency caps are too: the whole layer keys off the metered
    # tenant). The enabled default changes nothing by itself: every cap
    # below defaults to 0 = unlimited.
    quotas_enabled: bool = True
    # The DEFAULT per-tenant policy (every knob 0 = that cap is off):
    # chip-seconds a tenant may consume per sliding window...
    quota_chip_seconds_per_window: float = 0.0
    # ...the window those budgets slide over (also the violation-quota and
    # request-rate window)...
    quota_window_seconds: float = 3600.0
    # ...admitted requests per window (a cheap pre-scheduler rate cap —
    # the scheduler's per-tenant queue depth bounds INSTANTANEOUS backlog,
    # this bounds sustained rate)...
    quota_requests_per_window: int = 0
    # ...and concurrent admitted (not yet finished) requests.
    quota_max_concurrent: int = 0
    # Admission-time cost PREDICTION (the PR 11 carried follow-up): deny a
    # request whose declared chip_count x timeout cannot fit the tenant's
    # REMAINING chip-second budget — typed 429 reason=predicted_overrun
    # with a refill-derived Retry-After, before any scheduler state is
    # touched — instead of admitting it and billing the overrun after the
    # burn. 0 restores deny-after-the-burn behavior exactly. Inert unless
    # a chip-second budget is configured.
    quota_cost_prediction: bool = True
    # Repeat-offender shedding: typed limit violations (oom/disk_quota/
    # nproc/cpu_time/output_cap, from the ledger's violations-by-kind
    # counters) a tenant may accrue per window before it is QUARANTINED —
    # shed at the door with reason=quarantined instead of burning a
    # sandbox per violating attempt. 0 = off.
    quota_violations_per_window: int = 0
    # Quarantine durations grow exponentially per episode (base * 2^(n-1),
    # capped) and the offender level decays back one step per decay
    # interval of clean behavior after release — abusive tenants are shed
    # harder each storm, reformed ones earn their way back.
    quota_quarantine_base_seconds: float = 30.0
    quota_quarantine_max_seconds: float = 3600.0
    quota_quarantine_decay_seconds: float = 300.0
    # Optional JSON policy file layering per-tenant overrides on the
    # default policy above: {"default": {...}, "tenants": {"name": {...}}}
    # with keys chip_seconds_per_window / window_seconds /
    # requests_per_window / max_concurrent / violations_per_window /
    # quarantine_{base,max,decay}_seconds. Hot-reloadable: the enforcer
    # re-stats the file (at most every quota_policy_reload_seconds) and a
    # malformed rewrite keeps the last good policy instead of failing open.
    quota_policy_file: str = ""
    quota_policy_reload_seconds: float = 2.0
    # Per-tenant HBM budget over the same sliding window (byte-seconds of
    # peak device memory integrated over device-op wall, the ledger's
    # `hbm_byte_seconds` counter from the perf-observer plane): a memory
    # hog is bounded the way a compute hog is, with the same 429 +
    # refill-derived Retry-After semantics. 0 = off. Policy-file key:
    # `hbm_byte_seconds_per_window`.
    quota_hbm_byte_seconds: float = 0.0
    # Burst-credit smoothing (opt-in token bucket BESIDE the hard sliding
    # window): a tenant holds up to `burst_credits` chip-seconds of
    # credit, refilled at `refill_per_second` chip-seconds/s, and each
    # run's observed chip-seconds drain it. An empty bucket denies with
    # reason=burst_credits and a deficit-derived Retry-After — bursty
    # tenants smooth out instead of slamming into the window edge, and
    # the remaining credit rides the X-Quota-Burst-Credits header and the
    # Result.phases quota block. Both knobs must be > 0 to engage; the
    # window budget (when configured) still enforces beside it.
    quota_burst_credits: float = 0.0
    quota_refill_per_second: float = 0.0
    # -- scale-out control plane (services/state_store.py, replicas.py) ------
    # Where cross-replica scheduler/breaker/lease state lives. Empty or
    # "memory" = a PRIVATE in-memory store: single-replica mode, every
    # cross-replica code path skipped — today's behavior byte-for-byte.
    # "sqlite:///path/state.db" (or a bare path) = the shared file-backed
    # store (stdlib sqlite, WAL + advisory locking): point N replicas at
    # one path on a shared volume and they cooperate — WFQ tags stay
    # globally fair, a breaker tripped on one replica is open on all,
    # a host fenced by one is never granted by another.
    # "redis://host:port[/db]" = the dependency-free RESP adapter: replicas
    # on DIFFERENT nodes share one Redis-compatible server (or the in-repo
    # services/resp_stub.py), taking the control plane past the single-node
    # SQLite boundary.
    state_store: str = ""
    # Wrap SHARED stores in the degraded-mode layer (ResilientStateStore):
    # a store-health breaker plus the per-namespace fail-open/fail-closed
    # policy that keeps the fleet serving through a store outage. The
    # private in-memory default is never wrapped — single-replica wiring
    # stays byte-for-byte. Disable only in tests that want raw store
    # errors to surface.
    state_store_resilient: bool = True
    # Per-op budget for the RESP store (connect, command round-trip, and
    # the bound on one advisory-lock acquisition loop).
    state_store_timeout: float = 2.0
    # Store-health breaker shape: consecutive failed ops before the store
    # is declared down (every op from then on serves degraded without
    # touching the network), and the cooldown before a half-open probe
    # rides the next op through.
    state_store_failure_threshold: int = 3
    state_store_probe_cooldown: float = 5.0
    # Seeded store fault plan (services/backends/faults.py StoreFaultSpec):
    # "drop:0.05,seed:7" or "outage_after:100,outage_ops:50,seed:23".
    # Empty = no injection. Chaos/CI only.
    state_store_fault_spec: str = ""
    # Fleet-coherent quota windows (services/quotas.py): with a shared
    # store, per-tenant chip-second/HBM/request accrual publishes into
    # bucketed fleet counters and admission checks max(local, fleet) —
    # closing the documented N× multi-replica bound. Store loss fails
    # OPEN to replica-local enforcement (the PR 15 bound) with the
    # missed accrual journaled and replayed on reconnect.
    quota_fleet_windows: bool = True
    # This replica's identity on the consistent-hash ring. Empty = the
    # POD_NAME env var (k8s downward API), else the hostname.
    replica_self: str = ""
    # The replica set, comma-separated `id=http://host:port` (or bare
    # host:port) entries — e.g. the pod names a k8s headless Service
    # resolves. Empty = single-replica mode: no ring, no affinity checks,
    # no proxying (today's behavior).
    replica_peers: str = ""
    # How a non-owner replica handles a session request it does not own:
    # 1 = transparently proxy it to the owner; 0 = answer 307 with the
    # owner's URL in Location + X-Replica-Owner (clients re-issue).
    replica_proxy: bool = True
    # Liveness heartbeat cadence (each replica publishes into the shared
    # store) and the staleness TTL past which a silent peer drops off the
    # ring — its sessions then rehash onto the survivors.
    replica_heartbeat_interval: float = 2.0
    replica_heartbeat_ttl: float = 10.0
    # -- shutdown ------------------------------------------------------------
    # Graceful drain budget on SIGTERM: health flips to NOT_SERVING and new
    # executes shed immediately, then shutdown waits up to this many seconds
    # for in-flight executes to finish before closing the executor.
    shutdown_grace_seconds: float = 20.0
    # -- sandbox resource limits (local backend) ----------------------------
    # Extra address-space bytes user code may allocate beyond the warm
    # runner's baseline (soft RLIMIT_AS window in executor/runner.py): an
    # allocation bomb gets an in-process MemoryError instead of inviting
    # the host OOM killer. "auto" = 80% of the sandbox host's physical RAM;
    # "0" disables; any integer = explicit bytes. The kubernetes backend
    # ignores this — container resources own the bound there (the reference
    # delegates isolation wholesale to the cluster runtime, README.md:56-57).
    sandbox_max_user_memory_bytes: int | str = "auto"
    # Soft RLIMIT_NOFILE applied around user code; 0 = inherit the host's.
    sandbox_max_open_files: int = 0
    # Default accelerator request for kubernetes backend pods, merged into the
    # container resources (e.g. {"google.com/tpu": "4"}). Empty → CPU pods.
    tpu_resource_requests: dict = Field(default_factory=dict)
    # Node-selector hints for TPU slice topology, e.g.
    # {"cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
    #  "cloud.google.com/gke-tpu-topology": "2x2"}.
    tpu_node_selector: dict = Field(default_factory=dict)
    # Per-slice-size selector overrides, keyed by the TOTAL chip count of the
    # requested slice (as a string, env vars are JSON): a 2-host v5e-8 slice
    # needs topology "2x4" nodes while a single-host v5e-4 wants "2x2" — a
    # single static selector cannot serve both (the multi-host pods would
    # land on unrelated single-host slices where no ICI mesh can form).
    # Example: {"8": {"cloud.google.com/gke-tpu-topology": "2x4"}}.
    tpu_node_selector_by_chip_count: dict = Field(default_factory=dict)
    # Default chip count an Execute request gets when it doesn't ask.
    default_chip_count: int = 0  # 0 = whatever the sandbox has
    # Chips attached to one host of a slice. chip_count above this → a
    # multi-host sandbox group: one executor per host, jax.distributed
    # coordinator bootstrap over DCN, ICI collectives inside (v5e = 4
    # chips/host; v4/v5p = 4 chips/host for most topologies).
    tpu_chips_per_host: int = 4
    # Port the jax.distributed coordinator (host 0) listens on.
    coordinator_port: int = 8476
    # Persistent XLA compilation cache shared across sandbox generations:
    # jax_cache_dir() above decides it (the caller's JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache); "" turns the cache off. The executor
    # excludes this dir's subtree from reset wipes, so compiled kernels
    # survive generation turnover even under a wiped parent.
    jax_compilation_cache_dir: str = Field(default_factory=jax_cache_dir)
    # -- fleet compile cache (services/compile_cache.py) ---------------------
    # Kill switch for the fleet-wide persistent XLA compile cache: seeding
    # sandbox cache dirs at spawn, harvesting compiled kernels back at
    # turnover/teardown, and the pool-fill pre-warm. 0 = exact pre-cache
    # behavior (no compile-cache HTTP anywhere; the per-sandbox
    # JAX_COMPILATION_CACHE_DIR still works host-locally).
    compile_cache_enabled: bool = True
    # Where the control plane keeps the fleet hot set (content-addressed
    # objects + a JSON index that survives restarts). Empty = a
    # ".compile-cache" dir beside the workspace-file objects under
    # file_storage_path (the leading dot keeps it out of OBJECT_ID_RE's
    # namespace, like storage's ".tmp").
    compile_cache_store_path: str = ""
    # Hot-set bounds: seeding a fresh sandbox is O(hot set), so these cap
    # both the seed cost and the store's disk. Past either bound, entries
    # evict LRU-by-last-hit (an evicted-but-hot kernel costs the fleet one
    # recompile before harvest re-admits it).
    compile_cache_max_bytes: int = 1073741824
    compile_cache_max_entries: int = 4096
    # Pre-warm the store from the examples/ kernel set (distilled: matmul /
    # elementwise / reduction) in the background after the first pool fill —
    # never on a serving path (batch priority, skipped under backlog).
    compile_cache_prewarm: bool = True
    # Local backend: give each sandbox its own private cache dir (under the
    # sandbox dir) instead of sharing one host dir. Shared-dir is faster on
    # one machine (zero-copy across sandboxes, and the fleet-constant path
    # jax's key hashing demands) and stays the default — but the shared dir
    # is writable by every sandbox, so harvest stops control-plane-wide at
    # the first tenant execute, and a dir that already held entries at boot
    # is never harvested (see LocalSandboxBackend.compile_cache_dir_scope).
    # The per-sandbox mode reproduces the pod-local reality of the
    # kubernetes backend, where the fleet store is the ONLY cross-sandbox
    # channel (used by the compile-cache e2e suite).
    compile_cache_per_sandbox: bool = False
    # Kubernetes: the volume SOURCE mounted at the cache dir (the pod-side
    # path was previously just an env var pointing at the container
    # overlay — gone with the container). Default emptyDir survives
    # container restarts within the pod; point it at a PVC or hostPath to
    # share compiles across pods without control-plane seeding, e.g.
    # {"persistentVolumeClaim": {"claimName": "jax-cache"}} — which also
    # disables fleet harvest AND the pre-warm (other pods' tenants can
    # write a shared volume, so nothing can vouch for its contents; see
    # KubernetesSandboxBackend.compile_cache_dir_scope).
    compile_cache_volume_source: dict = Field(
        default_factory=lambda: {"emptyDir": {}}
    )
    # -- deterministic result memoization (services/result_memo.py) ----------
    # Kill switch for the content-addressed pure-run result cache. 0 = exact
    # pre-memo behavior byte-for-byte: no memo HTTP headers, no phases keys,
    # no Storage/StateStore IO on any path.
    result_memo_enabled: bool = True
    # Where record blobs live (content-addressed objects in their own
    # Storage — NOT the workspace-file store, since memo eviction deletes
    # objects). Empty = a ".result-memo" dir beside the workspace-file
    # objects under file_storage_path (dot-prefixed, outside OBJECT_ID_RE's
    # namespace like storage's ".tmp" and the compile cache).
    result_memo_store_path: str = ""
    # Record-store bounds; past either, entries evict LRU-by-last-hit.
    result_memo_max_bytes: int = 268435456
    result_memo_max_entries: int = 8192
    # Provenance-gated cross-tenant sharing: when on, control-plane-authored
    # (trusted) pure runs record into a shared scope every tenant's lookups
    # may hit. Tenant-authored runs always stay per-tenant keyed.
    result_memo_shared: bool = False
    # -- session durability (services/session_store.py) ----------------------
    # Kill switch for the session checkpoint/hibernate/restore/migrate
    # plane. 0 = today's pin-forever session semantics byte-for-byte: no
    # hibernate timer, no snapshot ops on any path, no store directories,
    # fence/idle-expiry destroy session state exactly as before.
    session_durability_enabled: bool = True
    # A parked session idle longer than this is HIBERNATED: interpreter
    # state + workspace manifest checkpointed to the session store, the
    # sandbox disposed, the chip released back through _session_held
    # accounting (the autoscaler sees reclaimed supply). The session's next
    # turn restores lazily onto a fresh sandbox (phases.restore reports the
    # cost). Kept below executor_session_idle_timeout on purpose — with
    # durability on, idle expiry hibernates instead of destroying. 0
    # disables the timer (sessions still migrate off fenced hosts).
    session_hibernate_idle_seconds: float = 45.0
    # Where interpreter-state blobs live (content-addressed objects in
    # their own Storage — NOT the workspace-file store, since record
    # eviction deletes objects). Empty = a ".session-store" dir under
    # file_storage_path (dot-prefixed, outside OBJECT_ID_RE's namespace).
    session_store_path: str = ""
    # A checkpoint nobody restored within this window is dropped (the
    # client is gone; holding its state forever is a leak, not a feature).
    session_record_ttl: float = 3600.0
    # Record-index bound; past it, oldest-saved records evict first.
    session_store_max_entries: int = 4096
    # Ceiling on one serialized interpreter state (the runner refuses
    # larger snapshots; the session then stays live until idle close —
    # honest degradation, never a truncated checkpoint).
    session_snapshot_max_bytes: int = 67108864
    # Runner round-trip budget for the snapshot/restore ops themselves.
    session_snapshot_timeout: float = 30.0
    # libtpu gives one process exclusive chip access, so warm-JAX sandboxes
    # on one machine must be serialized: at most this many hold the local
    # TPU at once (local backend spawn lease; raise on multi-chip hosts
    # where TPU_VISIBLE_CHIPS partitioning is in play).
    local_tpu_slots: int = 1
    # Max warm sandboxes a TPU pool lane keeps per backend (kubernetes):
    # each warm TPU pod owns its chips for its whole pool residency, so the
    # reference's target of 5 warm pods would demand 5× the chips of one
    # request and wedge Pending on a single-slice node (VERDICT r1 #5).
    tpu_warm_pool_capacity: int = 1
    # Per-lane capacity overrides layered over tpu_warm_pool_capacity,
    # keyed by the lane's chip count as a string (env vars are JSON):
    # {"4": 3} lets the 4-chip lane pool three warm pods on a cluster with
    # three 4-chip slices while bigger lanes keep the flat default. This
    # is the physical ceiling the autoscaler's dynamic targets are clamped
    # under — without it, demand-adaptive targets on kubernetes could
    # never exceed one warm pod per TPU lane no matter the hardware.
    tpu_warm_pool_capacity_by_chip_count: dict = Field(default_factory=dict)

    @classmethod
    def from_env(cls, environ: dict[str, str] | None = None) -> "Config":
        env = os.environ if environ is None else environ
        # An explicit APP_JAX_COMPILATION_CACHE_DIR below still overrides
        # (deployment setting; "" = cache off).
        values: dict[str, Any] = {"jax_compilation_cache_dir": jax_cache_dir(env)}
        for name, field in cls.model_fields.items():
            key = ENV_PREFIX + name.upper()
            if key not in env:
                continue
            raw = env[key]
            ann = str(field.annotation)
            if "dict" in ann or "list" in ann:
                try:
                    values[name] = json.loads(raw)
                except json.JSONDecodeError as e:
                    raise ValueError(
                        f"environment variable {key} must be valid JSON: {e}"
                    ) from None
            elif "bytes" in ann:
                values[name] = raw.encode()
            else:
                values[name] = raw  # pydantic coerces int/float/bool/str
        return cls(**values)
