"""The orchestrator: pooled single-use sandboxes + file round-trips.

Behavior parity with the reference's KubernetesCodeExecutor
(src/code_interpreter/services/kubernetes_code_executor.py:48-279), rebuilt
backend-agnostic and TPU-aware:

- `execute()` accepts BOTH inline `source_code` and file-based `source_file`
  coherently (the reference fork broke mid-refactor and its gRPC path crashed
  on the old kwarg — SURVEY.md §0.1; here both surfaces work).
- Warm pool is keyed by chip_count lanes: an Execute asking for a 4-chip
  slice gets a sandbox whose warm runner already initialized that topology
  (kubernetes_code_executor.py:163-201 pooled only "a pod"; a TPU pool must
  pool "a topology" — SURVEY.md §2 census).
- Workspace sync is delta-based (services/transfer.py): per-host SHA-256
  manifests skip uploads the sandbox already holds and downloads whose
  content is already in content-addressed Storage — a session turn with
  unchanged input files moves O(1) bytes, not O(total bytes x hosts). Hosts
  on an old executor binary transparently fall back to full transfers.
- Infrastructure failures retry up to 3× with exponential backoff
  (kubernetes_code_executor.py:76-80); user-code failures never retry.
- Per-request phase timings (queue-wait/upload/exec/download) are returned —
  the observability the reference lacked (SURVEY.md §5).
"""

from __future__ import annotations

import asyncio
import contextvars
import json
import logging
import time
from collections import deque
from dataclasses import dataclass, field

import httpx

from ..config import Config
from ..utils import tracing
from ..utils.logs import PhaseTimer
from ..utils.metrics import ExecutorMetrics
from ..utils.retrying import RetryPolicy, retry_async
from ..utils.tracing import Tracer
from ..utils.validation import (
    OBJECT_ID_RE,
    SHA256_HEX_RE,
    normalize_workspace_path,
)
from .autoscaler import LaneSnapshot, PoolAutoscaler
from .backends.base import Sandbox, SandboxBackend, SandboxSpawnError, num_hosts_for
from .batcher import Batcher, BatchJob, BatchKey, freeze_mapping
from .circuit_breaker import BreakerBoard
from .compile_cache import (
    PREWARM_SOURCES,
    CompileCacheStore,
    SandboxCacheSync,
)
from .errors import (  # noqa: F401 — canonical home is errors.py; re-exported
    AdmissionRejectedError,
    CapacityTimeoutError,
    CircuitOpenError,
    DeadlineInfeasibleError,
    ExecutorError,
    LimitExceededError,
    QueueDepthError,
    QuotaExceededError,
    SessionLimitError,
    SessionRestoringError,
    StaleLeaseError,
    StateStoreDegradedError,
)
from .leases import Lease, LeaseRegistry
from .limits import VIOLATION_KINDS, request_limits, validate_config_limits
from .perf_observer import PerfObserver
from .quotas import QuotaEnforcer, QuotaVerdict
from .result_memo import (
    SHARED_SCOPE,
    ResultMemoStore,
    binary_key_of,
    derive_key,
    result_content_sha,
)
from .scheduler import SandboxScheduler
from .session_store import SessionStore
from .state_store import StateStore, make_state_store, resolve_replica_id
from .storage import Storage, StorageObjectNotFound
from .transfer import (
    HostManifest,
    SandboxTransfer,
    TransferStats,
    parse_files_field,
)
from .usage import UsageDraft, UsageLedger

logger = logging.getLogger(__name__)

# The ONLY Result.phases keys the phase_seconds latency histogram may
# observe. Structural fix for a bug class three PRs re-fixed one key at a
# time (compile_cache_* in PR 6, batch_jobs/batch_index in PR 7, again in
# PR 8): phases also carries byte counts, cache/demux coordinates, the
# trace id, and now per-tenant attribution fields (chip_seconds /
# device_op_seconds) — none of which are latencies. An ALLOWLIST means a
# new non-latency key is excluded by default instead of poisoning the
# histogram until someone notices; a new latency phase must be added here
# deliberately (and the regression test in test_usage.py will catch a
# histogram observing anything else).
LATENCY_PHASES = frozenset(
    {"queue_wait", "upload", "exec", "download", "restore"}
)

# Where a served turn's time went outside the four latency phases, stamped
# into Result.phases by the serial path (seconds; 0.0 where the stage did not
# run, or the executor binary sends no stages). None is in LATENCY_PHASES:
# the histogram and the perf observer's baselines see nothing new.
#   edge_before / edge_after   the request's arrival to the queue, and the
#                              download's end to the body being serialised
#   turnover_before            the pool.turnover that put this sandbox back
#   pool_idle_before           that turnover's end (pooled_at) to the pop
#   exec_wire                  phases.exec minus the sandbox handler's total_s
#   sandbox_before_run / sandbox_after_run
#                              the handler's stages before the pipe write
#                              into the warm runner and after its reply line
#   runner_pickup              pipe write until the runner read the line
#   runner_before_user / runner_user_code / runner_after_user
#                              the runner's stages around runpy.run_path
STAGE_PHASES = (
    "edge_before",
    "edge_after",
    "turnover_before",
    "pool_idle_before",
    "exec_wire",
    "sandbox_before_run",
    "sandbox_after_run",
    "runner_pickup",
    "runner_before_user",
    "runner_user_code",
    "runner_after_user",
)
# What the numpy shim counted during the turn's user code (npdispatch's
# `lazy.Counters`, whose docstring says what each field means; taken by the
# warm runner), stamped into Result.phases on every served turn of a runner
# that has the shim installed, 0 where it did nothing: the field's name after
# `shim_`, a duration's `_s` left off. Counts, bytes, and six durations in
# seconds (`shim_load`, `shim_h2d`, `shim_host`, `shim_dispatch`, `shim_wait`,
# `shim_d2h`: the stages that, with a remainder, tile `runner_user_code`).
# None is in LATENCY_PHASES, so the histogram sees none of them.
SHIM_PHASES = {
    name: "shim_" + name.removesuffix("_s")
    for name in (
        "programs", "exec_cache_misses", "nodes", "flushes",
        "load_files", "load_bytes", "load_s",
        "h2d_arrays", "h2d_bytes", "h2d_s",
        "donated_bytes", "aligned_stores", "kernel_stores", "histograms",
        "dots", "dot_flops", "ufunc_methods", "fallbacks",
        "host_s", "dispatch_s", "wait_s",
        "d2h_arrays", "d2h_bytes", "d2h_s",
    )
}
_RUNNER_BEFORE_USER = ("runner.prepare", "runner.profile_start", "runner.limits_arm")
_RUNNER_AFTER_USER = ("runner.limits_restore", "runner.profile_stop", "runner.finish")

# The request's marks on either side of the executor's work, on the tracer's
# clock: {"t0", "before": [(name, started)], "queued_at", "after": [...],
# "download_at"}. Set by execute(); read where the queue is entered, where
# the download ends and where the body is about to be serialised. A
# contextvar for the same reason as the three below.
_edge_var: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "request_edge_marks", default=None
)

# True only inside _execute_trusted (the compile-cache pre-warm): the running
# request's source is control-plane-authored, so it does NOT taint its
# sandbox's compile-cache provenance. Everything else — every API-originated
# execute, session or one-shot — is tenant code and taints the sandbox
# forever (see SandboxCacheSync.tainted). A contextvar, not a parameter:
# the flag must ride the request's own task through the retry/session
# plumbing without widening every signature in between.
_trusted_source_var: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "compile_cache_trusted_source", default=False
)

# The trigger reason when THIS request's profiler run was armed by the perf
# observer (auto-triggered profiling), None otherwise. Control-plane-induced
# work must not hit tenant ledgers (the PR 9 trusted-run rule): the harvest
# path reads this to pull profile.zip OUT of the tenant's files/bill and
# into the profile store. A contextvar for the same reason as
# _trusted_source_var: the flag must ride the request's own task through
# the session/stream plumbing without widening every signature in between.
_auto_profile_var: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "perf_auto_profile_reason", default=None
)

# True while the running request DECLARED purity (no net, no randomness, no
# wall-clock reads — the client's promise): _run_on_sandbox forwards the
# declaration to the executor, which echoes it with a hashed result block
# the memo-record path verifies end-to-end. A contextvar for the same
# reason as the two above: the flag must ride the request's own task
# through retry/batch/stream plumbing without widening every signature.
_pure_run_var: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "result_memo_pure_run", default=False
)


def _drain(pool: deque) -> list:
    drained = []
    while pool:
        drained.append(pool.popleft())
    return drained


@dataclass
class Result:
    stdout: str
    stderr: str
    exit_code: int
    files: dict[str, str]  # absolute workspace path -> storage object id
    # Phase timings (seconds) + transfer byte counters, plus the request's
    # trace_id (a string) when tracing sampled it.
    phases: dict[str, float | str] = field(default_factory=dict)
    warm: bool = False
    # Per-stream truncation markers (satellite: the executor always tracked
    # these; clients previously had to pattern-match "[stdout truncated]").
    stdout_truncated: bool = False
    stderr_truncated: bool = False
    # Session continuity (executor_id requests only; 0/False otherwise):
    # session_seq is this request's 1-based position in its session — a
    # client expecting an existing session that sees 1 knows prior state was
    # lost (idle expiry). session_ended reports that THIS request killed the
    # session (runner timeout-kill/crash); the next request starts fresh.
    session_seq: int = 0
    session_ended: bool = False
    # Executor-verified purity echo (declared-pure memo-miss runs only):
    # the result hash the executor computed over its response, re-derived
    # and matched by the control plane from the same wire fields. None when
    # the run didn't declare purity, an old binary didn't echo, or the
    # hashes disagreed — nothing is recorded then (services/result_memo.py).
    pure_echo: str | None = None
    # The request's edge marks (execute() leaves them here for the API
    # surface's close_edge(); None once closed). Never on the wire.
    edge: dict | None = field(default=None, repr=False, compare=False)


@dataclass
class _Session:
    """One executor_id's live sandbox lease.

    The sandbox is held OUT of the pool for the session's lifetime — no
    /reset between its requests, so the workspace (and the warm process's
    imported modules) persist. `lock` serializes requests sharing the id;
    `ready` lets concurrent first requests wait for one creation instead of
    racing spawns. A closed session stays closed — holders re-fetch from
    the session table and recreate."""

    lane: int
    sandbox: Sandbox | None = None
    ready: asyncio.Future = field(default_factory=asyncio.Future)
    lock: asyncio.Lock = field(default_factory=asyncio.Lock)
    last_used: float = 0.0
    closed: bool = False
    seq: int = 0  # requests served (exposed as Result.session_seq)
    # Session durability plane (services/session_store.py): the tenant the
    # session was opened under (checkpoint key scope), the durable record
    # awaiting lazy restore on the first turn after a wake (None once
    # applied), whether that restore is in flight RIGHT NOW (a second turn
    # then sheds with the typed 409 instead of racing a double-restore),
    # and the sweep's idle-chip-seconds accounting watermark.
    tenant: str | None = None
    pending_restore: dict | None = None
    restoring: bool = False
    idle_accounted: float = 0.0


class CodeExecutor:
    def __init__(
        self,
        backend: SandboxBackend,
        storage: Storage,
        config: Config | None = None,
        metrics: ExecutorMetrics | None = None,
        breakers: BreakerBoard | None = None,
        scheduler: SandboxScheduler | None = None,
        tracer: Tracer | None = None,
        compile_cache: CompileCacheStore | None = None,
        usage: UsageLedger | None = None,
        quotas: QuotaEnforcer | None = None,
        perf: PerfObserver | None = None,
        state_store: StateStore | None = None,
    ) -> None:
        self.backend = backend
        self.storage = storage
        self.config = config or Config()
        # Malformed operator limit config must fail HERE (service boot),
        # not per request as a spurious client 400.
        validate_config_limits(self.config)
        self.metrics = metrics or ExecutorMetrics()
        # Pluggable control-plane state (services/state_store.py): the
        # scheduler's WFQ tags, breaker verdicts, lease generations/fence
        # floors, and lane-occupancy gauges route through this seam. The
        # default is a PRIVATE in-memory store — every component then
        # skips its cross-replica path and runs today's single-process
        # behavior byte-for-byte. A SHARED store (APP_STATE_STORE=sqlite
        # path, or one in-memory instance handed to several in-process
        # executors) is what lets N replicas cooperate instead of
        # double-granting lanes or double-fencing hosts.
        self.state_store = state_store or make_state_store(self.config)
        self._store_shared = bool(self.state_store.shared)
        self.replica_id = (
            resolve_replica_id(self.config) or self.config.replica_self or ""
        )
        if self._store_shared and not self.replica_id:
            # A shared store handed in directly (the tests do) still
            # needs a distinct identity per executor instance.
            self.replica_id = f"replica-{id(self) & 0xFFFF:04x}"
        # Session→replica affinity router (services/replicas.py), attached
        # by the application context when a replica set is configured;
        # surfaced through /statusz. None in single-replica mode.
        self.session_router = None
        # Short-lived cache over the peer-occupancy store scan (the
        # breaker's remote-read discipline): lane -> (expires_wall, busy).
        self._peer_busy_cache: dict[int, tuple[float, int]] = {}
        # Request-scoped tracing: the executor owns the tracer so both API
        # servers (which create the root spans) and the pipeline stages here
        # (which create children) share one sampling decision and one ring.
        self.tracer = tracer or Tracer.from_config(self.config, metrics=self.metrics)
        # Per-lane spawn circuit breakers: fail fast (retryable) while the
        # backend is persistently failing instead of burning each request's
        # 300s acquire budget plus a full retry ladder (injectable for
        # deterministic chaos tests).
        self.breakers = breakers or BreakerBoard(
            failure_threshold=self.config.breaker_failure_threshold,
            cooldown=self.config.breaker_cooldown,
            store=self.state_store,
        )
        # Backends with long-running watch paths (kubernetes pod-watch) feed
        # the same lane breakers directly, so a watch failure counts without
        # waiting for the whole spawn ladder to surface it.
        bind_breakers = getattr(self.backend, "bind_breakers", None)
        if bind_breakers is not None:
            bind_breakers(self.breakers)
        # All sandbox-slot admission goes through the fair-share scheduler:
        # per-lane ordered queues, weighted fair queueing across tenants,
        # priority classes, deadline-aware admission, bounded per-tenant
        # depth. _acquire is a thin client of its grant tokens.
        self.scheduler = scheduler or SandboxScheduler(
            self.config, metrics=self.metrics, store=self.state_store
        )
        # Per-tenant usage metering (services/usage.py): every request's
        # chip-seconds, queue wait, transfer bytes, recompiles, violations,
        # and request/batch-job counts attributed to its tenant, in a
        # durable journal-backed ledger. The kill switch constructs a
        # disabled ledger whose record paths are no-ops (pre-metering
        # behavior byte-for-byte). Queue wait is attributed by the
        # scheduler at grant time — only it knows tenant AND true wait.
        self.usage = usage or UsageLedger(self.config, metrics=self.metrics)
        if self.usage.enabled:
            self.scheduler.usage = self.usage
        # Quota enforcement (services/quotas.py): the admission gate that
        # READS the ledger above — sliding-window chip-second budgets,
        # request-rate/concurrency caps, and repeat-offender quarantine,
        # all checked before the scheduler ever enqueues. The kill switch
        # (APP_QUOTAS_ENABLED=0) constructs a disabled enforcer whose
        # admit()/release() are no-ops — pre-quota behavior byte-for-byte.
        self.quotas = quotas or QuotaEnforcer(
            self.config,
            usage=self.usage,
            metrics=self.metrics,
            store=self.state_store,
        )
        # Spawn retries mirror the reference's ladder (3 attempts, 0.5s
        # exponential base capped at 5s) with full jitter so parallel refill
        # failures don't re-synchronize into retry waves.
        self._spawn_retry_policy = RetryPolicy(
            attempts=max(1, self.config.executor_spawn_retry_attempts),
            base_delay=0.5,
            max_delay=5.0,
            retry_on=(SandboxSpawnError,),
        )
        self._execute_retry_policy = RetryPolicy(
            attempts=3,
            base_delay=0.5,
            max_delay=5.0,
            retry_on=(ExecutorError,),
        )
        self._pools: dict[int, deque[Sandbox]] = {}
        self._spawning: dict[int, int] = {}
        # Requests currently holding a sandbox, per lane. With reuse on,
        # these sandboxes come BACK to the pool at release (generation
        # turnover keeps the TPU lease), so they count toward the lane
        # target — a refill spawn for a sandbox that is about to recycle
        # would fight it for the physical TPU slot and lose (VERDICT r2 #1).
        self._in_use: dict[int, int] = {}
        # Of the in-use counts above, how many are only mid-RELEASE
        # (post-request turnover in a background task): still physical
        # slot-holders for the capacity math, but their requester is gone
        # — the autoscaler's demand model must not read them as load, or
        # a strictly sequential client (next request arriving while the
        # previous release settles) would ratchet the lane target up.
        self._releasing: dict[int, int] = {}
        # executor_id -> live session (sandbox held out of the pool).
        self._sessions: dict[str, _Session] = {}
        # EVERY live sandbox (pooled, in-use, session-parked), keyed by id:
        # the device-health probe's host inventory. Registered the moment a
        # spawn succeeds, dropped in _dispose — the in-use window is where
        # wedges actually happen (a mid-device-op kill), so probing only
        # the pool would miss the exact hosts that matter.
        self._live_sandboxes: dict[str, tuple[int, Sandbox]] = {}
        # Sandboxes held by sessions, per lane: they occupy physical TPU
        # slots (capacity accounting) but are NOT due back soon, so they are
        # tracked apart from _in_use (which waiters treat as imminent supply).
        self._session_held: dict[int, int] = {}
        self._fill_tasks: set[asyncio.Task] = set()
        self._dispose_tasks: set[asyncio.Task] = set()
        self._closed = False
        # Graceful drain (SIGTERM): while draining, new executes shed with a
        # retryable error and wait_drained() watches this in-flight count.
        self._draining = False
        self._inflight = 0
        # Repeat-offender accounting: CONSECUTIVE runner-killing limit
        # violations per lane (a clean request on the lane resets it). At
        # the breaker threshold the lane trips open for one cooldown — the
        # native failure count can't get there on its own because every
        # post-violation refill spawn succeeds and resets it.
        self._violation_strikes: dict[int, int] = {}
        # Fleet-wide persistent XLA compile cache: the hot set seeded into
        # every sandbox's cache dir at spawn and harvested back at
        # turnover/teardown, so the fleet compiles each kernel once
        # (services/compile_cache.py; the kill switch makes this a no-op
        # store that seeds and harvests nothing).
        self.compile_cache = compile_cache or CompileCacheStore.from_config(
            self.config
        )
        self._prewarm_started = False
        # Batched multi-chip execution lanes: eligible small jobs from one
        # tenant coalesce in a bounded window (services/batcher.py) and run
        # as ONE fused dispatch on a single multi-chip sandbox instead of N
        # serial round-trips. The kill switch (APP_BATCHING_ENABLED=0)
        # leaves this None and every request takes the exact serial path.
        self.batcher: Batcher | None = None
        if self.config.batching_enabled:
            self.batcher = Batcher(
                window_s=self.config.batch_window_ms / 1000.0,
                max_jobs=self.config.batch_max_jobs,
                dispatch=self._dispatch_batch,
            )
        # Demand-adaptive warm-pool autoscaling (services/autoscaler.py):
        # per-lane targets driven by arrival rate, queue depth, and the
        # scheduler's queue-wait/spawn EWMAs replace the static
        # executor_pod_queue_target_length constant as _lane_target's
        # input. The kill switch (APP_POOL_AUTOSCALE_ENABLED=0) makes
        # target() return the static constant — pre-autoscale behavior
        # byte-for-byte. Policy lives in the autoscaler; this class feeds
        # it snapshots and actuates (fill_pool up, the idle reaper down).
        self.autoscaler = PoolAutoscaler(
            self.config,
            clock=self.scheduler.now,
            metrics=self.metrics,
            tracer=self.tracer,
        )
        # Control-plane-wide taint for backends whose sandboxes SHARE one
        # cache dir (compile_cache_dir_scope == "shared": the local
        # backend's default mode). There, per-sandbox taint can't vouch
        # for the dir — any tenant run on ANY sandbox writes the same
        # path every other sandbox's harvest manifest lists — so the
        # first tenant execute ends harvesting for this control plane's
        # lifetime (the dir persists; a dir that was not empty at start is
        # "external" and never harvested, see
        # LocalSandboxBackend.compile_cache_dir_scope). Pre-warm runs before
        # tenant load, so the store still fills in the trusted-only epoch.
        self._shared_cache_tainted = False
        # Per-chip lease fencing (services/leases.py): every spawn mints a
        # monotonic generation token per lease scope (the physical chip-set
        # — backend lease_scope, or the lane); a wedged verdict revokes the
        # lease (on_host_wedged → fence_host), so a stale claim can never
        # re-wedge a successor's chips. Fenced scopes re-admit only after
        # the configured clean-probe streak.
        self.leases = LeaseRegistry(
            readmit_streak=self.config.device_probe_readmit_streak,
            clock=self.scheduler.now,
            store=self.state_store,
        )
        # Actuation budget: fence timestamps per lane — at most
        # device_fence_max_per_window actuations per window, so a probe
        # false-positive storm cannot mass-dispose a serving lane.
        self._fence_times: dict[int, deque[float]] = {}
        # Performance anomaly plane (services/perf_observer.py): streaming
        # latency baselines per (lane, phase) and per tenant, EWMA-banded
        # drift verdicts, per-request device-memory accounting, and
        # auto-triggered profiling. The kill switch constructs a disabled
        # observer — no recording, no device-memory wire field, no
        # auto-profiles, no perf metric families: today's behavior
        # byte-for-byte.
        self.perf = perf or PerfObserver(
            self.config,
            metrics=self.metrics,
            tracer=self.tracer,
            clock=self.scheduler.now,
        )
        # Telemetry-plane attachments (set by the application context): the
        # device-health probe daemon and the OTLP exporter, surfaced through
        # GET /statusz. Optional — the executor runs fine without either.
        self.device_health = None
        self.otlp_exporter = None
        # Deterministic result memoization (services/result_memo.py): a
        # declared-pure run that completed limit-clean is recorded keyed on
        # everything that could change its output, and a later identical
        # request serves from the record at admission — no scheduler ticket,
        # no sandbox round-trip, no chip-second billed. The index rides the
        # state store above (coherent across replicas); the kill switch
        # constructs a disabled store and every path is pre-memo
        # byte-for-byte.
        self.result_memo = ResultMemoStore.from_config(
            self.config, self.state_store, self.storage, metrics=self.metrics
        )
        # Session durability plane (services/session_store.py): idle
        # sessions checkpoint (interpreter state + workspace manifest) into
        # this store, dispose their sandbox, and release the chip through
        # _session_held — the autoscaler sees reclaimed supply — then
        # restore lazily on their next turn, session_seq continuous. The
        # same path migrates live sessions off fenced hosts. The index
        # rides the state store (a session hibernated behind replica A
        # restores behind replica B); the kill switch constructs a
        # disabled store and every session path is pre-durability
        # byte-for-byte (pin-forever semantics).
        self.session_store = SessionStore.from_config(
            self.config, self.state_store, self.storage, metrics=self.metrics
        )
        # Satellite observability: cumulative parked-idle chip-seconds the
        # sweeper has accounted (the reclaimed-supply justification metric,
        # also a statusz field).
        self._idle_chip_seconds = 0.0
        # The executor-binary component of every memo key, computed once: a
        # binary upgrade changes the key and old records miss.
        self._memo_binary_key = (
            binary_key_of(
                str(getattr(self.backend, "binary", "") or "")
                or self.config.executor_binary,
                self.config.executor_image,
            )
            if self.result_memo.enabled
            else ""
        )
        # One persistent client for all sandbox HTTP: connection pooling
        # keeps per-request TCP setup off the Execute path.
        self._client: httpx.AsyncClient | None = None
        # Keep-alive reuse proof for the pooled client: ids of network
        # streams already seen on a response — a repeat id is a dispatch
        # that skipped TCP (+TLS) setup entirely.
        self._seen_streams: set[int] = set()
        self.metrics.bind_pool(self._pools)
        self.metrics.bind_sessions(self._sessions)
        self.metrics.bind_breakers(self.breakers)
        self.metrics.bind_scheduler(self.scheduler)
        self.metrics.bind_compile_cache(self.compile_cache)
        self.metrics.bind_autoscale(self)
        self.metrics.bind_quotas(self.quotas)
        self.metrics.bind_perf(self.perf)
        self.metrics.bind_result_memo(self.result_memo)

    async def _count_stream_reuse(self, response) -> None:
        """Response event hook on the shared client: count dispatches that
        rode an already-established keep-alive connection. httpcore exposes
        the underlying socket as the identity-stable `network_stream`
        extension — a repeat id is a request that paid zero TCP setup.
        Mock/fault transports lack the extension; the hook no-ops there."""
        stream = response.extensions.get("network_stream")
        if stream is None:
            return
        key = id(stream)
        if key in self._seen_streams:
            self.metrics.executor_connections_reused.inc()
        else:
            self._seen_streams.add(key)
            # Bound the id set: a long-lived control plane churns sockets
            # (pool expiry, sandbox turnover) and ids recycle with them.
            if len(self._seen_streams) > 4096:
                self._seen_streams.clear()
                self._seen_streams.add(key)

    def _http_client(self) -> httpx.AsyncClient:
        if self._client is None or self._client.is_closed:
            # A fault-injecting backend supplies a transport that drops a
            # seeded fraction of requests on the wire (chaos testing the
            # mid-execute connection-loss path); real backends supply none.
            transport_fn = getattr(self.backend, "http_transport", None)
            transport = transport_fn() if transport_fn is not None else None
            # Explicit keep-alive pooling, tuned for the fleet shape: each
            # sandbox host gets a persistent connection (the C++ server
            # runs an HTTP/1.1 keep-alive loop), and the expiry comfortably
            # outlives a pool-idle gap so sequential dispatches to one host
            # reuse one TCP connection instead of re-handshaking
            # (executor_connections_reused_total proves it).
            limits = httpx.Limits(
                max_connections=max(
                    64, 4 * self.config.executor_pod_queue_target_length
                ),
                max_keepalive_connections=64,
                keepalive_expiry=30.0,
            )
            self._client = httpx.AsyncClient(
                timeout=httpx.Timeout(30.0),
                transport=transport,
                limits=limits,
                event_hooks={"response": [self._count_stream_reuse]},
            )
        return self._client

    # ------------------------------------------------------------ degradation

    def degraded(self) -> bool:
        """Is the control plane in degraded mode? True while the DEFAULT
        lane's spawn breaker is hard-open (the lane an Execute without an
        explicit chip_count lands on — config.default_chip_count, not a
        literal lane 0): new work there fails fast, so health surfaces must
        advertise NOT_SERVING/503 and shed load until a half-open probe
        succeeds."""
        return self.breakers.is_open(self.config.default_chip_count)

    def degraded_retry_after(self) -> float:
        """Seconds a shedding response should tell clients to wait
        (Retry-After); 0 when serving normally."""
        return self.breakers.retry_after(self.config.default_chip_count)

    def lane_degraded(self, chip_count: int) -> bool:
        """Per-lane degradation, for gRPC health's per-service-name
        reporting (`lane-<n>`): a dead 4-chip nodepool must read
        NOT_SERVING on `lane-4` while CPU-lane traffic stays SERVING."""
        return self.breakers.is_open(chip_count)

    # ----------------------------------------------------------------- drain

    @property
    def draining(self) -> bool:
        return self._draining

    def inflight(self) -> int:
        """Execute/execute_stream requests currently running end to end
        (admission through release hand-off)."""
        return self._inflight

    def begin_drain(self) -> None:
        """Stop admitting new executes (they shed with a retryable capacity
        error) while in-flight work runs to completion — the SIGTERM half of
        graceful shutdown; health surfaces flip alongside."""
        self._draining = True

    async def wait_drained(self, grace: float) -> bool:
        """Wait up to `grace` seconds for in-flight executes to finish.
        Returns True when the service drained fully (False = grace expired
        with work still running; close() will cut it off)."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(0.0, grace)
        while self._inflight > 0 and loop.time() < deadline:
            await asyncio.sleep(0.05)
        return self._inflight == 0

    def _check_admission_open(self) -> None:
        if self._draining:
            raise SessionLimitError(
                "service is draining (shutting down); retry against "
                "another replica"
            )

    # ------------------------------------------------------------------ pool

    def _pool(self, chip_count: int) -> deque[Sandbox]:
        return self._pools.setdefault(chip_count, deque())

    # Sandbox device-health marks that disqualify a pooled host from
    # SERVING: wedged (device plane dead), draining (fenced, dispose in
    # flight), recovering (on a fenced scope, still earning its clean-probe
    # streak).
    _UNSERVABLE_HEALTH = frozenset({"wedged", "draining", "recovering"})

    def _pool_supply(self, chip_count: int) -> int:
        """Pooled sandboxes that can actually serve. Wedged hosts hold a
        deque slot until the fencing actuator drains them (or, with the
        actuation kill switch off, until an operator does); draining and
        recovering hosts are quarantined by design — none of them count as
        supply, or a lane of zombies would read "full" and never refill."""
        pool = self._pools.get(chip_count)
        if not pool:
            return 0
        return sum(
            1
            for sandbox in pool
            if sandbox.meta.get("device_health") not in self._UNSERVABLE_HEALTH
        )

    def _pool_standby(self, chip_count: int) -> int:
        """Pooled RECOVERING hosts: supply-in-transit, like an in-flight
        spawn — they hold their physical chips and will serve once the
        clean-probe streak re-admits them, so refills must count them
        (spawning replacements for hosts that are about to re-admit would
        stampede the backend and, on a constrained lane, deadlock on the
        chips the recovering host still owns). Wedged/draining hosts are
        NOT standby: their chips are being reclaimed, and the refill that
        replaces them is exactly the point."""
        pool = self._pools.get(chip_count)
        if not pool:
            return 0
        return sum(
            1
            for sandbox in pool
            if sandbox.meta.get("device_health") == "recovering"
        )

    def _known_lanes(self) -> set[int]:
        """Every lane with any pool presence (pooled, in-use, spawning,
        session-parked) or autoscaler state — ONE membership rule shared
        by the sweep, the /healthz supply rows, and the autoscale gauges,
        so a lane can never be managed but invisible (or vice versa)."""
        return (
            set(self._pools)
            | set(self._in_use)
            | set(self._spawning)
            | set(self._session_held)
            | set(self.autoscaler.lanes())
        )

    def _lane_snapshot(self, chip_count: int, *, queued: int | None = None) -> LaneSnapshot:
        """The autoscaler's per-lane demand/supply instant."""
        return LaneSnapshot(
            queued=self.scheduler.queued(chip_count) if queued is None else queued,
            # Demand counts only sandboxes an ACTIVE request holds;
            # mid-release holds are supply-in-transit, not load.
            in_use=max(
                0,
                self._in_use.get(chip_count, 0)
                - self._releasing.get(chip_count, 0),
            ),
            pooled=self._pool_supply(chip_count),
            spawning=self._spawning.get(chip_count, 0),
            recovering=self._pool_standby(chip_count),
            draining=self._draining_count(chip_count),
            queue_wait_ewma=self.scheduler.queue_wait_ewma(chip_count),
            spawn_ewma=self.scheduler.spawn_ewma(chip_count),
            # Explicit hibernated-wake supply signal (session durability
            # plane): parked sessions whose wake would land on this lane.
            # Cached inside the session store; {} when durability is off.
            hibernated=self.session_store.hibernated_by_lane().get(
                chip_count, 0
            ),
        )

    def _draining_count(self, chip_count: int) -> int:
        """LIVE fenced hosts of the lane still being disposed (pooled or
        not): the /healthz + snapshot observability of an in-flight
        drain-and-replace."""
        return sum(
            1
            for lane, sandbox in self._live_sandboxes.values()
            if lane == chip_count and sandbox.meta.get("lease_fenced")
        )

    def _lane_capacity(self, chip_count: int) -> int | None:
        capacity_fn = getattr(self.backend, "pool_capacity", None)
        capacity = capacity_fn(chip_count) if capacity_fn is not None else None
        if (
            capacity is not None
            and self._store_shared
            # Backends whose capacity names REPLICA-LOCAL hardware (each
            # replica brought its own node pool) opt out: peers' holds
            # don't contend for these chips.
            and getattr(self.backend, "capacity_shared_across_replicas", True)
        ):
            # N replicas share one physical substrate (the k8s cluster's
            # chips, or one machine's TPU): subtract what PEERS currently
            # hold so their spawn-vs-wait decisions cooperate. The
            # cooperation is BOUNDED-STALENESS (gauges publish at the
            # spawn claim, reads cache 0.25s), not an atomic reservation:
            # two replicas racing the last slot inside one freshness
            # window both spawn, and the overshoot degrades to what the
            # physical backend arbitrates anyway — a queued/failed spawn
            # — never to corruption. Stale gauges (dead replica) age out
            # on the heartbeat TTL so a crashed peer's holds stop gating
            # the survivors.
            capacity = max(0, capacity - self._peer_busy(chip_count))
        return capacity

    # ------------------------------------------------- cross-replica state

    def _publish_occupancy(self, lane: int) -> None:
        """Publish this replica's physical holds on the lane (in-use +
        session-held + in-flight spawns) into the shared store — the other
        half of `_lane_capacity`'s peer subtraction. No-op in
        single-replica mode."""
        if not self._store_shared:
            return
        busy = (
            self._in_use.get(lane, 0)
            + self._session_held.get(lane, 0)
            + self._spawning.get(lane, 0)
        )
        try:
            self.state_store.put(
                "occupancy",
                f"{lane}/{self.replica_id}",
                {"busy": busy, "ts": time.time()},
            )
        except Exception:  # noqa: BLE001 — a gauge write must not fail serving
            logger.warning("occupancy publish failed", exc_info=True)

    def _peer_busy(self, lane: int) -> int:
        """Sum of PEER replicas' fresh occupancy gauges for the lane.
        The store scan is bounded by a short freshness window (the
        breaker's _remote_cache discipline): _lane_capacity sits on the
        hot acquire path, and occupancy staleness of a quarter second is
        already inside the sweep-kick staleness bound."""
        now = time.time()
        expires, cached = self._peer_busy_cache.get(lane, (0.0, 0))
        if now < expires:
            return cached
        ttl = max(1.0, self.config.replica_heartbeat_ttl)
        total = 0
        try:
            rows = self.state_store.items("occupancy")
        except Exception:  # noqa: BLE001 — degraded store reads as empty
            # Cache the failure verdict too: a degraded store must not be
            # re-scanned (up to the sqlite busy timeout, on the event
            # loop) by every capacity check.
            self._peer_busy_cache[lane] = (now + 0.25, 0)
            return 0
        for key, record in rows.items():
            row_lane, _, rid = key.partition("/")
            if row_lane != str(lane) or rid == self.replica_id:
                continue
            if not isinstance(record, dict):
                continue
            ts = record.get("ts")
            busy = record.get("busy")
            if (
                isinstance(ts, (int, float))
                and now - ts <= ttl
                and isinstance(busy, (int, float))
            ):
                total += max(0, int(busy))
        self._peer_busy_cache[lane] = (now + 0.25, total)
        return total

    def _notify_lane(self, chip_count: int) -> None:
        """Capacity turnover on the lane: the scheduler wakes the next
        waiter in fair order (an explicit grant, not a broadcast)."""
        self._publish_occupancy(chip_count)
        self.scheduler.kick(chip_count)

    def _notify_all_lanes(self) -> None:
        """Wake waiters on EVERY lane: freed capacity on a constrained
        backend is shared across lanes (see _session_held_constrained), so a
        session closing in lane 0 can unblock a lane-4 waiter."""
        self.scheduler.kick_all()

    def _session_held_constrained(self) -> int:
        """Session-parked sandboxes summed over ALL capacity-constrained
        lanes. Constrained lanes are treated as one shared physical
        substrate — the same model behind _evict_idle_other_lanes: on the
        local backend every warm-JAX sandbox holds the same exclusive TPU
        regardless of lane, so a session parked in lane 0 must gate lane 4's
        spawns too (per-lane counting would wedge those spawns behind libtpu
        for the session's whole lifetime). On backends whose lanes are truly
        separate pools this over-counts — a spawn then waits for a session
        to close when it needn't — which errs on the safe side."""
        capacity_fn = getattr(self.backend, "pool_capacity", None)
        if capacity_fn is None:
            return 0
        return sum(
            held
            for lane, held in self._session_held.items()
            if held and capacity_fn(lane) is not None
        )

    def _lane_target(self, chip_count: int, *, extra_free: int = 0) -> int:
        """Warm-pool target for a lane, capped by the backend's physical
        capacity: a warm TPU sandbox owns its chips for its whole pool
        residency, so an uncapped target (the reference's flat 5,
        config.py:77) would demand N× the chips of one request — wedging
        spawns behind libtpu's exclusive access locally, or pods Pending on
        Kubernetes. CPU lanes report no cap and keep the configured target.

        `extra_free` lets a closing session's turnover treat its own slot as
        available for the recycle decision while `_session_held` still counts
        it (the slot is only truly free once the sandbox is pooled/disposed).

        The uncapped input is the autoscaler's dynamic per-lane target
        (demand model: arrival rate, queue depth, queue-wait/spawn EWMAs);
        with the kill switch off it IS the static constant, so this method
        behaves exactly as before autoscaling existed."""
        target = self.autoscaler.target(chip_count)
        capacity = self._lane_capacity(chip_count)
        if capacity is not None:
            # Session-held sandboxes occupy physical slots for their whole
            # session lifetime — the pool must not demand the chips back.
            capacity = max(
                0, capacity - self._session_held_constrained() + extra_free
            )
            target = min(target, capacity)
        return target

    async def fill_pool(self, chip_count: int = 0) -> None:
        """Top the lane up to the target length, tracking in-flight spawns.

        In-use sandboxes count toward the target when reuse is on: they
        return to the pool at release (generation turnover), so spawning a
        replacement would overshoot — and on a capacity-constrained backend,
        deadlock against the in-flight request for the physical TPU slot."""
        if self._closed:
            return
        if self.breakers.is_open(chip_count):
            # Refill spawns against an open breaker would only feed its
            # failure count; the half-open probe (first real request after
            # cooldown) is what re-tests the backend.
            logger.debug(
                "pool refill skipped: lane-%d breaker open", chip_count
            )
            return
        pool = self._pool(chip_count)
        target = self._lane_target(chip_count)
        in_use = (
            self._in_use.get(chip_count, 0)
            if self.config.executor_reuse_sandboxes
            else 0
        )
        spawning = self._spawning.get(chip_count, 0)
        # Supply counts only servable pooled hosts (wedged/draining zombies
        # must be refilled past — their disposal is the fencing actuator's
        # job), plus recovering standby (due to re-admit; spawning past
        # them would overshoot and fight them for chips).
        missing = (
            target
            - self._pool_supply(chip_count)
            - self._pool_standby(chip_count)
            - spawning
            - in_use
        )
        if missing <= 0:
            return
        # Cap CONCURRENT refill spawns per lane: a large target jump
        # (exactly what autoscaling makes possible) must ramp in bounded
        # waves, not stampede the k8s API / libtpu attach path with every
        # missing sandbox at once. The tail of a capped fill re-arms below
        # once this wave lands.
        burst = self.config.pool_spawn_burst
        if burst > 0:
            missing = min(missing, max(0, burst - spawning))
            if missing <= 0:
                return
        self._spawning[chip_count] = self._spawning.get(chip_count, 0) + missing
        self._publish_occupancy(chip_count)
        succeeded = 0

        async def spawn_one() -> None:
            nonlocal succeeded
            try:
                # traced_seed=False: a refill task inherits whatever trace
                # context was current when fill_pool_soon fired, and a seed
                # span finishing after that request's trace is read would
                # make its span set nondeterministic. (Retry EVENTS still
                # attach while the requester's acquisition span is open —
                # exactly when they're relevant — and are silently dropped
                # once it has exported, the long-standing event semantics.)
                sandbox = await self._spawn_with_retry(
                    chip_count, traced_seed=False
                )
                if self._closed:
                    await self._dispose(sandbox)
                else:
                    sandbox.meta["pooled_at"] = self.scheduler.now()
                    pool.append(sandbox)
                    succeeded += 1
            except SandboxSpawnError:
                # degraded pool: log and continue (parity: reference logs and
                # keeps going, kubernetes_code_executor.py:184-194)
                logger.exception("pool prefill spawn failed (lane=%d)", chip_count)
            except CircuitOpenError as e:
                # The breaker opened while this refill was in flight (e.g.
                # a sibling spawn crossed the threshold): stop quietly — the
                # lane refills on the first request after a successful probe.
                logger.warning("pool prefill stopped (lane=%d): %s", chip_count, e)
            except StateStoreDegradedError as e:
                # Lease mints fail closed while the shared store is down:
                # background refills stop quietly (the lane refills on the
                # first acquire after the store heals) instead of escaping
                # the gather.
                logger.warning(
                    "pool prefill paused (lane=%d): %s", chip_count, e
                )
            finally:
                self._spawning[chip_count] -= 1
                self._notify_lane(chip_count)

        await asyncio.gather(*(spawn_one() for _ in range(missing)))
        if (
            burst > 0
            and succeeded > 0
            and not self._closed
            and self._pool_supply(chip_count)
            + self._pool_standby(chip_count)
            + self._spawning.get(chip_count, 0)
            + (
                self._in_use.get(chip_count, 0)
                if self.config.executor_reuse_sandboxes
                else 0
            )
            < self._lane_target(chip_count)
        ):
            # Burst-capped ramp: this wave landed and the lane is still
            # short — continue toward the target. Only re-arm on at least
            # one success, so a persistently failing backend degrades to
            # the pre-existing "log and refill on next acquire" behavior
            # instead of a hot retry loop.
            self.fill_pool_soon(chip_count)

    def fill_pool_soon(self, chip_count: int = 0) -> None:
        if self._closed:
            return
        task = asyncio.create_task(self.fill_pool(chip_count))
        self._fill_tasks.add(task)
        task.add_done_callback(self._fill_tasks.discard)

    async def _spawn_with_retry(
        self, chip_count: int, *, traced_seed: bool = True
    ) -> Sandbox:
        """Spawn with the retry engine + circuit breaker: bounded, jittered
        retries on SandboxSpawnError; every attempt first consults the
        lane's breaker, so a breaker opened mid-ladder (by this spawn's own
        failures or a sibling's) aborts the remaining attempts immediately
        with a retryable CircuitOpenError instead of hammering a backend
        that is down. `traced_seed` is True only for spawns AWAITED on a
        request path, where the compile-cache seed span deterministically
        finishes inside the request's trace."""
        breaker = self.breakers.lane(chip_count)

        async def attempt() -> Sandbox:
            breaker.check(chip_count)
            # Evict on EVERY attempt, not once before the retry loop: a
            # cross-lane refill that was mid-flight during the first eviction
            # can park an idle slot-holding sandbox right after it, and only
            # a fresh eviction at the next attempt can free that slot again.
            await self._evict_idle_other_lanes(chip_count)
            start = time.perf_counter()
            try:
                sandbox = await self.backend.spawn(chip_count)
            except SandboxSpawnError as e:
                # Backends with watch-path breaker integration mark errors
                # they already counted (kubernetes records one strike per
                # failed host watch) — counting the surfaced aggregate again
                # would open the lane faster than the configured threshold.
                if not getattr(e, "breaker_recorded", False):
                    breaker.record_failure()
                raise
            breaker.record_success()
            elapsed = time.perf_counter() - start
            self.metrics.spawn_seconds.observe(
                elapsed, chip_count=str(chip_count)
            )
            # Feed the scheduler's spawn-latency EWMA: one input to
            # deadline-aware admission when the warm pool is empty.
            self.scheduler.observe_spawn(chip_count, elapsed)
            # Per-chip lease FIRST: mint this sandbox's generation token
            # and push it to every host's executor before the sandbox
            # becomes visible anywhere — a stale-generation claim against
            # these chips must be distinguishable from the host's first
            # observable instant, not after a push races the first
            # dispatch. If the scope is recovering (the predecessor was
            # fenced), the replacement starts quarantined: probed, counted
            # as standby, handed nothing until the clean-probe streak
            # re-admits it.
            try:
                await self._attach_lease(sandbox, chip_count)
            except StateStoreDegradedError:
                # Mint failed closed (shared store down) AFTER the backend
                # spawn succeeded: the sandbox exists but can never be
                # granted — dispose it rather than leak a live host with
                # no lease, and surface the typed refusal (NOT a
                # SandboxSpawnError: retrying inside the same outage
                # window just burns spawns).
                await self._dispose(sandbox)
                raise
            # Register with the live-host inventory the probe daemon walks
            # (dropped again in _dispose).
            self._live_sandboxes[sandbox.id] = (chip_count, sandbox)
            # Seed the fleet's hot compile set into the fresh sandbox's
            # cache dir BEFORE it serves: the kernels someone already
            # compiled load from cache instead of recompiling. Best-effort
            # and cheap (O(hot set), conditional PUTs) — never fails a
            # spawn.
            await self._seed_compile_cache(sandbox, traced=traced_seed)
            return sandbox

        def on_retry(failures: int, error: BaseException, delay: float) -> None:
            self.metrics.retry_attempts.inc(operation="spawn")
            tracing.add_event(
                "retry",
                operation="spawn",
                attempt=failures,
                delay_s=round(delay, 3),
                error=str(error)[:200],
            )

        return await retry_async(
            attempt, self._spawn_retry_policy, on_retry=on_retry
        )

    async def _evict_idle_other_lanes(self, chip_count: int) -> None:
        """On a capacity-constrained backend, idle warm sandboxes pooled in
        OTHER lanes hold the physical TPU slots this lane's spawn needs —
        without eviction the spawn would block on the slot until timeout
        (starvation across lanes). Disposal is awaited so the slots are
        actually free before the spawn starts; the evicted lanes refill only
        when next requested."""
        capacity_fn = getattr(self.backend, "pool_capacity", None)
        if capacity_fn is None or capacity_fn(chip_count) is None:
            return
        evicted = [
            sandbox
            for lane, pool in self._pools.items()
            # Only lanes that actually hold constrained resources: draining
            # an unconstrained lane (e.g. CPU pods on kubernetes) would wipe
            # a warm pool without freeing anything.
            if lane != chip_count and capacity_fn(lane) is not None
            for sandbox in _drain(pool)
        ]
        if evicted:
            logger.info(
                "evicting %d idle sandbox(es) from other lanes to free TPU "
                "slots for lane %d",
                len(evicted),
                chip_count,
            )
            await asyncio.gather(*(self._dispose(s) for s in evicted))

    # ------------------------------------------------- lease fencing & wedge
    # recovery: the actuation half of the device-health story. The probe
    # daemon detects (PR 8); these methods act — lease revocation, lane
    # drain, dispose-and-replace, and the recovering-scope quarantine.

    def _lease_scope(self, chip_count: int, sandbox: Sandbox | None = None) -> str:
        """The lease scope a lane's sandboxes attach on: the backend's own
        hardware naming when it has one (`lease_scope(chip_count)`), else
        the chip-count lane — which on the local backend IS the chip-set
        (every warm sandbox holds the same physical TPU). Scopes name
        hardware, not sandboxes: that is what lets "the replacement on the
        same chips must re-earn trust" be expressed at all.

        Backends that can name PER-HOST hardware (kubernetes: the node/
        slice a pod landed on) take the sandbox too — fencing then
        quarantines exactly the wedged node's chips instead of the whole
        chip-count lane (the PR 13 carried follow-up). Callers without a
        sandbox in hand (the lane-level recovering gate) get the lane
        default, which such backends treat as the coarse parent scope."""
        scope_fn = getattr(self.backend, "lease_scope", None)
        if scope_fn is not None:
            try:
                scope = scope_fn(chip_count, sandbox=sandbox)
            except TypeError:
                # Older single-arg backends (and wrappers) keep working.
                scope = scope_fn(chip_count)
            if isinstance(scope, str) and scope:
                return scope
        return f"lane-{chip_count}"

    async def _attach_lease(self, sandbox: Sandbox, chip_count: int) -> None:
        """Mint the sandbox's generation token and record it on every host
        executor (POST /lease). Best-effort on the wire: an old binary
        (404) or a transient failure leaves the host without executor-side
        enforcement — the control-plane revocation check still fences it —
        and never fails a spawn."""
        scope = self._lease_scope(chip_count, sandbox)
        lease = self.leases.mint(scope, sandbox.id)
        sandbox.meta["lease"] = lease
        if self.leases.recovering(scope):
            sandbox.meta["device_health"] = "recovering"
        if self._store_shared:
            # Fleet host registry: which replica owns which host, on what
            # scope/generation — the shared-store view a peer (or an
            # operator reading any replica's /statusz) can join against.
            try:
                self.state_store.put(
                    "hosts",
                    sandbox.id,
                    {
                        "replica": self.replica_id,
                        "lane": chip_count,
                        "scope": scope,
                        "generation": lease.generation,
                        "ts": time.time(),
                    },
                )
            except Exception:  # noqa: BLE001
                logger.warning("host registry publish failed", exc_info=True)
        if not self.config.device_fence_enabled:
            return
        # Backends whose sandboxes are not real HTTP hosts (the in-memory
        # test fake) opt out of the wire push: minting stays (the
        # control-plane revocation check needs no wire), and skipping the
        # doomed POSTs keeps the seeded chaos suites' interleaving
        # deterministic — real-socket connect failures would re-deal which
        # request consumes which fault draw between runs.
        if getattr(self.backend, "supports_lease_push", True) is False:
            return
        client = self._http_client()

        async def push(url: str) -> None:
            try:
                await client.post(
                    f"{url}/lease",
                    json={"token": lease.wire_token},
                    timeout=5.0,
                )
            except httpx.HTTPError:
                logger.debug(
                    "lease push to %s failed (control-plane fencing still "
                    "covers it)",
                    url,
                )

        await asyncio.gather(*(push(url) for url in sandbox.host_urls))

    def _check_lease(self, sandbox: Sandbox) -> None:
        """Refuse to dispatch against a revoked lease: the fence landed
        while this request held (or was about to use) the sandbox. A clean
        refusal BEFORE the wire hop — the fenced host's device plane never
        sees the claim, the stateless retry ladder replays on a fresh
        sandbox, and a session gets the standard typed close."""
        lease = sandbox.meta.get("lease")
        if isinstance(lease, Lease) and self.leases.stale(lease):
            # Locally revoked (this replica fenced it), or at-or-below the
            # scope's shared fence floor (a PEER replica fenced the
            # hardware) — either way the claim must never reach the chips.
            raise StaleLeaseError(
                f"sandbox {sandbox.id} lease {lease.wire_token} was fenced "
                f"({lease.revoke_reason or 'fenced'}); the request must "
                "move to a healthy host",
                scope=lease.scope,
            )

    def _wire_headers(self, sandbox: Sandbox) -> dict | None:
        """Headers for a sandbox execute hop: trace propagation plus the
        sandbox's lease token — the executor rejects a token older than
        the one it holds with the typed 409 before taking any lock."""
        headers = self._trace_headers() or {}
        lease = sandbox.meta.get("lease")
        if isinstance(lease, Lease):
            headers["x-lease-token"] = lease.wire_token
        return headers or None

    @staticmethod
    def _raise_if_stale_lease(resp, sandbox: Sandbox) -> None:
        """Map the executor's typed ``409 stale_lease`` refusal to
        StaleLeaseError (409 also means other things on other routes —
        only the typed body counts)."""
        if resp.status_code != 409:
            return
        try:
            body = resp.json()
        except ValueError:
            return
        if isinstance(body, dict) and body.get("error") == "stale_lease":
            raise StaleLeaseError(
                f"sandbox {sandbox.id} rejected a stale lease claim "
                f"(held {body.get('held')!r}, offered {body.get('offered')!r})"
            )

    def _fence_budget_ok(self, lane: int) -> bool:
        """The actuation budget: admit this fence only if the lane has
        fenced fewer than the cap inside the sliding window. The cap is
        what keeps a probe false-positive storm from mass-disposing a
        serving lane — past it, verdicts defer (and re-assert each probe
        cycle) until the window slides."""
        cap = self.config.device_fence_max_per_window
        if cap <= 0:
            return True
        window = max(1.0, self.config.device_fence_window_seconds)
        now = self.scheduler.now()
        times = self._fence_times.setdefault(lane, deque())
        while times and times[0] <= now - window:
            times.popleft()
        if len(times) >= cap:
            return False
        times.append(now)
        return True

    def on_host_wedged(self, sandbox_id: str, *, reason: str = "wedged") -> None:
        """The probe daemon's actuation hook: schedule fence-and-replace
        for a wedged host, off the probe cycle (disposal can block on a
        wedged process's kill). Idempotent per sandbox — the probe
        re-asserts every cycle and this dedupes on the fence mark."""
        if not self.config.device_fence_enabled or self._closed:
            return
        entry = self._live_sandboxes.get(sandbox_id)
        if entry is None or entry[1].meta.get("lease_fenced"):
            return
        task = asyncio.get_running_loop().create_task(
            self._off_request_path(self.fence_host(sandbox_id, reason=reason))
        )
        self._dispose_tasks.add(task)
        task.add_done_callback(self._dispose_tasks.discard)

    async def fence_host(self, sandbox_id: str, *, reason: str = "wedged") -> str:
        """Fence one wedged host and replace it: revoke its lease (stale
        claims die typed), drain it from the lane (pool slot freed, parked
        sessions closed so their clients reconnect to healthy hosts,
        in-flight requests keep the existing fault/serial-fallback
        semantics when the dispose cuts them off), dispose it through the
        standard path, and refill the lane. Returns the outcome (also the
        device_fence_total label): fenced / already_fenced / gone /
        breaker_open / budget_exhausted / disabled."""
        if not self.config.device_fence_enabled:
            return "disabled"
        entry = self._live_sandboxes.get(sandbox_id)
        if entry is None:
            return "gone"
        lane, sandbox = entry
        if sandbox.meta.get("lease_fenced"):
            return "already_fenced"
        if self.breakers.is_open(lane):
            # The lane cannot spawn replacements while its breaker is open:
            # disposing supply now would deepen the outage for zero gain.
            # The verdict stands and re-asserts after the cooldown.
            self.metrics.device_fences.inc(
                lane=str(lane), outcome="breaker_open"
            )
            return "breaker_open"
        if not self._fence_budget_ok(lane):
            self.metrics.device_fences.inc(
                lane=str(lane), outcome="budget_exhausted"
            )
            logger.warning(
                "wedge actuation deferred (lane=%d sandbox=%s): fence "
                "budget exhausted (%d per %.0fs) — probe storm suspected",
                lane,
                sandbox_id,
                self.config.device_fence_max_per_window,
                self.config.device_fence_window_seconds,
            )
            return "budget_exhausted"
        # Commit: mark first (the dedupe + the probe's DRAINING overlay +
        # the turnover guard all key off this), then revoke the lease so
        # every dispatch path refuses the host from this instant.
        sandbox.meta["lease_fenced"] = True
        sandbox.meta["device_health"] = "draining"
        lease = sandbox.meta.get("lease")
        if isinstance(lease, Lease):
            self.leases.fence(lease, reason=reason)
        # Drain: free the pool slot (queued work reroutes via the
        # scheduler's kicks once the replacement lands)...
        pool = self._pools.get(lane)
        if pool is not None:
            try:
                pool.remove(sandbox)
            except ValueError:
                pass
        # ...and get every session parked on this host OFF it NOW, not when
        # the client times out. With the durability plane live, each
        # session is MIGRATED: snapshot-then-restore-elsewhere — awaited
        # INLINE, before the dispose below kills the host — so its next
        # turn restores behind any replica with session_seq continuous and
        # zero client-visible state loss. A migration that cannot complete
        # (snapshot refused, lock held past the budget, durability off)
        # falls back to the pre-durability force-close: the session's next
        # request recreates against a healthy host (session_seq=1 reports
        # the state loss), instead of dispatching into the wedge and
        # hanging out its timeout. Snapshot traffic against the fenced
        # host is fine: the server-side lease token is still the one it
        # holds — only NEW claims at a successor die typed.
        for executor_id, session in list(self._sessions.items()):
            if session.sandbox is not sandbox or session.closed:
                continue
            migrated = False
            if self.session_store.enabled:
                try:
                    migrated = await self._migrate_session(
                        executor_id, session, reason
                    )
                except Exception:  # noqa: BLE001 — fall back to force-close
                    logger.warning(
                        "session %s migration off fenced host %s failed",
                        executor_id,
                        sandbox.id,
                        exc_info=True,
                    )
            if migrated:
                logger.warning(
                    "session %s migrated off fenced host %s (%s): state "
                    "checkpointed, restores on next turn",
                    executor_id,
                    sandbox.id,
                    reason,
                )
                continue
            if session.closed:
                continue
            logger.warning(
                "session %s force-closed: its host %s was fenced (%s)",
                executor_id,
                sandbox.id,
                reason,
            )
            self._end_session_soon(executor_id, session, recycle=False)
        self.metrics.device_fences.inc(lane=str(lane), outcome="fenced")
        self.tracer.record_span(
            "device_fence",
            trace_id=tracing.new_trace_id(),
            parent_id=None,
            start_unix=time.time(),
            duration_s=0.0,
            attributes={
                "lane": lane,
                "sandbox": sandbox.id,
                "reason": reason,
                "scope": lease.scope if isinstance(lease, Lease) else "",
                "generation": (
                    lease.generation if isinstance(lease, Lease) else 0
                ),
            },
            status="error",
        )
        logger.warning(
            "fenced wedged host (lane=%d sandbox=%s reason=%s): lease "
            "revoked, draining and replacing",
            lane,
            sandbox.id,
            reason,
        )
        # Dispose-and-replace: the standard dispose path (idempotent with
        # any in-flight release — backend.delete tolerates repeats), then
        # the standard refill machinery. An in-flight request on this host
        # loses its connection mid-op and surfaces through the existing
        # fault semantics; its own release finds the sandbox unservable
        # and no-ops.
        await self._dispose(sandbox)
        self._notify_lane(lane)
        self.fill_pool_soon(lane)
        return "fenced"

    async def _acquire(
        self,
        chip_count: int,
        *,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        jobs: int = 1,
    ) -> Sandbox:
        """Acquire a sandbox slot — `_acquire_slot` inside a trace span
        carrying the admission attributes; the scheduler's enqueue/grant/
        shed events and the breaker's rejections attach to this span.
        `jobs` > 1 is a batched dispatch's multi-job token: one queue
        position and one sandbox serving N coalesced requests."""
        with self.tracer.span(
            "scheduler.queue_wait",
            attributes={
                "lane": chip_count,
                "tenant": tenant or self.scheduler.default_tenant,
                "priority": priority or "interactive",
                "jobs": jobs,
            },
        ):
            return await self._acquire_slot(
                chip_count,
                tenant=tenant,
                priority=priority,
                deadline=deadline,
                jobs=jobs,
            )

    async def _acquire_slot(
        self,
        chip_count: int,
        *,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        jobs: int = 1,
    ) -> Sandbox:
        """Acquire a sandbox slot through the scheduler.

        A thin client of the scheduler's grant tokens: submit() runs
        admission control (per-tenant depth bound, deadline feasibility) and
        queues a ticket; each explicit grant wakes exactly one waiter — in
        weighted-fair, priority-aware order — which then runs the same
        pool-pop / spawn-vs-wait / breaker-fail-fast logic as before. The
        old 30s safety-net poll is gone: every turnover issues a grant, and
        a turnover landing mid-evaluation is remembered by the scheduler
        (pending kicks), so a wake-up cannot be lost."""
        pool = self._pool(chip_count)
        # Demand signal for the autoscaler BEFORE admission: the arriving
        # acquisition updates the lane's arrival-rate EWMA and applies any
        # scale-up immediately, so the refill this very request triggers
        # (fill_pool_soon below) already sees the raised target —
        # spawn-ahead for the rest of the burst behind it.
        self.autoscaler.observe_arrival(
            chip_count, self._lane_snapshot(chip_count), jobs=jobs
        )
        now = self.scheduler.now()
        # After this long without a sandbox, spawn regardless of what is
        # "due back" — a long-running in-flight execute must not block a
        # waiter on an unconstrained lane indefinitely.
        grace_deadline = now + 10.0
        # On a constrained lane no amount of waiting helps while active
        # sessions hold every slot — bound the wait and surface a
        # retryable error instead of an open-ended hang.
        acquire_deadline = (
            now + self.config.executor_acquire_timeout
            if self.config.executor_acquire_timeout > 0
            else None
        )
        # Admission control happens HERE, at arrival: depth-bound sheds and
        # infeasible deadlines raise retryable errors carrying a computed
        # Retry-After instead of burning the acquire budget first.
        ticket = self.scheduler.submit(
            chip_count,
            tenant=tenant,
            priority=priority,
            deadline=deadline,
            # Warm supply for the admission estimate: wedged pooled hosts
            # can't serve a granted pop usefully, so they don't count.
            pool_ready=self._pool_supply(chip_count),
            jobs=jobs,
            # Trusted (pre-warm) acquisitions queue like anyone but bill
            # nobody — internal warmup wait is not a tenant's queue wait.
            metered=not _trusted_source_var.get(),
        )
        sandbox: Sandbox | None = None
        try:
            while True:
                capacity = self._lane_capacity(chip_count)
                # Unconstrained lanes re-wake at the grace deadline even
                # without a grant: a spawn CREATES capacity rather than
                # consuming queued supply, so it needn't wait its fair turn.
                deadline_at = ticket.deadline_at if ticket is not None else None
                candidates = [
                    t for t in (acquire_deadline, deadline_at) if t is not None
                ]
                if capacity is None and now < grace_deadline:
                    candidates.append(grace_deadline)
                timeout_at = min(candidates) if candidates else None
                granted = await self.scheduler.wait_grant(
                    ticket, timeout_at=timeout_at
                )
                now = self.scheduler.now()
                woke_at = self.tracer.clock()
                spawning = self._spawning.get(chip_count, 0)
                in_use = self._in_use.get(chip_count, 0)
                session_held = self._session_held_constrained()
                if not granted and deadline_at is not None and now >= deadline_at:
                    # Admission let the request in on an estimate; reality
                    # disagreed. The declared start deadline has passed, so
                    # keeping the ticket queued can only waste the client's
                    # time — reject NOW with the same retryable signal as an
                    # arrival-time rejection.
                    raise DeadlineInfeasibleError(
                        f"deadline ({deadline:.1f}s) expired while queued "
                        f"for a lane-{chip_count} sandbox slot",
                        lane=chip_count,
                        tenant=ticket.tenant,
                        retry_after=self.scheduler.estimated_wait(
                            chip_count, pool_ready=len(pool)
                        ),
                    )
                if (
                    not granted
                    and acquire_deadline is not None
                    and now >= acquire_deadline
                ):
                    raise CapacityTimeoutError(
                        f"no lane-{chip_count} sandbox slot freed within "
                        f"{self.config.executor_acquire_timeout:.0f}s "
                        f"(in_use={in_use}, session_held={session_held}, "
                        f"capacity={capacity}); retry later"
                    )
                if granted and pool:
                    sandbox = self._pop_pool_sandbox(pool)
                    if sandbox is not None:
                        # What the holder did between this sandbox's last
                        # turn and this one: the turnover that put it back
                        # (0 for a pre-warmed spawn), then idle in the pool.
                        sandbox.meta["acquired"] = {
                            "turnover_before": float(
                                sandbox.meta.get("turnover_s", 0.0)
                            ),
                            "pool_idle_before": max(
                                0.0,
                                now - float(sandbox.meta.get("pooled_at", now)),
                            ),
                        }
                        self._record_timed(
                            "pool.acquire", woke_at, attributes={"source": "pool"}
                        )
                        break
                    # Pool holds only recovering/draining quarantined hosts:
                    # nothing servable to pop — fall through to the
                    # spawn-vs-wait logic (which counts those hosts as
                    # standby on constrained lanes, so the waiter parks
                    # until re-admission kicks it rather than fighting the
                    # quarantined host for its chips).
                if (
                    self.breakers.is_open(chip_count)
                    and spawning == 0
                    and in_use == 0
                ):
                    # Pool empty, nothing in flight or due back, and the
                    # lane's backend is known-down: waiting out the acquire
                    # budget (up to 300s) cannot help — fail fast with the
                    # retryable circuit error instead.
                    self.breakers.lane(chip_count).check(chip_count)
                if capacity is not None:
                    # Constrained lane: a competing spawn would lose the
                    # physical-slot race to an in-flight refill or an
                    # about-to-recycle request — spawn only under capacity.
                    # Session-held sandboxes count ACROSS constrained lanes
                    # (shared physical substrate, as in the eviction logic):
                    # they own their chips until the session closes (the
                    # idle sweep bounds this). Recovering standby hosts
                    # count too: they hold their chips through the
                    # quarantine, and the re-admission settle kicks every
                    # lane the moment they can serve.
                    can_spawn = (
                        spawning
                        + in_use
                        + session_held
                        + self._pool_standby(chip_count)
                        < capacity
                    )
                else:
                    # Unconstrained lane: sandboxes "due back" are in-flight
                    # refills plus (with reuse on) in-use sandboxes that will
                    # recycle into the pool at release. Wait when supply
                    # covers the queue — a recycle lands in milliseconds, a
                    # fresh spawn takes seconds — but spawn when demand
                    # exceeds it (burst) or the grace deadline passes.
                    due_back = spawning + (
                        in_use if self.config.executor_reuse_sandboxes else 0
                    )
                    can_spawn = (
                        due_back == 0
                        or self.scheduler.queued(chip_count) > due_back
                        # >= to match wait_grant's timeout comparison: a
                        # waiter woken exactly at the grace boundary must
                        # spawn, not fall through to the acquire deadline.
                        or now >= grace_deadline
                    )
                if (
                    can_spawn
                    and self.leases.recovering(self._lease_scope(chip_count))
                    and (self._pool_standby(chip_count) > 0 or spawning > 0)
                ):
                    # The lane's lease scope is mid-quarantine (a fence's
                    # replacement is earning its clean-probe streak) and a
                    # standby replacement already exists or is on its way:
                    # a direct spawn would land on the SAME recovering
                    # hardware and hand it straight to this request —
                    # exactly the early-handout _pop_pool_sandbox refuses
                    # for pooled hosts. Constrained lanes were already
                    # covered by the standby capacity count; unconstrained
                    # lanes (where nothing counted standby) slipped
                    # through. Park in fair order instead — the
                    # re-admission settle kicks every lane the moment the
                    # standby can serve. (With NO standby anywhere, the
                    # spawn below still runs: its recovering-marked result
                    # is parked as the scope's probe target, never handed
                    # out — see the post-spawn check.)
                    can_spawn = False
                if can_spawn:
                    # Count the direct spawn in _spawning: a concurrent
                    # waiter evaluating the guards mid-spawn must see it, or
                    # two waiters would race past a capacity-1 check and the
                    # loser would starve on the backend's physical slot.
                    self._spawning[chip_count] = (
                        self._spawning.get(chip_count, 0) + 1
                    )
                    # Publish the claim BEFORE the spawn starts (peers'
                    # capacity subtraction sees it at the earliest
                    # possible instant, not after the grant settles).
                    self._publish_occupancy(chip_count)
                    # Leave the queue BEFORE spawning: this waiter now owns
                    # its own supply, so the grant passes to the next waiter,
                    # which re-evaluates against the bumped spawn count.
                    self.scheduler.complete(ticket)
                    ticket = None
                    try:
                        sandbox = await self._spawn_with_retry(chip_count)
                    finally:
                        self._spawning[chip_count] -= 1
                        self._notify_lane(chip_count)
                    if sandbox.meta.get("device_health") in (
                        "recovering",
                        "draining",
                    ):
                        # The spawn landed on a quarantined lease scope
                        # (the fence raced this spawn, or this spawn IS
                        # the fenced scope's first replacement): the
                        # sandbox must serve NOTHING until the clean-probe
                        # streak re-admits it. Park it as the scope's
                        # standby/probe target and rejoin the queue — the
                        # standby gate above stops the next loop from
                        # spawning again behind it.
                        sandbox.meta["pooled_at"] = self.scheduler.now()
                        pool.append(sandbox)
                        sandbox = None
                        self._notify_lane(chip_count)
                        ticket = self.scheduler.submit(
                            chip_count,
                            tenant=tenant,
                            priority=priority,
                            deadline=deadline,
                            pool_ready=self._pool_supply(chip_count),
                            jobs=jobs,
                            metered=not _trusted_source_var.get(),
                        )
                        continue
                    sandbox.meta.pop("acquired", None)
                    self._record_timed(
                        "pool.acquire", woke_at, attributes={"source": "spawn"}
                    )
                    break
                if granted:
                    # Nothing to pop and must not spawn: back to sleep in
                    # fair position (or straight back to evaluation, if a
                    # turnover landed while this holder was deciding).
                    self.scheduler.rearm(ticket)
        except BaseException:
            if ticket is not None:
                self.scheduler.abandon(ticket)
            raise
        if ticket is not None:
            self.scheduler.complete(ticket)
        self._in_use[chip_count] = self._in_use.get(chip_count, 0) + 1
        self._publish_occupancy(chip_count)
        self.fill_pool_soon(chip_count)
        return sandbox

    def _pop_pool_sandbox(self, pool: deque) -> Sandbox | None:
        """Pop the next pooled sandbox for the current request, skipping
        hosts the device-health probe marked WEDGED while anything
        healthier is available (handing a fresh request to a wedged device
        buys a full acquire-budget hang). RECOVERING/DRAINING hosts are
        never popped at all — a fenced scope's replacement must finish its
        clean-probe streak before it serves, and that gate is only real if
        no "last resort" hands it out early; when the pool holds nothing
        else the method returns None and the caller falls through to its
        spawn-vs-wait logic (bounded: the re-admission settle kicks every
        lane). Trusted (pre-warm) requests additionally prefer an
        UNTAINTED sandbox: a recycled sandbox that ever ran tenant code is
        harvest-ineligible for life — running the trusted kernels there
        compiles fine but admits nothing. Wedged-as-last-resort is kept
        for kill-switch parity (with actuation off, a lane whose only
        pooled hosts are wedged zombies must still hand something out
        rather than livelock a constrained lane, the PR 8 behavior)."""
        if self._store_shared:
            # Shared-fence gate: a pooled host whose lease sits at-or-below
            # its scope's published fence floor was fenced by a PEER
            # replica — it must never be granted here ("a host fenced by A
            # is never granted by B"). Drain it through the standard
            # dispose path (lease-fenced turnover) so the lane refills with
            # a fresh-generation host instead of carrying a zombie slot.
            for candidate in [
                c
                for c in pool
                if not c.meta.get("lease_fenced")
                and isinstance(c.meta.get("lease"), Lease)
                and self.leases.stale(c.meta["lease"])
            ]:
                try:
                    pool.remove(candidate)
                except ValueError:
                    continue
                candidate.meta["lease_fenced"] = True
                candidate.meta["device_health"] = "draining"
                logger.warning(
                    "pooled host %s drained: its lease scope was fenced by "
                    "a peer replica",
                    candidate.id,
                )
                task = asyncio.get_running_loop().create_task(
                    self._off_request_path(self._dispose(candidate))
                )
                self._dispose_tasks.add(task)
                task.add_done_callback(self._dispose_tasks.discard)
        prefer_untainted = self.compile_cache.enabled and _trusted_source_var.get()
        fallback: int | None = None
        wedged_fallback: int | None = None
        for i, candidate in enumerate(pool):
            health = candidate.meta.get("device_health")
            if health in ("recovering", "draining"):
                continue
            if health == "wedged":
                if wedged_fallback is None:
                    wedged_fallback = i
                continue
            if prefer_untainted and self._cache_sync(candidate).tainted:
                if fallback is None:
                    fallback = i
                continue
            del pool[i]
            return candidate
        for index in (fallback, wedged_fallback):
            if index is not None:
                candidate = pool[index]
                del pool[index]
                return candidate
        return None

    # --------------------------------------------------------------- execute

    async def execute(
        self,
        source_code: str | None = None,
        *,
        source_file: str | None = None,
        files: dict[str, str] | None = None,
        timeout: float | None = None,
        env: dict[str, str] | None = None,
        chip_count: int | None = None,
        profile: bool = False,
        executor_id: str | None = None,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        limits: dict | None = None,
        pure: bool = False,
    ) -> Result:
        """Run user code in a sandbox; returns output + changed files.

        `pure=True` is the client's purity declaration — this run reads no
        network, no randomness, no wall clock: its output is a function of
        its inputs. Declared-pure runs ride the result memo
        (services/result_memo.py): an identical earlier run serves from its
        record at admission with zero sandbox HTTP and zero chip-seconds
        billed; a miss executes normally and records for the next caller.
        The declaration is a promise, not a sandbox restriction — a false
        one only risks the declarer's own (tenant-scoped) repeat results.

        Exactly one of `source_code` (inline) / `source_file` (an absolute
        workspace path that must appear in `files`) is required. With
        ``profile=True`` the sandbox captures a JAX profiler trace of the run
        and ships it back as ``/workspace/profile.zip``.

        `tenant` / `priority` / `deadline` are admission-control inputs for
        the fair-share scheduler: tenant defaults to the shared tenant,
        priority is `interactive` (default) or `batch`, and deadline is
        "this request must START within N seconds" — infeasible deadlines
        are rejected at arrival with a retryable error.

        `limits` is this request's resource-budget override (keys from
        services.limits.LIMIT_KEYS); it layers over the configured default
        and lane budgets and is min-clamped by the server caps — a request
        can only tighten its box. Breaches surface as LimitExceededError
        with the typed violation kind, never retried.

        Without `executor_id` each request gets a pristine sandbox. With it,
        requests sharing the id run in ONE live sandbox whose workspace (and
        warm process) persists across them — session affinity (the upstream
        bee-code-interpreter's persistent-executor semantics; the reference
        fork carried the field but its single-use pods ignored it). Session
        requests are never retried on infrastructure failure: a retry would
        land on a fresh sandbox and silently drop the session's state.
        """
        edge = self._edge_begin()
        env, executor_id = self._normalize_request(env, profile, executor_id)
        usage_tenant = self._usage_tenant(tenant)
        self._check_admission_open()
        # Quota enforcement sits HERE — before the scheduler, the batcher,
        # or any session machinery sees the request. A denied (or
        # quarantined) request is never enqueued and consumes zero
        # sandboxes; the typed QuotaExceededError maps to HTTP 429 /
        # gRPC RESOURCE_EXHAUSTED with Retry-After + x-quota-* metadata.
        # The declared cost rides along for the predicted-overrun check.
        quota = self._quota_admit(
            usage_tenant, chip_count=chip_count, timeout=timeout
        )
        self._edge_mark(edge, "edge.memo_lookup")
        # Result-memo admission check: AFTER the quota gate (hits are still
        # request-rate-governed — free answers are not unmetered answers)
        # and BEFORE the auto-profile arm below (a served-from-record
        # request must not eat the lane's one profiling arm).
        memo_key, memo_state = self._memo_admission(
            pure,
            executor_id=executor_id,
            profile=profile,
            source_code=source_code,
            source_file=source_file,
            files=files,
            env=env,
            chip_count=chip_count,
            tenant=tenant,
            limits=limits,
        )
        if memo_state == "lookup":
            record = await self.result_memo.lookup(memo_key)
            if record is not None:
                try:
                    result = self._memo_hit_result(record)
                    self._apply_quota_phases(result, quota)
                    self._count_memo_hit(result, usage_tenant)
                    return result
                finally:
                    self.quotas.release(quota)
            memo_state = "miss"
        self._edge_mark(edge, "edge.resolve")
        # Auto-triggered profiling: a pending arm on this request's lane
        # (set by the drift detector or a p99 outlier) is consumed here,
        # AFTER admission — a denied request must not eat the arm. The
        # profiler env rides this request, and the contextvar marks it so
        # the pipeline harvests (and zero-bills) the artifact.
        env, auto_profile = self._maybe_auto_profile(env, chip_count, tenant)
        profile_token = _auto_profile_var.set(auto_profile)
        # The purity declaration rides the request's task tree only while a
        # record could come of it (a miss): _run_on_sandbox forwards it to
        # the executor for the hashed echo.
        pure_token = _pure_run_var.set(memo_state == "miss")
        edge_token = _edge_var.set(edge)
        self._inflight += 1
        try:
            if executor_id is not None:
                result = await self._execute_in_session(
                    executor_id,
                    source_code,
                    source_file=source_file,
                    files=files,
                    timeout=timeout,
                    env=env,
                    chip_count=chip_count,
                    tenant=tenant,
                    priority=priority,
                    deadline=deadline,
                    limits=limits,
                )
            elif self._batch_eligible(source_code, files, env, deadline):
                result = await self._execute_batched(
                    source_code,
                    timeout=timeout,
                    env=env,
                    chip_count=chip_count,
                    tenant=tenant,
                    priority=priority,
                    limits=limits,
                )
            else:
                result = await self._execute_with_retry(
                    source_code,
                    source_file=source_file,
                    files=files,
                    timeout=timeout,
                    env=env,
                    chip_count=chip_count,
                    tenant=tenant,
                    priority=priority,
                    deadline=deadline,
                    limits=limits,
                )
        except CircuitOpenError as e:
            self.metrics.breaker_rejections.inc(chip_count=str(e.lane))
            self.metrics.executions.inc(outcome="rejected")
            self._usage_request(usage_tenant, "rejected")
            raise
        except LimitExceededError as e:
            self._count_violation(e)
            # The violating request is billed (its device time landed via
            # the attempt's draft) AND counted under its violation kind —
            # the abuse-control feed services/quotas.py reads: enough of
            # these inside one window and the tenant's NEXT request is
            # quarantined at the door instead of burning a sandbox here.
            self._usage_request(
                usage_tenant, "limit_violation", violation=e.kind
            )
            raise
        except SessionLimitError:
            # Capacity-cap rejections must be visible on dashboards — a
            # burst of 429s with no counter movement reads as "healthy idle".
            self.metrics.executions.inc(outcome="rejected")
            self._usage_request(usage_tenant, "rejected")
            raise
        except (ExecutorError, SandboxSpawnError):
            self.metrics.executions.inc(outcome="infra_error")
            self._usage_request(usage_tenant, "infra_error")
            raise
        finally:
            self._inflight -= 1
            self.quotas.release(quota)
            _auto_profile_var.reset(profile_token)
            _pure_run_var.reset(pure_token)
            _edge_var.reset(edge_token)
        self._edge_mark(edge, "edge.memo_record")
        await self._memo_finish(memo_key, memo_state, result, auto_profile)
        self._apply_quota_phases(result, quota)
        self._edge_mark(edge, "edge.observe")
        self._count_execution(
            result,
            session=executor_id is not None,
            usage_tenant=usage_tenant,
            lane=self._lane_hint(chip_count),
            tenant=tenant,
        )
        result.edge = edge
        self.close_edge(result, final=False)
        return result

    # ------------------------------------------------------- request edges

    def _edge_begin(self) -> dict:
        """Open the request's edge marks (see `_edge_var`). The origin is
        the root span's start where this task has one (the HTTP middleware's:
        body parse, validation and session routing then fall into
        `edge.parse`), else now."""
        now = self.tracer.clock()
        t0 = getattr(tracing.current_span(), "_start_mono", None)
        if t0 is None:
            t0 = now
        return {
            "t0": t0,
            "before": [("edge.parse", t0), ("edge.quota", now)],
            "queued_at": None,
            "after": [],
            "download_at": None,
        }

    def _edge_mark(self, edge: dict | None, name: str) -> None:
        """`name` begins now; the mark before it on that side ends."""
        if edge is None:
            return
        side = "before" if edge["queued_at"] is None else "after"
        edge[side].append((name, self.tracer.clock()))

    def _edge_queued(self) -> None:
        """The request enters the queue: `edge.before_queue` ends, and is
        recorded with its marks as children. A retry's second entry is not
        an edge any more."""
        edge = _edge_var.get()
        if edge is None or edge["queued_at"] is not None:
            return
        edge["queued_at"] = now = self.tracer.clock()
        self._record_marks("edge.before_queue", edge["t0"], now, edge["before"])

    def _edge_downloaded(self) -> None:
        """The download ended (this attempt's): what follows is the edge."""
        edge = _edge_var.get()
        if edge is not None and edge["queued_at"] is not None:
            edge["download_at"] = now = self.tracer.clock()
            edge["after"] = [("edge.result", now)]

    def close_edge(self, result: Result, *, final: bool = True) -> None:
        """Stamp `edge_before` / `edge_after` into the result's phases. The
        API surface calls this at the last point before it serialises the
        body (`final`): `edge.after_download` then ends and is recorded with
        its marks as children. execute() itself stamps a first reading, for
        callers that never serialise."""
        edge = result.edge
        if edge is None or edge["queued_at"] is None:
            return
        now = self.tracer.clock()
        result.phases["edge_before"] = round(edge["queued_at"] - edge["t0"], 6)
        if edge["download_at"] is None:
            return
        result.phases["edge_after"] = round(now - edge["download_at"], 6)
        if final:
            result.edge = None
            self._record_marks(
                "edge.after_download", edge["download_at"], now, edge["after"]
            )

    def _record_marks(
        self, name: str, started: float, ended: float, marks: list
    ) -> None:
        """Export `name` as a child of the current span and each mark as a
        child of it, every mark ending where the next begins."""
        parent = tracing.current_span()
        if parent is None or not parent.recording:
            return
        span_id = self._record_timed(name, started, ended)
        for (mark, begun), (_next, until) in zip(
            marks, marks[1:] + [("", ended)]
        ):
            self._record_timed(mark, begun, until, parent_id=span_id)

    def _record_timed(
        self,
        name: str,
        started: float,
        ended: float | None = None,
        *,
        parent_id: str | None = None,
        attributes: dict | None = None,
    ) -> str | None:
        """Export a span for work already timed on the tracer's clock
        (`started` to `ended`, default now) in the current span's trace, as
        a child of `parent_id` (default: the current span). Returns its id;
        None where nothing records."""
        current = tracing.current_span()
        if current is None or not current.recording:
            return None
        ended = self.tracer.clock() if ended is None else ended
        return self.tracer.record_span(
            name,
            trace_id=current.trace_id,
            parent_id=parent_id or current.span_id,
            # anchored to the current span's own start, on the one clock
            start_unix=current.start_unix + (started - current._start_mono),
            duration_s=ended - started,
            attributes=attributes,
        )

    # ------------------------------------------------------ result memoization

    def _memo_admission(
        self,
        pure: bool,
        *,
        executor_id: str | None,
        profile: bool,
        source_code: str | None,
        source_file: str | None,
        files: dict[str, str] | None,
        env: dict[str, str] | None,
        chip_count: int | None,
        tenant: str | None,
        limits: dict | None,
    ) -> tuple:
        """Classify one request for the memo check. Returns (key, state):
        state None = memo not in play (purity undeclared, or the kill
        switch — no phases keys, no header, no IO, byte-for-byte pre-memo);
        "bypass" = declared pure but ineligible; "lookup" = eligible.

        Sessions bypass (their whole point is state accumulating across
        requests — the workspace is an input the key can't see); profiler
        runs bypass (the artifact is a side effect keyed outside the
        inputs). Key-derivation failures bypass too: the request's own
        validation owns malformed inputs, never a memo error."""
        if not pure or not self.result_memo.enabled:
            return None, None
        if executor_id is not None or profile or (
            env and "APP_JAX_PROFILE" in env
        ):
            return None, "bypass"
        try:
            lane = self._lane_hint(chip_count)
            # The EFFECTIVE limit box (defaults -> lane -> clamped request
            # override), not the raw override: two requests whose limits
            # resolve identically share output-determining state.
            limits_payload = request_limits(self.config, lane, limits)
            scope = (
                SHARED_SCOPE
                if self.result_memo.shared and _trusted_source_var.get()
                else self.scheduler.normalize_tenant(tenant)
            )
            key = derive_key(
                scope=scope,
                source_code=source_code,
                source_file=source_file,
                files=files,
                env=env,
                limits=limits_payload,
                lane=lane,
                binary_key=self._memo_binary_key,
            )
        except (ValueError, TypeError):
            return None, "bypass"
        return key, "lookup"

    def _memo_hit_result(self, record: dict) -> Result:
        """Build this request's Result from a memo record. The request's
        OWN attribution is zero (no device ran for it); what the recorded
        run measured rides inside the memo block for clients comparing
        cached-vs-live cost."""
        phases: dict[str, float | str] = {
            "chip_seconds": 0.0,
            "device_op_seconds": 0.0,
        }
        trace_id = tracing.current_trace_id()
        if trace_id is not None:
            phases["trace_id"] = trace_id
        memo_block: dict = {"state": "hit"}
        recorded_phases = record.get("phases")
        if isinstance(recorded_phases, dict):
            memo_block["recorded"] = recorded_phases
        phases["memo"] = memo_block
        files = record.get("files")
        return Result(
            stdout=str(record.get("stdout", "")),
            stderr=str(record.get("stderr", "")),
            exit_code=int(record.get("exit_code", 0)),
            files=(
                {str(k): str(v) for k, v in files.items()}
                if isinstance(files, dict)
                else {}
            ),
            phases=phases,
            warm=bool(record.get("warm", True)),
            stdout_truncated=bool(record.get("stdout_truncated", False)),
            stderr_truncated=bool(record.get("stderr_truncated", False)),
        )

    def _count_memo_hit(self, result: Result, usage_tenant: str | None) -> None:
        """A memo hit is a LOGICAL request on every surface that counts
        requests — and on none that counts device time: zero chip-seconds
        on the ledger, no perf-baseline sample (nothing was measured; a
        flood of 0-latency hits would poison the drift bands live traffic
        is judged against), no latency-histogram phases."""
        self.result_memo.hits += 1
        self.metrics.result_memo_requests.inc(outcome="hit")
        outcome = "ok" if result.exit_code == 0 else "user_error"
        self.metrics.executions.inc(outcome=outcome)
        self._usage_request(usage_tenant, outcome)

    async def _memo_finish(
        self,
        memo_key,
        memo_state: str | None,
        result: Result,
        auto_profile: str | None,
    ) -> None:
        """Post-run half of the memo protocol: record an eligible miss and
        stamp the request's phases block. Never on the failure path —
        violations and infra faults raised past this point, and a record
        error degrades to an un-memoized success."""
        if memo_state is None:
            return
        recorded = None
        if memo_state == "miss":
            self.result_memo.misses += 1
            if auto_profile is not None:
                # The run grew a control-plane profiler env mid-flight: its
                # key no longer describes what executed.
                recorded = "skipped_profile"
            else:
                recorded = await self._memo_record(memo_key, result)
        block: dict = {"state": memo_state}
        if recorded is not None:
            block["recorded"] = recorded
        result.phases["memo"] = block
        self.metrics.result_memo_requests.inc(outcome=memo_state)

    async def _memo_record(self, memo_key, result: Result) -> str:
        """Admit one completed declared-pure run, when it proved eligible:
        every host echoed the purity declaration and the executor's result
        hash re-derived from the wire fields (result.pure_echo), with
        nothing truncated (a truncation boundary is a limit artifact, not
        program output). Returns the admit outcome string."""
        if memo_key is None:
            return "skipped"
        if result.pure_echo is None:
            return "skipped_echo"
        if result.stdout_truncated or result.stderr_truncated:
            return "skipped_truncated"
        recorded_phases = {
            k: round(float(v), 6)
            for k, v in result.phases.items()
            if isinstance(v, (int, float))
        }
        record = {
            "stdout": result.stdout,
            "stderr": result.stderr,
            "exit_code": result.exit_code,
            "files": dict(result.files),
            "stdout_truncated": result.stdout_truncated,
            "stderr_truncated": result.stderr_truncated,
            "warm": result.warm,
            "phases": recorded_phases,
            # First-write-wins compares THIS: the canonical hash over the
            # merged result (file values are content-addressed object ids,
            # so file bytes are covered transitively).
            "result_sha": result_content_sha(
                result.stdout,
                result.stderr,
                result.exit_code,
                sorted(result.files.values()),
            ),
        }
        try:
            return await self.result_memo.record(memo_key, record)
        except Exception:  # noqa: BLE001 — recording never fails the request
            logger.warning("result memo record failed", exc_info=True)
            return "error"

    @staticmethod
    def _verified_pure_echo(bodies: list) -> str | None:
        """End-to-end check of the executor's purity echo: every host
        acknowledged the declaration, and the primary host's result hash
        re-derives from the very wire fields the Result is built from.
        None — record nothing — on any disagreement, including old
        binaries that don't echo and manifests without content hashes."""
        if not bodies or not all(body.get("pure") is True for body in bodies):
            return None
        primary = bodies[0]
        wire_sha = primary.get("result_sha256")
        if not isinstance(wire_sha, str):
            return None
        entries, has_hashes = parse_files_field(primary.get("files", []))
        if entries and not has_hashes:
            return None
        expected = result_content_sha(
            str(primary.get("stdout", "")),
            str(primary.get("stderr", "")),
            int(primary.get("exit_code", -1)),
            [sha for _rel, sha in entries],
        )
        return wire_sha if wire_sha == expected else None

    def _lane_hint(self, chip_count: int | None) -> int:
        """The lane a request resolves to before validation (the perf
        observer's series key and the auto-profile arm lookup)."""
        if chip_count is None:
            return self.config.default_chip_count
        try:
            return int(chip_count)
        except (TypeError, ValueError):
            return self.config.default_chip_count

    def _maybe_auto_profile(
        self,
        env: dict[str, str] | None,
        chip_count: int | None,
        tenant: str | None,
    ) -> tuple[dict[str, str] | None, str | None]:
        """Consume a pending auto-profile arm for this request's lane, if
        its tenant consents: returns (env with APP_JAX_PROFILE, trigger
        reason) or (env unchanged, None). Client-requested profiling
        (profile=True / explicit env) always wins — that run is the tenant
        profiling itself and bills normally; trusted control-plane runs
        are never auto-profiled (their latencies aren't even recorded)."""
        if not self.perf.enabled or _trusted_source_var.get():
            return env, None
        if env and "APP_JAX_PROFILE" in env:
            return env, None
        try:
            label = self.scheduler.normalize_tenant(tenant)
        except ValueError:
            return env, None  # the request's own validation owns this
        reason = self.perf.take_profile_arm(self._lane_hint(chip_count), label)
        if reason is None:
            return env, None
        return {**(env or {}), "APP_JAX_PROFILE": "1"}, reason

    def _quota_admit(
        self,
        usage_tenant: str | None,
        *,
        chip_count: int | None = None,
        timeout: float | None = None,
    ) -> QuotaVerdict | None:
        """Run the quota gate and keep the rejection observable: a quota
        denial is a rejected request on the dashboards and in the tenant's
        ledger row (requests-by-outcome), exactly like a scheduler shed —
        but it never touches the scheduler. The request's DECLARED cost
        (chip_count x clamped timeout) rides along so the gate can deny a
        predicted overrun before the burn (typed reason=predicted_overrun),
        not after it."""
        try:
            return self.quotas.admit(
                usage_tenant,
                predicted_chip_seconds=self._predicted_chip_seconds(
                    chip_count, timeout
                ),
            )
        except QuotaExceededError:
            self.metrics.executions.inc(outcome="rejected")
            self._usage_request(usage_tenant, "rejected")
            raise

    def _predicted_chip_seconds(
        self, chip_count: int | None, timeout: float | None
    ) -> float:
        """The request's worst-case bill AS DECLARED: chips x the clamped
        timeout the CLIENT declared. A request that declares no timeout
        predicts 0 — the server-side default (60s) is not something the
        client said, and gating on it would permanently deny every tenant
        whose window budget is under chips x 60 regardless of what its
        runs actually cost (those tenants keep the deny-after-the-burn
        semantics). Clamps mirror _validate_request; malformed inputs
        predict 0 (their own validation error owns them, not a quota
        denial)."""
        if timeout is None:
            return 0.0
        try:
            lane = (
                self.config.default_chip_count
                if chip_count is None
                else int(chip_count)
            )
            clamped = min(float(timeout), self.config.max_execution_timeout)
        except (TypeError, ValueError):
            return 0.0
        if clamped <= 0:
            return 0.0
        return max(1, lane) * clamped

    def _apply_quota_phases(
        self, result: Result, quota: QuotaVerdict | None
    ) -> None:
        """Success-path quota exposure (the pacing satellite): a `quota`
        block in Result.phases with the POST-run remaining budget, so a
        well-behaved agent can slow down before ever seeing a 429. Only
        for tenants with a chip-second budget; absent otherwise (and with
        the kill switch, byte-for-byte)."""
        if quota is None:
            return
        # Refresh to the POST-run remaining (this run's bill is already in
        # the ledger), then let the verdict render its one canonical shape.
        self.quotas.refresh_verdict(quota)
        block = quota.phases_block()
        if block is not None:
            result.phases["quota"] = block

    def _usage_tenant(self, tenant: str | None) -> str | None:
        """The normalized tenant name usage accounting records under, or
        None with the metering kill switch on (every `_usage_request` /
        `draft` call then no-ops — pre-metering behavior byte-for-byte).
        Also None for control-plane-authored (trusted) runs: the
        compile-cache pre-warm's JIT compiles are internal warmup work,
        and billing them to the default tenant would contaminate the row
        that bills genuine header-less client requests."""
        if not self.usage.enabled or _trusted_source_var.get():
            return None
        return self.scheduler.normalize_tenant(tenant)

    def _usage_draft(self, tenant: str | None) -> UsageDraft | None:
        """A per-attempt consumption accumulator, or None when this run
        is unmetered (kill switch, or trusted control-plane source)."""
        usage_tenant = self._usage_tenant(tenant)
        if usage_tenant is None:
            return None
        return self.usage.draft(usage_tenant)

    def _usage_request(
        self,
        usage_tenant: str | None,
        outcome: str,
        *,
        violation: str | None = None,
    ) -> None:
        """Count one LOGICAL request against its tenant (resource usage is
        billed per attempt by the drafts; the request itself counts exactly
        once, here at the API surface)."""
        if usage_tenant is None:
            return
        self.usage.add(
            usage_tenant, requests=1, outcome=outcome, violation=violation
        )

    def _count_violation(self, e: LimitExceededError) -> None:
        """Violation bookkeeping shared by both execute surfaces: the
        lane×kind counter, the outcome counter, and — when the violation
        killed the runner (not an in-process guard) — the repeat-offender
        strike on the lane breaker. Enough CONSECUTIVE killed-runner
        violations trip the lane open for one cooldown, so a fleet being
        hammered by violating tenants sheds fast instead of churning
        through kill/respawn cycles at full request rate."""
        self.metrics.limit_violations.inc(
            chip_count=str(e.lane), kind=e.kind
        )
        self.metrics.executions.inc(outcome="limit_violation")
        if not e.continuable:
            breaker = self.breakers.lane(e.lane)
            breaker.record_failure()
            strikes = self._violation_strikes.get(e.lane, 0) + 1
            self._violation_strikes[e.lane] = strikes
            if strikes >= self.config.breaker_failure_threshold:
                breaker.trip(
                    f"{strikes} consecutive limit violations "
                    f"(last: {e.kind})"
                )

    async def _execute_with_retry(
        self,
        source_code: str | None = None,
        *,
        source_file: str | None = None,
        files: dict[str, str] | None = None,
        timeout: float | None = None,
        env: dict[str, str] | None = None,
        chip_count: int | None = None,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        limits: dict | None = None,
    ) -> Result:
        """Stateless execute with bounded infra retries (ExecutorError only:
        user-code failures are results, capacity/breaker rejections are not
        infrastructure flakes, and limit violations are DETERMINISTIC — the
        same snippet breaches the same budget on any sandbox, so replaying
        one would burn a fresh host per attempt — none of those retry)."""

        def on_retry(failures: int, error: BaseException, delay: float) -> None:
            self.metrics.retry_attempts.inc(operation="execute")
            tracing.add_event(
                "retry",
                operation="execute",
                attempt=failures,
                delay_s=round(delay, 3),
                error=str(error)[:200],
            )

        return await retry_async(
            lambda: self._execute_once(
                source_code,
                source_file=source_file,
                files=files,
                timeout=timeout,
                env=env,
                chip_count=chip_count,
                tenant=tenant,
                priority=priority,
                deadline=deadline,
                limits=limits,
            ),
            self._execute_retry_policy,
            on_retry=on_retry,
        )

    # ------------------------------------------------- batched execution lanes

    def _batch_eligible(
        self,
        source_code: str | None,
        files: dict[str, str] | None,
        env: dict[str, str] | None,
        deadline: float | None,
    ) -> bool:
        """May this request ride a coalesced dispatch? Eligible = stateless
        inline source with no input files, no start deadline, and no
        profiler (the JAX profiler is process-global in the warm runner —
        two jobs cannot trace concurrently). Ineligible requests take the
        EXACT serial path; with the kill switch off, everything does."""
        if self.batcher is None:
            return False
        if _trusted_source_var.get():
            # Control-plane-authored runs (the compile-cache pre-warm) stay
            # serial: coalescing one with tenant jobs would taint the
            # sandbox mid-pre-warm (harvest admits nothing), and the fused
            # dispatch's usage billing keys on the batch's tenant — which
            # an unmetered internal run must not be.
            return False
        if source_code is None or files:
            return False
        if deadline is not None:
            # Deadline admission is a per-request promise about START time;
            # a window-parked job's start is the batch's, not its own. Keep
            # the serial path's exact semantics for deadline traffic.
            return False
        if env and "APP_JAX_PROFILE" in env:
            return False
        return True

    async def _execute_batched(
        self,
        source_code: str,
        *,
        timeout: float | None = None,
        env: dict[str, str] | None = None,
        chip_count: int | None = None,
        tenant: str | None = None,
        priority: str | None = None,
        limits: dict | None = None,
    ) -> Result:
        """Park one eligible request in the batching window and await its
        demuxed result. Compatibility keying happens HERE (tenant is part
        of the key by construction — batching never crosses tenants); the
        fused dispatch and per-job fan-out live in `_dispatch_batch`."""
        lane, _files, timeout, limits_payload = self._validate_request(
            source_code, None, None, timeout, chip_count, limits
        )
        if lane < 2 or num_hosts_for(lane, self.config.tpu_chips_per_host) > 1:
            # Coalescing pays on MULTI-chip lanes (idle chips are the waste
            # it recovers); single-chip/CPU lanes keep the serial path
            # byte-for-byte. Multi-HOST slices also stay serial: their
            # hosts rendezvous via jax.distributed, while the fused driver
            # runs on one host's runner (and their jobs are not "small
            # array jobs" anyway).
            return await self._execute_with_retry(
                source_code,
                timeout=timeout,
                env=env,
                chip_count=lane,
                tenant=tenant,
                priority=priority,
                limits=limits,
            )
        # Normalization (and its ValueError on bad client input) happens
        # BEFORE keying, exactly where the serial path validates.
        key = BatchKey(
            lane=lane,
            tenant=self.scheduler.normalize_tenant(tenant),
            priority=self.scheduler.normalize_priority(priority),
            env=freeze_mapping(env),
            limits=tuple(
                sorted(
                    (str(k), float(v))
                    for k, v in (limits_payload or {}).items()
                )
            ),
            timeout=float(timeout),
        )
        span = tracing.current_span()
        job = BatchJob(
            source_code=source_code,
            timeout=timeout,
            trace_id=tracing.current_trace_id(),
            parent_span_id=(
                span.span_id if span is not None and span.recording else None
            ),
            submitted_at=time.perf_counter(),
            # The dispatcher's task doesn't inherit this request's
            # contextvars — the purity declaration rides the job.
            pure=_pure_run_var.get(),
        )
        tracing.add_event(
            "batch.enqueue", lane=lane, pending=self.batcher.pending_jobs(key)
        )
        await self.batcher.submit(key, job)
        return await job.future

    async def _dispatch_batch(self, key: BatchKey, jobs: list[BatchJob]) -> None:
        """One closed batching window: acquire ONE sandbox with a multi-job
        token, run the fused dispatch, and settle every job's future. Any
        batch-level fault (acquisition failure, wire error, old binary,
        runner death, unattributable violation) falls back to the serial
        path per job — no request ever fails *because* it was batched."""
        # Dispatch runs in a batcher task that inherited SOME submitter's
        # trace context; detach so late spans never contaminate that
        # request's exported trace (the _off_request_path discipline).
        tracing.current_span_var.set(None)
        n = len(jobs)
        self.scheduler.observe_batch(key.lane, n, self.config.batch_max_jobs)
        if n < 2:
            # A window that expired under-filled: nothing to fuse, no
            # reason to leave the serial path's exact behavior.
            await self._serial_fallback(key, jobs, reason="underfilled")
            return
        try:
            sandbox = await self._acquire(
                key.lane, tenant=key.tenant, priority=key.priority, jobs=n
            )
        except Exception:
            # Breaker open / capacity timeout / shed: the serial path hits
            # the same admission wall per job and surfaces the standard
            # typed errors (with their standard metrics) to each caller.
            # CancelledError (and other BaseExceptions) propagate instead:
            # a shutdown-cancelled dispatch must fail its jobs' futures
            # (Batcher._run_dispatch does), not restart N serial runs at
            # exactly the moment the service is trying to stop.
            await self._serial_fallback(key, jobs, reason="acquire_failed")
            return
        reusable = False
        settled = False
        try:
            outcomes = await self._run_batch_on_sandbox(sandbox, key, jobs)
            reusable = True
            self.metrics.batch_dispatches.inc(outcome="ok")
            self.metrics.batch_jobs.inc(n, outcome="batched")
            for job, outcome in zip(jobs, outcomes):
                if isinstance(outcome, BaseException):
                    job.fail(outcome)
                else:
                    job.resolve(outcome)
            settled = True
        except LimitExceededError as e:
            # Batch-LEVEL violation (watchdog group kill / post-exec quota):
            # one address space means the breach cannot be pinned on one
            # job here. Dispose-vs-recycle follows the violation's own
            # continuable flag; the serial rerun below gives each job its
            # individual verdict (the real violator gets its 422, its
            # batchmates their clean results).
            reusable = e.continuable
            logger.warning(
                "batched dispatch hit a batch-level %s violation; "
                "re-running %d jobs serially",
                e.kind,
                n,
            )
            self.metrics.batch_dispatches.inc(outcome="violation_fallback")
        except Exception:
            logger.warning(
                "batched dispatch failed; re-running %d jobs serially",
                n,
                exc_info=True,
            )
            self.metrics.batch_dispatches.inc(outcome="error_fallback")
        finally:
            self._release_soon(sandbox, key.lane, reusable)
        if not settled:
            await self._serial_fallback(key, jobs, reason="batch_fault")

    async def _serial_fallback(
        self, key: BatchKey, jobs: list[BatchJob], reason: str
    ) -> None:
        """Run each job through the ordinary serial path and settle its
        future with whatever that path produces — success, typed violation,
        or the standard retryable errors. This is the transparency
        guarantee: a batch partner's fault costs its batchmates only time."""
        if reason != "underfilled":
            logger.info(
                "batch fallback (lane=%d, jobs=%d, reason=%s)",
                key.lane,
                len(jobs),
                reason,
            )
        self.metrics.batch_jobs.inc(len(jobs), outcome=f"serial_{reason}")
        env = dict(key.env) or None
        limits = {k: v for k, v in key.limits} or None

        async def one(job: BatchJob) -> None:
            # gather() wraps each coroutine in its own task (own context
            # copy), so re-asserting the submitter's purity declaration
            # here is job-isolated.
            token = _pure_run_var.set(job.pure)
            try:
                result = await self._execute_with_retry(
                    job.source_code,
                    timeout=job.timeout,
                    env=env,
                    chip_count=key.lane,
                    tenant=key.tenant,
                    priority=key.priority,
                    limits=limits,
                )
            except BaseException as e:
                job.fail(e)
            else:
                job.resolve(result)
            finally:
                _pure_run_var.reset(token)

        await asyncio.gather(*(one(job) for job in jobs))

    async def _run_batch_on_sandbox(
        self, sandbox: Sandbox, key: BatchKey, jobs: list[BatchJob]
    ) -> list:
        """The fused round-trip: POST /execute-batch to the sandbox (which
        stages one workdir per job and runs them as one dispatch spread
        over the lane's device axis), then demux per-job stdout/stderr,
        changed files, violations, and trace spans back to each caller.

        Returns one outcome per job: a Result, or a LimitExceededError for
        a job whose IN-PROCESS guard fired (its batchmates' results stay
        clean). Batch-level faults raise instead — the caller falls back."""
        self._check_lease(sandbox)
        client = self._http_client()
        if self.compile_cache.enabled:
            # Tenant code is about to run: same provenance taint as the
            # serial path (see _run_on_sandbox).
            self._cache_sync(sandbox).taint()
            if self._compile_cache_dir_scope() == "shared":
                self._shared_cache_tainted = True
        base = sandbox.host_urls[0]
        n = len(jobs)
        overall_timeout = max(job.timeout for job in jobs)
        try:
            from ..parallel.mesh import job_device_assignment

            assignment = job_device_assignment(n, sandbox.chip_count or None)
        except Exception:  # noqa: BLE001 — placement is a hint, not a gate
            assignment = [None] * n
        payload: dict = {
            "timeout": overall_timeout,
            "jobs": [
                {
                    "source_code": job.source_code,
                    **({"trace_id": job.trace_id} if job.trace_id else {}),
                    **({"pure": True} if job.pure else {}),
                    **(
                        {"device_index": device}
                        if device is not None
                        else {}
                    ),
                }
                for job, device in zip(jobs, assignment)
            ],
        }
        if self.perf.enabled:
            # Per-job device-memory brackets, same knob as the serial path.
            payload["device_memory"] = True
        if key.env:
            payload["env"] = dict(key.env)
        if key.limits:
            payload["limits"] = {k: v for k, v in key.limits}
        usage_tenant = key.tenant if self.usage.enabled else None
        chips = max(1, sandbox.chip_count or 0)
        exec_start_wall = time.time()
        exec_start = time.perf_counter()
        try:
            body = await self._post_execute_batch(
                client, base, payload, overall_timeout, sandbox
            )
        except ExecutorError as e:
            if usage_tenant is not None and getattr(
                e, "device_may_have_run", True
            ):
                # Wire fault mid-dispatch: the fused run consumed (or is
                # still consuming) real device time — bill the measured
                # wall, like the serial fault path. The serial fallback's
                # reruns bill their own consumption separately (the chips
                # really do run twice). CLEAN REFUSALS are exempt: a 404
                # (old binary) or 409 (no warm runner) answered without
                # running anything — billing wall x chips there would
                # systematically overbill every batch during a rolling
                # upgrade, on top of the serial rerun's real bill.
                wall = max(0.0, time.perf_counter() - exec_start)
                self.usage.add(
                    usage_tenant,
                    chip_seconds=wall * chips,
                    device_op_seconds=wall,
                )
            raise
        exec_seconds = time.perf_counter() - exec_start
        # The fused dispatch's device-op wall, from the executor's own op
        # window — billed to the batch's ONE tenant (tenant is in the
        # BatchKey by construction) BEFORE the verdict checks below, so a
        # batch that violated or aborted still bills the device time it
        # consumed.
        device_op = self._reported_device_op([body], fallback=exec_seconds)
        total_chip_seconds = device_op * chips
        if usage_tenant is not None:
            cc_block = body.get("compile_cache")
            self.usage.add(
                usage_tenant,
                chip_seconds=total_chip_seconds,
                device_op_seconds=device_op,
                compile_cache_recompiles=self._cc_count(cc_block, "misses"),
                compile_cache_new_bytes=self._cc_count(cc_block, "new_bytes"),
            )
        runner_restarted = bool(body.get("runner_restarted"))
        batch_violation = body.get("violation")
        if batch_violation:
            if batch_violation not in VIOLATION_KINDS:
                batch_violation = "unknown"
            raise LimitExceededError(
                f"sandbox resource limit exceeded during a batched dispatch: "
                f"{batch_violation} (sandbox {sandbox.id})",
                kind=batch_violation,
                lane=sandbox.chip_count,
                continuable=not runner_restarted,
            )
        if runner_restarted:
            raise ExecutorError(
                f"sandbox {sandbox.id} warm runner died mid-batch"
            )
        if body.get("timed_out"):
            # The OVERALL batch window timed out — per-job timeouts are only
            # enforceable on the serial path (threads cannot be killed
            # individually), so rerun there for each job's own verdict.
            raise ExecutorError(
                f"sandbox {sandbox.id} batch dispatch timed out"
            )
        results = body.get("results")
        if not isinstance(results, list) or len(results) != n:
            raise ExecutorError(
                f"sandbox {sandbox.id} returned {0 if not isinstance(results, list) else len(results)} "
                f"batch results for {n} jobs"
            )
        if any(not isinstance(entry, dict) for entry in results):
            # Malformed per-job entries are a BATCH-level fault like a short
            # results array: raising here routes through the serial
            # fallback (with its retries), instead of failing one caller
            # with a hard infra error the serial path would have retried.
            raise ExecutorError(
                f"sandbox {sandbox.id} returned a malformed batch entry"
            )
        if any(entry.get("aborted") for entry in results):
            # A job thread never finished (batch-level abort mid-run): its
            # "result" is unusable and its batchmates' are suspect — the
            # serial rerun owns the per-job verdicts.
            raise ExecutorError(
                f"sandbox {sandbox.id} aborted a batch mid-run"
            )
        if body.get("batch_stdout"):
            # fd-level stdout (a subprocess, a C extension writing fd 1)
            # bypasses the per-thread stream demux and lands in the
            # batch-level capture — it cannot be attributed to a job, and
            # silently dropping it would lose output the serial path
            # returns. Rerun serially: every caller gets its exact output,
            # batching costs this window only time.
            raise ExecutorError(
                f"sandbox {sandbox.id} produced un-demuxable batch-level "
                f"stdout ({len(body['batch_stdout'])} bytes)"
            )
        # Apportion the fused run's chip-seconds across its jobs: per-job
        # exec spans give the weights (equal split when any are absent), so
        # the jobs' shares sum EXACTLY to the dispatch's total — a tenant's
        # bill is identical whether its jobs rode the fused or serial path,
        # and per-job attribution never double-bills or loses time.
        shares = self._batch_chip_shares(results)
        stats = TransferStats()
        outcomes = await asyncio.gather(
            *(
                self._demux_batch_job(
                    client,
                    base,
                    sandbox,
                    key,
                    job,
                    entry,
                    index=i,
                    batch_jobs=n,
                    exec_start_wall=exec_start_wall,
                    exec_start_perf=exec_start,
                    exec_seconds=exec_seconds,
                    warm=bool(body.get("warm", False)),
                    stats=stats,
                    chip_seconds_share=(
                        total_chip_seconds * shares[i]
                        if usage_tenant is not None
                        else None
                    ),
                    device_op_share=(
                        device_op * shares[i]
                        if usage_tenant is not None
                        else None
                    ),
                )
                for i, (job, entry) in enumerate(zip(jobs, results))
            )
        )
        stats.emit(self.metrics)
        if usage_tenant is not None:
            # hbm-byte-seconds, fused-path flavor: each job's peak
            # integrated over ITS device-op share, summing to the same
            # bill the jobs would produce serially (path-invariance, the
            # chip-second discipline).
            hbm_byte_seconds = sum(
                self._block_peak_bytes(entry["device_memory"])
                * device_op
                * share
                for entry, share in zip(results, shares)
                if isinstance(entry.get("device_memory"), dict)
            )
            self.usage.add(
                usage_tenant,
                batch_jobs=n,
                download_bytes=stats.download_bytes,
                hbm_byte_seconds=hbm_byte_seconds,
            )
        # A clean fused run ends the lane's consecutive-violation streak,
        # exactly like a clean serial run.
        self._violation_strikes.pop(sandbox.chip_count, None)
        return outcomes

    @staticmethod
    def _batch_chip_shares(results: list) -> list[float]:
        """Per-job fractions of the fused dispatch's chip-seconds. Weights
        are the per-job exec spans the demux already carries
        (device_op_seconds / duration_s); when ANY job's span is absent the
        whole batch falls back to an equal split — mixing measured weights
        with invented ones would silently skew every share. Fractions sum
        to 1.0 by construction."""
        n = len(results)
        weights: list[float] = []
        for entry in results:
            value = entry.get("device_op_seconds", entry.get("duration_s"))
            if isinstance(value, (int, float)) and value > 0:
                weights.append(float(value))
            else:
                weights = []
                break
        if len(weights) != n or not sum(weights):
            return [1.0 / n] * n
        total = sum(weights)
        return [w / total for w in weights]

    async def _post_execute_batch(
        self,
        client: httpx.AsyncClient,
        base: str,
        payload: dict,
        timeout: float,
        sandbox: Sandbox,
    ) -> dict:
        """The /execute-batch wire hop (split out so tests can fake the
        sandbox exactly like `_post_execute`)."""
        try:
            resp = await client.post(
                f"{base}/execute-batch",
                json=payload,
                headers=self._wire_headers(sandbox),
                timeout=httpx.Timeout(timeout + 30.0),
            )
        except httpx.HTTPError as e:
            raise ExecutorError(
                f"sandbox {sandbox.id} ({base}) unreachable: {e}"
            )
        # 409 on this route ALSO means "no warm runner" (serial-fallback
        # refusal); only the typed stale_lease body raises the lease error.
        self._raise_if_stale_lease(resp, sandbox)
        if resp.status_code != 200:
            # 404 = old binary without the route, 409 = no warm runner:
            # either way the serial path is the answer. The server
            # ANSWERED with a refusal — nothing ran on the device, so
            # usage billing must not charge wall time for this hop
            # (device_may_have_run gates the fault-billing path).
            error = ExecutorError(
                f"sandbox {sandbox.id} ({base}) /execute-batch -> "
                f"{resp.status_code}: {resp.text[:300]}"
            )
            error.device_may_have_run = False
            raise error
        try:
            return resp.json()
        except ValueError as e:
            raise ExecutorError(
                f"sandbox {sandbox.id} ({base}) returned malformed JSON: {e}"
            )

    async def _demux_batch_job(
        self,
        client: httpx.AsyncClient,
        base: str,
        sandbox: Sandbox,
        key: BatchKey,
        job: BatchJob,
        entry,
        *,
        index: int,
        batch_jobs: int,
        exec_start_wall: float,
        exec_seconds: float,
        warm: bool,
        stats: TransferStats,
        exec_start_perf: float | None = None,
        chip_seconds_share: float | None = None,
        device_op_share: float | None = None,
    ):
        """One job's slice of the batch response → its Result (changed
        files downloaded from its private workdir, hash-negotiated like any
        download) or its typed in-process violation. Also grafts the job's
        sandbox timing into the ORIGINATING request's trace. Entries are
        dict-validated by the caller (a malformed one is a batch-level
        fault, not one job's)."""
        duration = entry.get("duration_s")
        if job.trace_id is not None and job.parent_span_id is not None:
            offset = entry.get("start_offset_s")
            self.tracer.record_span(
                "sandbox.batch_job",
                trace_id=job.trace_id,
                parent_id=job.parent_span_id,
                start_unix=exec_start_wall
                + (float(offset) if isinstance(offset, (int, float)) else 0.0),
                duration_s=(
                    float(duration)
                    if isinstance(duration, (int, float))
                    else exec_seconds
                ),
                attributes={
                    "host": base,
                    "batch_index": index,
                    "batch_jobs": batch_jobs,
                },
            )
        violation = entry.get("violation")
        if violation and isinstance(violation, str):
            if violation not in VIOLATION_KINDS:
                logger.warning(
                    "sandbox %s reported unknown batch violation kind %.40r",
                    sandbox.id,
                    violation,
                )
                violation = "unknown"
            stderr_tail = str(entry.get("stderr", ""))[-500:]
            return LimitExceededError(
                f"sandbox resource limit exceeded: {violation} "
                f"(sandbox {sandbox.id}, batched); {stderr_tail}".rstrip("; "),
                kind=violation,
                lane=sandbox.chip_count,
                # The in-process guard fired inside ONE job's thread; the
                # runner (and its batchmates) survived.
                continuable=True,
            )
        workdir = entry.get("workdir")
        merged_files: dict[str, str] = {}
        if isinstance(workdir, str) and workdir:
            entries, _has_hashes = parse_files_field(entry.get("files", []))
            fetched = await asyncio.gather(
                *(
                    self._fetch_changed(
                        client,
                        base,
                        f"{workdir}/{rel}",
                        sha if self._transfer_state(sandbox).enabled else None,
                        stats,
                    )
                    for rel, sha in entries
                )
            )
            for (full_rel, object_id), (rel, _sha) in zip(fetched, entries):
                # Demux contract: the caller sees ITS files at the paths
                # its code wrote them, not the batch's staging prefix.
                merged_files[f"/workspace/{rel}"] = object_id
        phases: dict[str, float | str] = {
            "exec": (
                float(duration)
                if isinstance(duration, (int, float))
                else exec_seconds
            ),
            "batch_jobs": float(batch_jobs),
            "batch_index": float(index),
        }
        if exec_start_perf is not None and job.submitted_at:
            # The job's real pre-exec wait: batching window + scheduler
            # queue — the fused path's analogue of the serial queue_wait
            # phase (a latency; it rides the phase_seconds histogram).
            phases["queue_wait"] = round(
                max(0.0, exec_start_perf - job.submitted_at), 6
            )
        if chip_seconds_share is not None:
            # This job's apportioned slice of the fused dispatch's
            # chip-seconds (per-job exec spans weight the split): summed
            # over the batch these equal the dispatch's total exactly.
            phases["chip_seconds"] = round(chip_seconds_share, 6)
        if device_op_share is not None:
            phases["device_op_seconds"] = round(device_op_share, 6)
        # Per-job device-memory block (best-effort under concurrent
        # batchmates — one address space): same phase keys as the serial
        # path, so a client reads one shape either way.
        mem_phases, _peak = self._device_memory_phases([entry])
        phases.update(mem_phases)
        if job.trace_id is not None:
            phases["trace_id"] = job.trace_id
        return Result(
            stdout=str(entry.get("stdout", "")),
            stderr=str(entry.get("stderr", "")),
            exit_code=int(entry.get("exit_code", -1)),
            files=merged_files,
            phases=phases,
            warm=warm,
            stdout_truncated=bool(entry.get("stdout_truncated", False)),
            stderr_truncated=bool(entry.get("stderr_truncated", False)),
            # Per-job purity echo: the entry hashes ITS OWN demuxed
            # streams/files, so a batchmate's output can never leak into a
            # recorded result unnoticed.
            pure_echo=(
                self._verified_pure_echo([entry]) if job.pure else None
            ),
        )

    async def _execute_once(
        self,
        source_code: str | None = None,
        *,
        source_file: str | None = None,
        files: dict[str, str] | None = None,
        timeout: float | None = None,
        env: dict[str, str] | None = None,
        chip_count: int | None = None,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        limits: dict | None = None,
        emit=None,
    ) -> Result:
        lane, files, timeout, limits_payload = self._validate_request(
            source_code, source_file, files, timeout, chip_count, limits
        )
        timer = PhaseTimer()
        # One draft per ATTEMPT (the retry ladder re-enters here): a failed
        # attempt consumed real device time and is billed; the logical
        # request is counted once, at the API surface.
        usage = self._usage_draft(tenant)

        self._edge_queued()
        with timer.phase("queue_wait"):
            sandbox = await self._acquire(
                lane, tenant=tenant, priority=priority, deadline=deadline
            )
        acquired = sandbox.meta.pop("acquired", None)
        reusable = False
        try:
            result, _continuable = await self._run_on_sandbox(
                sandbox, source_code, source_file, files, timeout, env, timer,
                limits=limits_payload, emit=emit, usage=usage,
            )
            if acquired:
                result.phases.update(acquired)
            # The request completed (user errors included). Whether the
            # sandbox is actually safe to recycle is the server's call —
            # /reset refuses (409) when its runner was killed by a timeout
            # or died — so only infra failures (exceptions before this
            # point) hard-disqualify reuse here.
            reusable = True
            return result
        except LimitExceededError as e:
            # Repeat-offender path: a violation that killed the runner makes
            # the host non-reusable (disposed + lane refilled); an
            # in-process guard left it scrubbable, so it recycles normally.
            reusable = e.continuable
            raise
        finally:
            # Attribution commits on EVERY exit — success, violation, or
            # fault: a request that fails after consuming device time is
            # still billed (the draft holds whatever the attempt measured).
            self._edge_mark(_edge_var.get(), "edge.usage_commit")
            self.usage.commit(usage)
            # Sandbox release off the hot path: recycle the warm device
            # process back into the pool (generation turnover via /reset),
            # or dispose it when it can't be safely reused.
            self._edge_mark(_edge_var.get(), "edge.release")
            self._release_soon(sandbox, lane, reusable)

    def _validate_request(
        self,
        source_code: str | None,
        source_file: str | None,
        files: dict[str, str] | None,
        timeout: float | None,
        chip_count: int | None,
        limits: dict | None = None,
    ) -> tuple[int, dict[str, str], float, dict | None]:
        if (source_code is None) == (source_file is None):
            raise ValueError("exactly one of source_code/source_file is required")
        files = files or {}
        lane = self.config.default_chip_count if chip_count is None else chip_count
        # Fail a non-tiling chip_count here, before any pool machinery runs
        # (surfaces as an invalid-argument error, not a spawn failure).
        num_hosts_for(lane, self.config.tpu_chips_per_host)
        timeout = min(
            timeout or self.config.default_execution_timeout,
            self.config.max_execution_timeout,
        )
        # Resource budget: defaults -> lane -> request override, clamped by
        # the server caps; malformed overrides fail here as client errors.
        limits_payload = request_limits(self.config, lane, limits)
        return lane, files, timeout, limits_payload

    async def _run_on_sandbox(
        self,
        sandbox: Sandbox,
        source_code: str | None,
        source_file: str | None,
        files: dict[str, str],
        timeout: float,
        env: dict[str, str] | None,
        timer: PhaseTimer,
        limits: dict | None = None,
        emit=None,
        usage: UsageDraft | None = None,
    ) -> tuple[Result, bool]:
        """The sandbox round-trip: upload inputs, fan /execute out to every
        host, download changed files. Returns (result, continuable) —
        continuable is False when a host's warm runner was killed (timeout)
        or crashed, i.e. any in-process state is gone and a session must not
        keep using the sandbox.

        A host reporting a typed `violation` raises LimitExceededError
        BEFORE the download phase: the bytes a disk-filler left behind are
        exactly what must not be shipped into content-addressed storage.

        With `emit` (an async callback), host 0 runs via /execute/stream and
        stdout/stderr chunks are emitted as the code produces them; the final
        Result is identical either way (the stream's last event carries the
        full response body). Peers of a multi-host slice never stream — host
        0 is the coordinator and, per JAX convention, does the singular side
        effects worth watching live."""
        # Lease gate before ANY wire traffic: a fence that landed while
        # this request held the sandbox refuses here, cleanly, instead of
        # dispatching into (or racing) the wedged device plane.
        self._check_lease(sandbox)
        client = self._http_client()
        if self.compile_cache.enabled and not _trusted_source_var.get():
            # Tenant code is about to run (or try to): this sandbox's cache
            # dir is attacker-writable from here on, so its compile-cache
            # harvest eligibility is revoked for the sandbox's lifetime —
            # the cache dir survives /reset, so the taint must too. Set
            # BEFORE any tenant byte runs, so a harvest racing this request
            # can never observe untainted state after a tenant write.
            self._cache_sync(sandbox).taint()
            if self._compile_cache_dir_scope() == "shared":
                # Every sandbox shares this one's cache dir: the write
                # surface is control-plane-wide, so the taint is too.
                self._shared_cache_tainted = True
        # A multi-host slice is one sandbox with an executor per host:
        # inputs go to every host, /execute fires on every host (the
        # hosts rendezvous via their pre-established jax.distributed
        # mesh), and outputs merge with host-0 precedence.
        hosts = sandbox.host_urls
        transfer = self._transfer_state(sandbox)
        stats = TransferStats()
        if usage is not None:
            # The chip multiplier: the sandbox's actual topology (a lane-0
            # "whatever the sandbox has" request bills what it really
            # held; CPU sandboxes bill device-op seconds x 1).
            usage.chips = max(1, sandbox.chip_count or 0)
        with timer.phase("upload"):
            with self.tracer.span("transfer.upload") as upload_span:
                try:
                    await self._upload_inputs(
                        client, hosts, transfer, files, stats
                    )
                except LimitExceededError as e:
                    # The executor's PUT quota fired (413): enrich with the
                    # lane and account it like an exec-phase violation.
                    e.lane = sandbox.chip_count
                    tracing.add_event(
                        "limit.violation", kind=e.kind, lane=e.lane,
                        phase="upload",
                    )
                    raise
                upload_span.set_attribute("bytes_moved", stats.upload_bytes)
                upload_span.set_attribute(
                    "bytes_copied", stats.upload_copied_bytes
                )
                upload_span.set_attribute(
                    "bytes_skipped", stats.upload_skipped_bytes
                )
                upload_span.set_attribute("files_moved", stats.upload_files)
                upload_span.set_attribute(
                    "files_skipped", stats.upload_skipped_files
                )
        with timer.phase("exec"):
            payload: dict = {"timeout": timeout}
            if self.perf.enabled:
                # Ask the sandbox for the device-memory bracket (live/peak
                # buffer bytes + runner RSS around the run). Only when the
                # perf plane is live — the kill switch keeps the wire
                # payload, and the runner's sampling cost, byte-for-byte
                # what it is today.
                payload["device_memory"] = True
            if _pure_run_var.get():
                # Purity declaration (result-memo miss in flight): the
                # executor echoes it with a result hash the record path
                # verifies end-to-end (see _verified_pure_echo).
                payload["pure"] = True
            if env:
                payload["env"] = env
            if limits:
                payload["limits"] = limits
            if source_code is not None:
                payload["source_code"] = source_code
            else:
                payload["source_file"] = source_file
            if usage is not None:
                usage.upload_bytes += stats.upload_bytes
            exec_started = time.perf_counter()
            bodies = await asyncio.gather(
                *(
                    self._call_host(
                        client, index, base, payload, timeout, sandbox, emit
                    )
                    for index, base in enumerate(hosts)
                ),
                # Let every host finish before surfacing a failure — a
                # half-cancelled slice group would leak in-flight
                # requests into the dispose path.
                return_exceptions=True,
            )
            failure = next(
                (b for b in bodies if isinstance(b, BaseException)), None
            )
            if failure is not None:
                if usage is not None and getattr(
                    failure, "device_may_have_run", True
                ):
                    # Wire fault mid-exec: the executor's own op clock is
                    # unreachable, but the device very likely ran (or is
                    # still running) the whole window — bill the measured
                    # exec wall, the best evidence available. A request is
                    # never free just because it faulted. Clean refusals
                    # (non-200: the server answered without running) are
                    # exempt — see _post_execute.
                    usage.device_op_seconds += max(
                        0.0, time.perf_counter() - exec_started
                    )
                raise failure
            # The executor's OWN op window (the device_op_seconds wire
            # field; duration_s on an older binary) — NOT control-plane
            # wall, which includes queueing/transfer. A multi-host slice's
            # hosts run one op in parallel: the op wall is the slowest
            # host's. Held in a local because both the chip-second bill
            # and the hbm-byte-second integral below read it.
            op_wall = self._reported_device_op(
                bodies,
                fallback=max(0.0, time.perf_counter() - exec_started),
            )
            if usage is not None:
                # Observed BEFORE the violation check below, so a violating
                # request still bills the device time it consumed.
                usage.device_op_seconds += op_wall
            self._raise_on_violation(sandbox, hosts, bodies)
        with timer.phase("download"):
            with self.tracer.span("transfer.download") as download_span:
                merged_files = await self._download_changed(
                    client, hosts, transfer, bodies, stats
                )
                download_span.set_attribute("bytes_moved", stats.download_bytes)
                download_span.set_attribute(
                    "bytes_skipped", stats.download_skipped_bytes
                )
                download_span.set_attribute(
                    "files_moved", stats.download_files
                )
                download_span.set_attribute(
                    "files_skipped", stats.download_skipped_files
                )
        self._edge_downloaded()
        primary = bodies[0]
        stderr = primary.get("stderr", "")
        exit_code = int(primary.get("exit_code", -1))
        for host_index, body in enumerate(bodies[1:], start=1):
            host_exit = int(body.get("exit_code", -1))
            if host_exit != 0 and exit_code == 0:
                exit_code = host_exit
            if host_exit != 0 and body.get("stderr"):
                stderr += ("\n" if stderr else "") + (
                    f"[host {host_index}] {body['stderr']}"
                )
        continuable = not any(bool(b.get("runner_restarted")) for b in bodies)
        if not continuable:
            # A runner was killed mid-request: stray user processes may have
            # mutated the workspace after the post-execute scan, so the
            # cached manifests are no longer trustworthy. Forget them; the
            # next upload phase resyncs from GET /workspace-manifest.
            transfer.invalidate()
        stats.emit(self.metrics)
        phases = {**timer.as_dict(), **stats.as_phases()}
        phases.update(self._stage_phases(primary, phases.get("exec", 0.0)))
        phases.update(self._user_cpu_phase(primary))
        phases.update(self._compile_cache_phases(sandbox, bodies))
        phases.update(self._shim_phases(primary))
        # Device-memory accounting: the hosts' wire blocks folded into
        # phases (peak_hbm_bytes / live_buffer_bytes_delta — non-latency
        # keys, excluded from the histogram by the allowlist) and, below,
        # integrated over the op wall into the tenant's ledger.
        mem_phases, peak_hbm = self._device_memory_phases(bodies)
        phases.update(mem_phases)
        # Auto-profile harvest: a control-plane-armed profiler run's
        # profile.zip moves OUT of the tenant's files into the profile
        # store — the tenant neither asked for nor receives it, and (the
        # PR 9 trusted-run rule) must not be billed its transfer.
        auto_profile = _auto_profile_var.get()
        # A mark on the turn the service's own profiler captured: whoever
        # reads a window's phases can tell which turns it reached into.
        phases["auto_profiled"] = 0.0 if auto_profile is None else 1.0
        harvested_bytes = 0
        if auto_profile is not None:
            harvested_bytes = await self._harvest_profile(
                merged_files,
                sandbox,
                auto_profile,
                tenant=usage.tenant if usage is not None else None,
            )
        if usage is not None:
            usage.hbm_byte_seconds += max(0.0, peak_hbm) * op_wall
            usage.download_bytes += max(
                0, stats.download_bytes - harvested_bytes
            )
            usage.compile_cache_recompiles += float(
                phases.get("compile_cache_misses", 0.0)
            )
            usage.compile_cache_new_bytes += float(
                phases.get("compile_cache_new_bytes", 0.0)
            )
            # Per-request attribution fields: what THIS run cost, as
            # billed. Not latencies — the phase_seconds allowlist keeps
            # them out of the latency histogram by construction.
            phases["device_op_seconds"] = round(usage.device_op_seconds, 6)
            phases["chip_seconds"] = round(usage.chip_seconds, 6)
        # Correlate the response with its trace: clients quote this id at
        # GET /traces/{trace_id} (it also rides the X-Trace-Id header and
        # gRPC trailing metadata). A string among the float phase values —
        # consumers that iterate phases numerically skip non-numbers.
        trace_id = tracing.current_trace_id()
        if trace_id is not None:
            phases["trace_id"] = trace_id
        # A clean run ends the lane's consecutive-violation streak (the
        # repeat-offender trip targets storms, not a mixed workload).
        self._violation_strikes.pop(sandbox.chip_count, None)
        result = Result(
            stdout=primary.get("stdout", ""),
            stderr=stderr,
            exit_code=exit_code,
            files=merged_files,
            phases=phases,
            warm=bool(primary.get("warm", False)),
            stdout_truncated=bool(primary.get("stdout_truncated", False)),
            stderr_truncated=any(
                bool(b.get("stderr_truncated", False)) for b in bodies
            ),
            pure_echo=(
                self._verified_pure_echo(bodies)
                if _pure_run_var.get()
                else None
            ),
        )
        return result, continuable

    @classmethod
    def _stage_phases(cls, body, exec_seconds: float) -> dict[str, float]:
        """STAGE_PHASES from host 0's `trace` block (all 0.0 where it sent
        none): where inside `exec` the time went, by the sandbox handler's
        and the warm runner's own clocks. The parts never sum past `exec`:
        what is left over is the reply line's way back through the pipe."""
        phases = dict.fromkeys(STAGE_PHASES, 0.0)
        stages = {
            name: (offset, max(0.0, duration))
            for name, offset, duration, _ in cls._trace_block_entries(body)
        }
        if not stages:
            return phases
        total = body["trace"].get("total_s")
        if isinstance(total, (int, float)) and total >= 0:
            phases["exec_wire"] = max(0.0, exec_seconds - float(total))
            if "runner_wait" in stages:
                sent, waited = stages["runner_wait"]
                phases["sandbox_before_run"] = max(0.0, sent)
                phases["sandbox_after_run"] = max(0.0, float(total) - sent - waited)

        def seconds(*names: str) -> float:
            return sum(stages[n][1] for n in names if n in stages)

        phases["runner_pickup"] = seconds("runner.pickup")
        phases["runner_before_user"] = seconds(*_RUNNER_BEFORE_USER)
        phases["runner_user_code"] = seconds("runner.user_code")
        phases["runner_after_user"] = seconds(*_RUNNER_AFTER_USER)
        return {key: round(value, 6) for key, value in phases.items()}

    @staticmethod
    def _user_cpu_phase(body) -> dict[str, float]:
        """`runner_user_cpu` from host 0's `user_cpu_s`: the runner process's
        CPU seconds (user and system, every thread) inside its `user_code`
        stage, which says of that stage's wall whether the host computed or
        slept. Nothing where the runner sent no number (a cold run, an older
        runner)."""
        value = body.get("user_cpu_s")
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            return {}
        return {"runner_user_cpu": round(max(0.0, float(value)), 6)}

    @staticmethod
    def _shim_phases(body) -> dict[str, float]:
        """SHIM_PHASES from host 0's `shim` block; nothing where the runner
        sent none (no shim installed, a cold run, an older binary), and no key
        for a name the block lacks (a runner from before the counter): a
        metric that reads it then finds nothing, never a 0 that was not
        measured. Only the known names, and only numbers: the block comes from
        the process that ran the user's code."""
        block = body.get("shim")
        if not isinstance(block, dict):
            return {}
        phases = {}
        for name, key in SHIM_PHASES.items():
            if name not in block:
                continue
            value = block[name]
            if isinstance(value, bool) or not isinstance(value, (int, float)):
                value = 0
            phases[key] = round(max(0.0, float(value)), 6)
        return phases

    @staticmethod
    def _reported_device_op(bodies: list, fallback: float = 0.0) -> float:
        """The device-op wall the executor itself measured for this
        request: `device_op_seconds` from the wire (the op window around
        the runner round-trip / cold subprocess), `duration_s` from an
        older binary, control-plane exec wall only when neither answered.
        Hosts of one slice run the op in parallel — the wall is the max."""
        values = [
            body.get("device_op_seconds", body.get("duration_s"))
            for body in bodies
            if isinstance(body, dict)
        ]
        numbers = [
            float(v) for v in values if isinstance(v, (int, float)) and v >= 0
        ]
        return max(numbers) if numbers else max(0.0, fallback)

    PROFILE_ARTIFACT = "/workspace/profile.zip"

    @staticmethod
    def _block_peak_bytes(block: dict) -> float:
        """One host's per-request peak device-buffer bytes from its
        device_memory wire block. When the allocator's process-lifetime
        peak MOVED during the run, that new high-water IS this request's
        peak; otherwise the request ran under an older high-water and the
        honest per-request figure is what it actually held (the larger of
        the live samples bracketing the run — the CPU/live_arrays path,
        which has no allocator peak at all, always lands here). -1 wire
        values mean "unavailable" and never poison the max."""

        def num(key: str) -> float:
            value = block.get(key)
            return float(value) if isinstance(value, (int, float)) else -1.0

        live = [
            v
            for v in (num("live_bytes_before"), num("live_bytes_after"))
            if v >= 0
        ]
        base = max(live) if live else 0.0
        peak_before = num("peak_bytes_before")
        peak_after = num("peak_bytes_after")
        if peak_after >= 0 and peak_after > peak_before >= 0:
            return max(base, peak_after)
        return base

    def _device_memory_phases(
        self, bodies: list[dict]
    ) -> tuple[dict[str, float], float]:
        """Fold the hosts' device_memory wire blocks into Result.phases
        fields; returns (phases, peak_hbm_bytes). A multi-host slice sums
        peaks and live deltas across hosts (the slice's total footprint)
        and reports the largest runner RSS. Returns ({}, 0) when no host
        reported (old binary, cold subprocess, plane disabled)."""
        if not self.perf.enabled:
            return {}, 0.0
        peak = delta = 0.0
        rss = -1.0
        seen = False
        for body in bodies:
            block = body.get("device_memory")
            if not isinstance(block, dict):
                continue
            seen = True
            peak += self._block_peak_bytes(block)
            before = block.get("live_bytes_before")
            after = block.get("live_bytes_after")
            if (
                isinstance(before, (int, float))
                and isinstance(after, (int, float))
                and before >= 0
                and after >= 0
            ):
                delta += float(after) - float(before)
            block_rss = block.get("rss_bytes")
            if isinstance(block_rss, (int, float)) and block_rss > rss:
                rss = float(block_rss)
        if not seen:
            return {}, 0.0
        phases: dict[str, float] = {
            "peak_hbm_bytes": round(peak, 1),
            "live_buffer_bytes_delta": round(delta, 1),
        }
        if rss >= 0:
            phases["runner_rss_bytes"] = round(rss, 1)
        return phases, peak

    async def _harvest_profile(
        self,
        merged_files: dict[str, str],
        sandbox: Sandbox,
        reason: str,
        *,
        tenant: str | None,
    ) -> int:
        """Move an auto-captured profile.zip from the request's changed
        files into the profile store (content-addressed, trace-id
        cross-linked). Returns the artifact's byte size so the caller can
        exempt the harvest from the tenant's transfer bill. Best-effort:
        a failed harvest logs and bills nothing extra — the artifact
        simply stays in the tenant's files like a client-requested
        profile."""
        object_id = merged_files.get(self.PROFILE_ARTIFACT)
        if object_id is None:
            return 0
        try:
            data = await self.storage.read(object_id)
        except (StorageObjectNotFound, OSError):
            logger.warning(
                "auto-profile artifact %s unreadable; leaving it in the "
                "request's files",
                object_id,
            )
            return 0
        profile_id = self.perf.note_profile_captured(
            data,
            lane=sandbox.chip_count,
            reason=reason,
            tenant=tenant,
            trace_id=tracing.current_trace_id(),
        )
        if profile_id is None:
            # The store couldn't make the artifact durable (full/unwritable
            # volume): leave the ONLY copy in the request's files — billed
            # and returned like a client-requested profile — instead of
            # destroying the regression evidence.
            logger.warning(
                "auto-profile store rejected the artifact; leaving it in "
                "the request's files (billed normally)"
            )
            return 0
        del merged_files[self.PROFILE_ARTIFACT]
        tracing.add_event(
            "perf.profile_harvested", reason=reason, bytes=len(data)
        )
        return len(data)

    @staticmethod
    def _cc_count(block, key: str) -> int:
        """One reading of the executor's `compile_cache` response block:
        non-dict blocks and non-numeric/negative values read as 0. ONE
        implementation for the serial and batch paths — a wire-format
        tweak parsed differently per path would skew batch billing
        relative to serial, breaking the bill's path-invariance."""
        if not isinstance(block, dict):
            return 0
        value = block.get(key)
        return int(value) if isinstance(value, (int, float)) and value > 0 else 0

    def _compile_cache_phases(
        self, sandbox: Sandbox, bodies: list[dict]
    ) -> dict[str, float]:
        """Per-request compile-cache observability: the hosts' hit/miss and
        new-entry counters summed into Result.phases, a trace event on the
        execute span, and the hit/miss outcome counters. A request that
        popped a freshly seeded sandbox also reports what seeding it cost."""
        if not self.compile_cache.enabled:
            return {}
        hits = misses = new_entries = new_bytes = 0
        seen = False
        for body in bodies:
            block = body.get("compile_cache")
            if not isinstance(block, dict):
                continue
            seen = True
            hits += self._cc_count(block, "hits")
            misses += self._cc_count(block, "misses")
            new_entries += self._cc_count(block, "new_entries")
            new_bytes += self._cc_count(block, "new_bytes")
        phases: dict[str, float] = {}
        if seen:
            phases["compile_cache_hits"] = float(hits)
            phases["compile_cache_misses"] = float(misses)
            phases["compile_cache_new_bytes"] = float(new_bytes)
            if hits:
                self.metrics.compile_cache_kernels.inc(hits, outcome="hit")
            if misses:
                self.metrics.compile_cache_kernels.inc(misses, outcome="miss")
            if hits or misses or new_entries:
                tracing.add_event(
                    "compile_cache",
                    hits=hits,
                    misses=misses,
                    new_entries=new_entries,
                    new_bytes=new_bytes,
                )
        sync = sandbox.meta.get("compile_cache")
        if (
            isinstance(sync, SandboxCacheSync)
            and sync.pending_seed_bytes is not None
        ):
            phases["compile_cache_seeded_bytes"] = float(
                sync.pending_seed_bytes
            )
            sync.pending_seed_bytes = None
        return phases

    def _raise_on_violation(
        self, sandbox: Sandbox, hosts: list[str], bodies: list[dict]
    ) -> None:
        """Map a host-reported typed `violation` into LimitExceededError.
        `continuable` mirrors the executor's runner_restarted: an in-process
        guard (runner alive) leaves the host recyclable; a watchdog kill
        marks it for disposal and a lane-breaker strike."""
        for base, body in zip(hosts, bodies):
            kind = body.get("violation")
            if not kind or not isinstance(kind, str):
                continue
            if kind not in VIOLATION_KINDS:
                # The kind is a metrics label and a wire contract: an
                # out-of-contract executor (version skew, compromise) must
                # not mint unbounded label cardinality or leak junk to
                # clients.
                logger.warning(
                    "sandbox %s reported unknown violation kind %.40r",
                    sandbox.id,
                    kind,
                )
                kind = "unknown"
            continuable = not bool(body.get("runner_restarted"))
            tracing.add_event(
                "limit.violation",
                kind=kind,
                lane=sandbox.chip_count,
                host=base,
                continuable=continuable,
            )
            stderr_tail = str(body.get("stderr", ""))[-500:]
            raise LimitExceededError(
                f"sandbox resource limit exceeded: {kind} "
                f"(sandbox {sandbox.id}); {stderr_tail}".rstrip("; "),
                kind=kind,
                lane=sandbox.chip_count,
                continuable=continuable,
            )

    async def execute_stream(
        self,
        source_code: str | None = None,
        *,
        source_file: str | None = None,
        files: dict[str, str] | None = None,
        timeout: float | None = None,
        env: dict[str, str] | None = None,
        chip_count: int | None = None,
        profile: bool = False,
        executor_id: str | None = None,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        limits: dict | None = None,
        pure: bool = False,
    ):
        """Streaming variant of execute(): an async generator yielding
        ``{"stream": "stdout"|"stderr", "data": str}`` events while the code
        runs (host 0 of the sandbox), then one ``{"result": Result}`` event.

        Infra failures are NOT retried — output already streamed to the
        client cannot be un-streamed, so a silent retry would duplicate it;
        the error surfaces and the client decides (same policy as sessions).

        A declared-pure (`pure=True`) hit serves the final result event
        directly — the full stdout/stderr ride it, exactly as a live
        stream's final event carries them; there is simply nothing to
        stream incrementally because nothing runs.
        """
        env, executor_id = self._normalize_request(env, profile, executor_id)
        usage_tenant = self._usage_tenant(tenant)
        self._check_admission_open()
        # Same quota gate as execute(): a denial surfaces before the first
        # stream event (the HTTP layer still returns a clean 429).
        quota = self._quota_admit(
            usage_tenant, chip_count=chip_count, timeout=timeout
        )
        # Result-memo admission, like execute(): after the quota gate,
        # before the profile arm.
        memo_key, memo_state = self._memo_admission(
            pure,
            executor_id=executor_id,
            profile=profile,
            source_code=source_code,
            source_file=source_file,
            files=files,
            env=env,
            chip_count=chip_count,
            tenant=tenant,
            limits=limits,
        )
        if memo_state == "lookup":
            record = await self.result_memo.lookup(memo_key)
            if record is not None:
                try:
                    result = self._memo_hit_result(record)
                    self._apply_quota_phases(result, quota)
                    self._count_memo_hit(result, usage_tenant)
                finally:
                    self.quotas.release(quota)
                yield {"result": result}
                return
            memo_state = "miss"
        # Auto-profile arming, like execute() (post-admission). Set BEFORE
        # the run task is created: create_task snapshots the contextvars,
        # which is how the marker reaches the pipeline inside run().
        env, auto_profile = self._maybe_auto_profile(env, chip_count, tenant)
        profile_token = _auto_profile_var.set(auto_profile)
        pure_token = _pure_run_var.set(memo_state == "miss")
        queue: asyncio.Queue = asyncio.Queue()
        done = object()

        async def emit(event: dict) -> None:
            queue.put_nowait(event)

        async def run() -> Result:
            try:
                if executor_id is not None:
                    return await self._execute_in_session(
                        executor_id,
                        source_code,
                        source_file=source_file,
                        files=files,
                        timeout=timeout,
                        env=env,
                        chip_count=chip_count,
                        tenant=tenant,
                        priority=priority,
                        deadline=deadline,
                        limits=limits,
                        emit=emit,
                    )
                return await self._execute_once(
                    source_code,
                    source_file=source_file,
                    files=files,
                    timeout=timeout,
                    env=env,
                    chip_count=chip_count,
                    tenant=tenant,
                    priority=priority,
                    deadline=deadline,
                    limits=limits,
                    emit=emit,
                )
            finally:
                queue.put_nowait(done)

        self._inflight += 1
        task = asyncio.create_task(run())
        try:
            while True:
                event = await queue.get()
                if event is done:
                    break
                yield event
            try:
                result = await task
            except CircuitOpenError as e:
                self.metrics.breaker_rejections.inc(chip_count=str(e.lane))
                self.metrics.executions.inc(outcome="rejected")
                self._usage_request(usage_tenant, "rejected")
                raise
            except LimitExceededError as e:
                self._count_violation(e)
                self._usage_request(
                    usage_tenant, "limit_violation", violation=e.kind
                )
                raise
            except SessionLimitError:
                self.metrics.executions.inc(outcome="rejected")
                self._usage_request(usage_tenant, "rejected")
                raise
            except (ExecutorError, SandboxSpawnError):
                self.metrics.executions.inc(outcome="infra_error")
                self._usage_request(usage_tenant, "infra_error")
                raise
        except BaseException:
            task.cancel()
            # The run task owns sandbox/session cleanup; let it finish it.
            await asyncio.gather(task, return_exceptions=True)
            raise
        finally:
            self._inflight -= 1
            self.quotas.release(quota)
            _auto_profile_var.reset(profile_token)
            _pure_run_var.reset(pure_token)
        await self._memo_finish(memo_key, memo_state, result, auto_profile)
        self._apply_quota_phases(result, quota)
        self._count_execution(
            result,
            session=executor_id is not None,
            usage_tenant=usage_tenant,
            lane=self._lane_hint(chip_count),
            tenant=tenant,
        )
        yield {"result": result}

    def _normalize_request(
        self,
        env: dict[str, str] | None,
        profile: bool,
        executor_id: str | None,
    ) -> tuple[dict[str, str] | None, str | None]:
        """Request normalization shared by execute() and execute_stream():
        profile flag → sandbox env; "" executor_id → stateless (proto3
        default); sessions disabled → executor_id accepted and IGNORED
        (reference-parity mode: the -fs reference carried the field but
        ignored it, and clients threading opaque per-request ids under that
        contract must not open one throwaway session per request)."""
        if profile:
            env = {**(env or {}), "APP_JAX_PROFILE": "1"}
        if executor_id == "":
            executor_id = None
        if executor_id is not None and self.config.executor_session_max <= 0:
            executor_id = None
        return env, executor_id

    def _count_execution(
        self,
        result: Result,
        *,
        session: bool,
        usage_tenant: str | None = None,
        lane: int | None = None,
        tenant: str | None = None,
    ) -> None:
        outcome = "ok" if result.exit_code == 0 else "user_error"
        self.metrics.executions.inc(outcome=outcome)
        self._usage_request(usage_tenant, outcome)
        if result.warm:
            self.metrics.warm_hits.inc()
        if session:
            self.metrics.session_executions.inc()
        if (
            lane is not None
            and self.perf.enabled
            and not _trusted_source_var.get()
        ):
            # The perf plane's ONE record point: every LOGICAL request
            # (serial, session, or batched — batch demux fills the same
            # phase keys) feeds the lane×phase baselines and the tenant
            # series. Trusted pre-warm runs stay out: control-plane warmup
            # latency must not poison the baselines tenant traffic is
            # judged against. Independent of the metering kill switch —
            # drift detection is not billing.
            try:
                perf_tenant = self.scheduler.normalize_tenant(tenant)
            except ValueError:
                perf_tenant = None
            self.perf.record_request(lane, result.phases, tenant=perf_tenant)
        for phase, seconds in result.phases.items():
            # ALLOWLIST, not exclusion: phases also carries byte counts,
            # compile-cache/batch coordinates, the trace id, and the usage
            # attribution fields (chip_seconds/device_op_seconds) — PRs 6,
            # 7, and 8 each re-fixed a new non-latency key polluting this
            # histogram; now a key must be a known latency phase to land.
            if phase not in LATENCY_PHASES or not isinstance(
                seconds, (int, float)
            ):
                continue
            self.metrics.phase_seconds.observe(seconds, phase=phase)

    # --------------------------------------------------------------- sessions

    async def _execute_in_session(
        self,
        executor_id: str,
        source_code: str | None = None,
        *,
        source_file: str | None = None,
        files: dict[str, str] | None = None,
        timeout: float | None = None,
        env: dict[str, str] | None = None,
        chip_count: int | None = None,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
        limits: dict | None = None,
        emit=None,
    ) -> Result:
        """Run one request inside the executor_id's session sandbox.

        No retry wrapper: an infra failure means the session's
        sandbox (and its state) is gone — retrying on a replacement would
        silently pretend the state survived. The session is closed and the
        error surfaces; the client decides whether to rebuild.
        """
        if not OBJECT_ID_RE.match(executor_id):
            raise ValueError(
                "invalid executor_id (want ^[0-9a-zA-Z_-]{1,255}$)"
            )
        lane, files, timeout, limits_payload = self._validate_request(
            source_code, source_file, files, timeout, chip_count, limits
        )
        timer = PhaseTimer()
        # Sessions never retry, so one draft covers the whole request.
        # The commit lives in the OUTER finally, not the loop body's: the
        # closed-while-waiting `continue` passes through the inner finally,
        # and committing there would mark the (still empty) draft spent —
        # the retry iteration's real consumption would then never bill.
        usage = self._usage_draft(tenant)
        loop = asyncio.get_running_loop()
        try:
            return await self._session_loop(
                executor_id,
                lane,
                source_code,
                source_file,
                files,
                timeout,
                env,
                timer,
                limits_payload,
                chip_count=chip_count,
                tenant=tenant,
                priority=priority,
                deadline=deadline,
                emit=emit,
                usage=usage,
                loop=loop,
            )
        finally:
            # Attribution commits on EVERY exit — success, violation, or
            # fault: the draft holds whatever the session run measured.
            self.usage.commit(usage)

    async def _session_loop(
        self,
        executor_id: str,
        lane: int,
        source_code,
        source_file,
        files,
        timeout,
        env,
        timer: PhaseTimer,
        limits_payload,
        *,
        chip_count,
        tenant,
        priority,
        deadline,
        emit,
        usage,
        loop,
    ) -> Result:
        while True:
            with timer.phase("queue_wait"):
                session = await self._get_session(
                    executor_id,
                    lane,
                    tenant=tenant,
                    priority=priority,
                    deadline=deadline,
                )
                await session.lock.acquire()
            try:
                if session.closed or self._sessions.get(executor_id) is not session:
                    continue  # closed while we waited for the lock; recreate
                if chip_count is not None and session.lane != lane:
                    raise ValueError(
                        f"session {executor_id} runs on a {session.lane}-chip "
                        f"sandbox; requested chip_count={chip_count}"
                    )
                assert session.sandbox is not None
                session.last_used = loop.time()
                if session.pending_restore is not None:
                    # First turn after a hibernate/migrate: rehydrate the
                    # fresh sandbox from the durable checkpoint before the
                    # user code runs. A wire failure mid-restore raises
                    # ExecutorError below — the session closes and the
                    # RECORD SURVIVES (blob intact), so the retry restores
                    # again; a half-restored sandbox is never served.
                    try:
                        with timer.phase("restore"):
                            restored = await self._restore_session(
                                executor_id, session
                            )
                    except (ExecutorError, SandboxSpawnError):
                        self._end_session_soon(executor_id, session, recycle=False)
                        raise
                    except asyncio.CancelledError:
                        self._end_session_soon(executor_id, session, recycle=False)
                        raise
                    if not restored:
                        # Clean refusal (version skew / corrupt state): the
                        # record is already evicted — close this sandbox
                        # (its workspace may hold the partial upload) and
                        # recreate GENUINELY fresh: the turn still succeeds,
                        # with an honest session_seq=1 reporting state loss.
                        await self._end_session(executor_id, session, recycle=True)
                        continue
                try:
                    result, continuable = await self._run_on_sandbox(
                        session.sandbox,
                        source_code,
                        source_file,
                        files,
                        timeout,
                        env,
                        timer,
                        limits=limits_payload,
                        emit=emit,
                        usage=usage,
                    )
                except LimitExceededError as e:
                    # A violation breaks the session either way: the killed
                    # runner lost its state, and even an in-process guard
                    # leaves the workspace in whatever shape the runaway
                    # left it. Recycle the host only if its runner survived.
                    self._end_session_soon(
                        executor_id, session, recycle=e.continuable
                    )
                    raise
                except (ExecutorError, SandboxSpawnError):
                    # The sandbox is unreachable/broken: session state is
                    # already lost — close it so the id can start fresh.
                    self._end_session_soon(executor_id, session, recycle=False)
                    raise
                except asyncio.CancelledError:
                    # Client disconnect mid-request: the sandbox server is
                    # still running the orphaned script and mutating the
                    # workspace — the session contract is unrecoverable.
                    self._end_session_soon(executor_id, session, recycle=False)
                    raise
                session.last_used = loop.time()
                session.seq += 1
                result.session_seq = session.seq
                if not continuable:
                    # A host's warm runner was killed (timeout) or crashed:
                    # in-process state is gone, so the session contract is
                    # broken. Close it (reported via session_ended); turnover
                    # decides recycle-vs-dispose (the server refuses /reset
                    # mid-rewarm).
                    result.session_ended = True
                    self._end_session_soon(executor_id, session, recycle=True)
                return result
            finally:
                session.lock.release()

    async def _get_session(
        self,
        executor_id: str,
        lane: int,
        *,
        tenant: str | None = None,
        priority: str | None = None,
        deadline: float | None = None,
    ) -> _Session:
        """Fetch or create the id's session. Concurrent first requests wait
        on one creation (the `ready` future) instead of racing spawns.
        Admission params apply to the CREATING request's slot acquisition;
        follow-up requests ride the already-held sandbox."""
        while True:
            session = self._sessions.get(executor_id)
            if session is not None:
                if session.restoring:
                    # The session is mid-restore from its checkpoint: one
                    # turn owns the restore; a second admitted now would
                    # race a double-restore into the same sandbox. Typed,
                    # retryable, NOT session-ending — HTTP 409 +
                    # Retry-After / gRPC UNAVAILABLE + x-session-restoring.
                    raise SessionRestoringError(
                        f"session {executor_id} is restoring from its "
                        "durable checkpoint; retry shortly",
                        executor_id=executor_id,
                        retry_after=1.0,
                    )
                if session.sandbox is None and not session.closed:
                    await asyncio.shield(session.ready)
                if session.closed:
                    # Closed while we waited; loop and re-create against
                    # current table state.
                    continue
                return session
            active = sum(1 for s in self._sessions.values() if not s.closed)
            if active >= self.config.executor_session_max:
                raise SessionLimitError(
                    f"too many active sessions "
                    f"({active}/{self.config.executor_session_max}); retry "
                    "later or close one via DELETE /v1/executors/{id}"
                )
            # A hibernated checkpoint wakes here: the durable record
            # (replica-coherent — a peer may have written it) pins the
            # session's lane and starting seq, and the record itself rides
            # the new session as pending_restore, applied lazily under the
            # session lock on this first turn (phases.restore reports the
            # cost). A corrupt/expired record loads as None and the
            # session recreates fresh with an honest seq reset.
            record = await self.session_store.load(tenant, executor_id)
            if record is not None:
                lane = int(record.get("lane", lane))
            session = _Session(lane=lane, last_used=asyncio.get_running_loop().time())
            session.tenant = tenant
            if record is not None:
                session.pending_restore = record
                session.seq = int(record.get("seq", 0))
            self._sessions[executor_id] = session
            try:
                sandbox = await self._acquire(
                    lane, tenant=tenant, priority=priority, deadline=deadline
                )
            except BaseException as e:
                session.closed = True
                if self._sessions.get(executor_id) is session:
                    del self._sessions[executor_id]
                if isinstance(e, asyncio.CancelledError):
                    # The CREATOR was cancelled (its client disconnected).
                    # Waiters parked on `ready` are unrelated requests —
                    # cancelling them too would drop their connections with
                    # no response; give them a retryable infra error instead.
                    session.ready.set_exception(
                        ExecutorError(
                            f"session {executor_id} creation was cancelled"
                        )
                    )
                else:
                    session.ready.set_exception(e)
                # The future may have no waiters; don't warn about it.
                session.ready.exception()
                raise
            # Move the hold from in_use ("due back to the pool shortly") to
            # session_held ("parked until the session closes"): waiters and
            # the refill logic treat the two very differently.
            self._in_use[lane] = max(0, self._in_use.get(lane, 0) - 1)
            self._session_held[lane] = self._session_held.get(lane, 0) + 1
            self._notify_lane(lane)
            session.sandbox = sandbox
            session.ready.set_result(True)
            logger.info(
                "session %s opened (lane=%d, sandbox=%s)",
                executor_id,
                lane,
                sandbox.id,
            )
            return session

    def _detach_session(
        self, executor_id: str, session: _Session
    ) -> Sandbox | None:
        """Synchronously mark THIS session closed and drop its table entry
        (identity-checked: a caller that waited on a stale lock must not
        tear down a successor session that reused the id). Returns the
        sandbox still needing turnover, or None."""
        if session is None or session.closed:
            return None
        if self._sessions.get(executor_id) is session:
            del self._sessions[executor_id]
        session.closed = True
        return session.sandbox

    async def _drop_session_sandbox(
        self, lane: int, sandbox: Sandbox, *, recycle: bool
    ) -> None:
        """Turn over a detached session's sandbox. The slot stays counted in
        _session_held until the sandbox is actually pooled or disposed —
        freeing it first would let a constrained-lane waiter start a spawn
        that blocks on the physical chip this sandbox still owns (same
        invariant as _release, which decrements _in_use only after turnover).
        extra_free lets the recycle decision see the slot as available."""
        try:
            await self._turnover(sandbox, lane, recycle, extra_free=1)
        finally:
            self._session_held[lane] = max(0, self._session_held.get(lane, 0) - 1)
            self._notify_all_lanes()

    async def _end_session(
        self, executor_id: str, session: _Session, *, recycle: bool
    ) -> bool:
        """Close THIS session (caller holds its lock, or knows it is idle):
        release the lane slot and hand the sandbox to turnover."""
        sandbox = self._detach_session(executor_id, session)
        if sandbox is None:
            return False
        logger.info(
            "session %s closed (lane=%d, sandbox=%s)",
            executor_id,
            session.lane,
            sandbox.id,
        )
        await self._drop_session_sandbox(session.lane, sandbox, recycle=recycle)
        return True

    def _end_session_soon(
        self, executor_id: str, session: _Session, *, recycle: bool
    ) -> None:
        """Close THIS session with turnover off the hot path: detach
        SYNCHRONOUSLY (a new request must not grab the doomed session, and a
        cancelled caller must not lose the teardown to a second cancel),
        then reset/dispose in a tracked background task — the same
        discipline as the stateless release (close() awaits the task)."""
        sandbox = self._detach_session(executor_id, session)
        if sandbox is None:
            return
        logger.info(
            "session %s closed (lane=%d, sandbox=%s)",
            executor_id,
            session.lane,
            sandbox.id,
        )
        task = asyncio.get_running_loop().create_task(
            self._off_request_path(
                self._drop_session_sandbox(session.lane, sandbox, recycle=recycle)
            )
        )
        self._dispose_tasks.add(task)
        task.add_done_callback(self._dispose_tasks.discard)

    # ------------------------------------------------- session durability

    async def _restore_session(self, executor_id: str, session: _Session) -> bool:
        """Rehydrate a fresh sandbox from the session's durable checkpoint
        (caller holds the session lock). Workspace bytes ride the existing
        delta upload path — a fresh sandbox's manifest is empty so every
        file moves, but conditional PUTs and the content-addressed store
        keep the movement to what the sandbox does not already hold — then
        POST /restore ships the interpreter state to every host of the
        slice (host 0's state is the checkpoint; per JAX convention host 0
        owns the singular side effects, and module-level state must agree
        across the SPMD group).

        Returns True when the checkpoint applied (seq continues from the
        record) and False on a CLEAN refusal (bad_state_version /
        corrupt_state): the runner decodes every blob before mutating
        anything, so a refusal leaves it untouched — but the workspace
        upload may have landed, so the caller must still recreate the
        session on a fresh sandbox. The record is evicted here either way
        on refusal. A wire failure raises ExecutorError and KEEPS the
        record: the blob is intact, the next attempt restores again."""
        record = session.pending_restore
        assert record is not None and session.sandbox is not None
        sandbox = session.sandbox
        session.restoring = True
        try:
            self._check_lease(sandbox)
            client = self._http_client()
            hosts = sandbox.host_urls
            workspace = record.get("workspace") or {}
            files = {
                f"/workspace/{rel}": object_id
                for rel, object_id in workspace.items()
            }
            if files:
                await self._upload_inputs(
                    client,
                    hosts,
                    self._transfer_state(sandbox),
                    files,
                    TransferStats(),
                )
            payload = {
                "state": record.get("interp") or {},
                "timeout": self.config.session_snapshot_timeout,
            }
            replies = await asyncio.gather(
                *(
                    self._post_snapshot_op(client, base, "restore", payload, sandbox)
                    for base in hosts
                )
            )
            if all(reply.get("ok") for reply in replies):
                session.pending_restore = None
                session.seq = int(record.get("seq", session.seq))
                self.session_store.restores += 1
                self.metrics.session_restores.inc(outcome="restored")
                logger.info(
                    "session %s restored from checkpoint (seq=%d, files=%d)",
                    executor_id,
                    session.seq,
                    len(files),
                )
                return True
            reason = next(
                (
                    str(reply.get("reason") or "refused")
                    for reply in replies
                    if not reply.get("ok")
                ),
                "refused",
            )
            await self.session_store.delete(session.tenant, executor_id)
            session.pending_restore = None
            self.metrics.session_restores.inc(outcome="fresh")
            logger.warning(
                "session %s checkpoint refused by runner (%s): record "
                "evicted, recreating fresh",
                executor_id,
                reason,
            )
            return False
        finally:
            session.restoring = False

    async def _post_snapshot_op(
        self,
        client: httpx.AsyncClient,
        base: str,
        op: str,
        payload: dict,
        sandbox: Sandbox,
    ) -> dict:
        """One host's /snapshot or /restore round-trip: lease-headered like
        every dispatch, typed-409-aware, and strict about the reply shape —
        any wire or protocol failure is an ExecutorError (the caller's
        session close / record-keep semantics key off that type)."""
        timeout = float(payload.get("timeout", 30.0)) + 10.0
        try:
            resp = await client.post(
                f"{base}/{op}",
                json=payload,
                timeout=timeout,
                headers=self._wire_headers(sandbox),
            )
        except httpx.HTTPError as e:
            raise ExecutorError(f"session {op} to {base} failed: {e}")
        self._raise_if_stale_lease(resp, sandbox)
        if resp.status_code != 200:
            raise ExecutorError(
                f"session {op} to {base} failed: {resp.status_code} "
                f"{resp.text[:200]}"
            )
        try:
            body = resp.json()
        except ValueError:
            raise ExecutorError(f"session {op} to {base} returned a bad body")
        if not isinstance(body, dict):
            raise ExecutorError(f"session {op} to {base} returned a bad body")
        return body

    async def _snapshot_interp(self, sandbox: Sandbox) -> dict:
        """Capture host 0's interpreter state (env deltas, cwd, workspace
        modules' plain-data globals, installed packages) via the runner's
        snapshot op. Raises ExecutorError when the runner refuses (e.g.
        state_too_large) — the hibernate caller degrades gracefully by
        leaving the session parked."""
        client = self._http_client()
        body = await self._post_snapshot_op(
            client,
            sandbox.host_urls[0],
            "snapshot",
            {
                "timeout": self.config.session_snapshot_timeout,
                "max_bytes": self.config.session_snapshot_max_bytes,
            },
            sandbox,
        )
        if not body.get("ok") or not isinstance(body.get("state"), dict):
            raise ExecutorError(
                "session snapshot refused: "
                f"{body.get('reason', 'no state returned')}"
            )
        return body["state"]

    async def _capture_workspace(self, sandbox: Sandbox) -> dict[str, str]:
        """Fold host 0's workspace into content-addressed storage and return
        {rel: object id}. Manifest-sha-negotiated: a file whose sha already
        exists() in storage records the mapping and moves ZERO bytes — the
        common hibernate (unchanged workspace since the last download
        phase) is pure bookkeeping. A legacy executor (no manifest route)
        fails the hibernate instead of checkpointing blind."""
        client = self._http_client()
        base = sandbox.host_urls[0]
        try:
            resp = await client.get(f"{base}/workspace-manifest")
        except httpx.HTTPError as e:
            raise ExecutorError(f"workspace manifest fetch failed: {e}")
        if resp.status_code != 200:
            raise ExecutorError(
                f"workspace manifest fetch failed: {resp.status_code} "
                "(legacy executor binaries cannot hibernate)"
            )
        try:
            entries = resp.json().get("files", {})
        except ValueError:
            raise ExecutorError("workspace manifest fetch returned a bad body")
        if not isinstance(entries, dict):
            raise ExecutorError("workspace manifest fetch returned a bad body")

        async def capture(rel: str, sha) -> tuple[str, str]:
            if isinstance(sha, str) and SHA256_HEX_RE.match(sha):
                if await self.storage.exists(sha):
                    return rel, sha
            _, object_id, _ = await self._download_file(client, base, rel)
            return rel, object_id

        captured = await asyncio.gather(
            *(capture(rel, sha) for rel, sha in sorted(entries.items()))
        )
        return dict(captured)

    async def _hibernate_session(
        self, executor_id: str, session: _Session, *, reason: str = "hibernate"
    ) -> bool:
        """Checkpoint THIS session into the durable store and release its
        chip (caller holds the session lock). Returns True when the session
        ended with its state durable — the sweep's hibernate leg and the
        fence path's migrate leg both ride this. A session that never woke
        from its previous checkpoint (pending_restore still set) just ends:
        the admitted record IS its state, byte-for-byte."""
        sandbox = session.sandbox
        if sandbox is None or session.closed:
            return False
        if session.pending_restore is not None:
            # Parked-but-never-woken: nothing ran since the checkpoint was
            # admitted, so the record already holds the exact state.
            await self._end_session(executor_id, session, recycle=True)
            self.metrics.session_hibernates.inc(outcome=reason)
            return True
        try:
            interp_state = await self._snapshot_interp(sandbox)
            workspace = await self._capture_workspace(sandbox)
        except (ExecutorError, SandboxSpawnError) as e:
            self.metrics.session_hibernates.inc(outcome="failed")
            logger.warning(
                "session %s %s checkpoint failed (%s); leaving it parked",
                executor_id,
                reason,
                e,
            )
            return False
        outcome = await self.session_store.save(
            session.tenant,
            executor_id,
            lane=session.lane,
            seq=session.seq,
            interp_state=interp_state,
            workspace=workspace,
            reason=reason,
        )
        if outcome != "admitted":
            self.metrics.session_hibernates.inc(outcome="failed")
            logger.warning(
                "session %s %s checkpoint not admitted (%s); leaving it "
                "parked",
                executor_id,
                reason,
                outcome,
            )
            return False
        await self._end_session(executor_id, session, recycle=True)
        self.metrics.session_hibernates.inc(outcome=reason)
        logger.info(
            "session %s hibernated (%s): seq=%d, %d workspace files, chip "
            "released to lane %d",
            executor_id,
            reason,
            session.seq,
            len(workspace),
            session.lane,
        )
        return True

    async def _migrate_session(
        self, executor_id: str, session: _Session, reason: str
    ) -> bool:
        """Live-migrate one session off a host being fenced: bounded lock
        wait (an in-flight request finishes its turn first), then the
        hibernate path with reason="migrate" — the durable record restores
        the session behind ANY replica on its next turn, session_seq
        continuous, zero client-visible state loss. Returns False when the
        snapshot cannot be taken in time; the caller falls back to the
        pre-durability force-close."""
        try:
            await asyncio.wait_for(
                session.lock.acquire(),
                timeout=self.config.session_snapshot_timeout,
            )
        except asyncio.TimeoutError:
            return False
        try:
            if session.closed or self._sessions.get(executor_id) is not session:
                return True  # already gone — nothing to lose
            ok = await self._hibernate_session(
                executor_id, session, reason="migrate"
            )
            self.metrics.session_migrations.inc(
                outcome="saved" if ok else "forced"
            )
            return ok
        finally:
            session.lock.release()

    def _account_idle(self, session: _Session, now: float) -> None:
        """Fold this session's parked-idle time since the last sweep into
        the idle-chip-seconds counter (satellite: make the cost hibernation
        kills VISIBLE). Busy sessions reset the watermark — time under the
        lock is work, not waste."""
        if session.lock.locked() or session.sandbox is None:
            session.idle_accounted = now
            return
        since = max(session.last_used, session.idle_accounted)
        delta = max(0.0, now - since)
        if delta <= 0.0:
            return
        chips = max(1, session.lane or 1)
        self._idle_chip_seconds += delta * chips
        self.metrics.session_idle_chip_seconds.inc(delta * chips)
        session.idle_accounted = now

    def list_sessions(self) -> list[dict]:
        """Live sessions for GET /v1/executors: id, lane, idle seconds,
        whether a request is in flight, and requests served. Sessions still
        spawning their sandbox are included (status "spawning") — they count
        toward executor_session_max, so hiding them would make the list
        contradict the cap's own error message."""
        now = asyncio.get_running_loop().time()
        return [
            {
                "executor_id": executor_id,
                "chip_count": session.lane,
                "idle_s": round(max(0.0, now - session.last_used), 3),
                "busy": session.lock.locked(),
                "requests": session.seq,
                "status": "ready" if session.sandbox is not None else "spawning",
            }
            for executor_id, session in self._sessions.items()
            if not session.closed
        ]

    async def close_session(
        self, executor_id: str, *, tenant: str | None = None
    ) -> bool:
        """Explicitly end a session (DELETE /v1/executors/{id}). Waits for an
        in-flight request on the session to finish first. Returns False if no
        such session exists. The durable checkpoint (if any) is evicted too:
        an explicit close means the client is done — the record must not
        resurrect the session on an id reuse."""
        session = self._sessions.get(executor_id)
        if session is None or session.closed:
            # No live session — but a HIBERNATED one may exist as a record
            # only. Deleting it IS the close; report it as one.
            return await self.session_store.delete(tenant, executor_id)
        await self.session_store.delete(session.tenant or tenant, executor_id)
        if session.sandbox is None:
            try:
                await asyncio.shield(session.ready)
            except asyncio.CancelledError:
                raise  # the CALLER was cancelled — do not swallow it
            except Exception:  # noqa: BLE001 — creation failed = closed
                return False
        async with session.lock:
            # `closed` may have flipped while we waited for the lock (e.g.
            # the in-flight request hit runner_restarted and ended the
            # session itself); _end_session's identity check then keeps a
            # successor session under the same id untouched.
            return await self._end_session(executor_id, session, recycle=True)

    async def sweep_sessions(self) -> int:
        """Close sessions idle past the configured timeout. An idle session
        parks a sandbox (on TPU lanes: physical chips) indefinitely; the
        sweep bounds that at executor_session_idle_timeout.

        With the durability plane live, a cheaper bound fires FIRST: a
        session idle past session_hibernate_idle_seconds is checkpointed
        and its chip released (the autoscaler sees the reclaimed supply),
        instead of waiting for the hard expiry. A failed hibernate leaves
        the session parked — the plain idle close still bounds it. The
        sweep also folds parked-idle time into the idle-chip-seconds
        counter, and TTL-prunes durable records nobody woke."""
        loop = asyncio.get_running_loop()
        idle_cutoff = self.config.executor_session_idle_timeout
        hibernate_after = (
            self.config.session_hibernate_idle_seconds
            if self.session_store.enabled
            else 0.0
        )
        closed = 0
        for executor_id, session in list(self._sessions.items()):
            if session.closed or session.sandbox is None:
                continue
            self._account_idle(session, loop.time())
            if session.lock.locked():  # request in flight
                continue
            idle = loop.time() - session.last_used
            if hibernate_after > 0 and idle >= hibernate_after:
                async with session.lock:
                    # Re-check under the lock: a request may have slipped in.
                    if (
                        self._sessions.get(executor_id) is session
                        and not session.closed
                        and loop.time() - session.last_used >= hibernate_after
                    ):
                        if await self._hibernate_session(executor_id, session):
                            closed += 1
                            continue
                if self._sessions.get(executor_id) is not session or session.closed:
                    continue
                idle = loop.time() - session.last_used
            if idle < idle_cutoff:
                continue
            async with session.lock:
                # Re-check under the lock: a request may have slipped in.
                if (
                    self._sessions.get(executor_id) is session
                    and loop.time() - session.last_used >= idle_cutoff
                ):
                    if await self._end_session(executor_id, session, recycle=True):
                        logger.info("session %s expired (idle)", executor_id)
                        closed += 1
        try:
            self.session_store.sweep_expired()
        except Exception:  # noqa: BLE001 — pruning must not break the sweep
            logger.warning("session record TTL sweep failed", exc_info=True)
        return closed

    def start_session_sweeper(self, interval: float | None = None) -> asyncio.Task | None:
        """Run sweep_sessions periodically until close(). Default cadence:
        a quarter of the idle timeout, so expiry lands within ~125% of it —
        tightened to half the hibernate threshold when the durability plane
        is live, so a hibernation lands within ~150% of its own bound too."""
        if self.config.executor_session_max <= 0:
            return None
        if interval is None:
            interval = max(1.0, self.config.executor_session_idle_timeout / 4)
            if (
                self.session_store.enabled
                and self.config.session_hibernate_idle_seconds > 0
            ):
                interval = min(
                    interval,
                    max(1.0, self.config.session_hibernate_idle_seconds / 2),
                )
        return self._start_sweeper(self.sweep_sessions, interval, "session sweep")

    def _start_sweeper(self, sweep, interval: float, label: str) -> asyncio.Task | None:
        """Shared periodic-sweep loop: run `sweep` every `interval` seconds
        until close(), logging (not dying on) failures."""
        if interval <= 0:
            return None

        async def sweeper() -> None:
            while not self._closed:
                await asyncio.sleep(interval)
                try:
                    await sweep()
                except Exception:  # noqa: BLE001 — keep sweeping
                    logger.exception("%s failed", label)

        task = asyncio.get_running_loop().create_task(sweeper())
        self._fill_tasks.add(task)  # cancelled/awaited by close()
        task.add_done_callback(self._fill_tasks.discard)
        return task

    async def _call_host(
        self,
        client: httpx.AsyncClient,
        index: int,
        base: str,
        payload: dict,
        timeout: float,
        sandbox: Sandbox,
        emit,
    ) -> dict:
        """One host's /execute round-trip inside its own trace span. The
        `traceparent` for the wire hop is read back out of the contextvar by
        `_trace_headers` (keeping `_post_execute`'s signature stable — tests
        monkeypatch it), and the sandbox's in-process phase timings come
        back in the response's `trace` block and graft in as child spans."""
        with self.tracer.span(
            "executor.execute", attributes={"host": base, "host_index": index}
        ) as span:
            if emit is not None and index == 0:
                body = await self._post_execute_stream(
                    client, base, payload, timeout, sandbox, emit
                )
            else:
                body = await self._post_execute(
                    client, base, payload, timeout, sandbox
                )
            self._graft_sandbox_trace(span, base, body)
            return body

    def _trace_headers(self) -> dict | None:
        """Headers propagating the current span's context to a sandbox (the
        executor server echoes the value and stamps its phase timings into a
        `trace` block). None when there is nothing to propagate."""
        return tracing.trace_headers()

    def _graft_sandbox_trace(self, span, base: str, body) -> None:
        """Fold a sandbox's reported per-phase timings (install/exec/collect,
        measured in-process by executor/server.cpp) into the trace as
        children of this host's executor.execute span. Offsets are relative
        to the sandbox's own request start and are applied to THIS span's
        start time, so cross-process clock skew never enters the math (the
        child spans are guaranteed to nest inside the HTTP call window)."""
        if not span.recording:
            return
        # An entry may name its `parent`, an earlier entry of the same
        # block (the handler's stages inside install/exec/collect, the warm
        # runner's inside exec): it then hangs under that span.
        grafted: dict[str, str | None] = {}
        for name, offset, duration, parent in self._trace_block_entries(body):
            grafted[name] = self.tracer.record_span(
                f"sandbox.{name}"[:64],
                trace_id=span.trace_id,
                parent_id=grafted.get(parent) or span.span_id,
                start_unix=span.start_unix + max(0.0, offset),
                duration_s=duration,
                attributes={"host": base},
            )

    @staticmethod
    def _trace_block_entries(body):
        """The well-formed entries of a sandbox reply's `trace` block, as
        (name, start_offset_s, duration_s, parent or None)."""
        block = body.get("trace") if isinstance(body, dict) else None
        entries = block.get("spans") if isinstance(block, dict) else None
        if not isinstance(entries, list):
            return
        for entry in entries:
            if not isinstance(entry, dict):
                continue
            name = entry.get("name")
            offset = entry.get("start_offset_s")
            duration = entry.get("duration_s")
            if (
                isinstance(name, str)
                and name
                and isinstance(offset, (int, float))
                and isinstance(duration, (int, float))
            ):
                yield name, float(offset), float(duration), entry.get("parent")

    async def _post_execute_stream(
        self,
        client: httpx.AsyncClient,
        base: str,
        payload: dict,
        timeout: float,
        sandbox: Sandbox,
        emit,
    ) -> dict:
        """POST /execute/stream: NDJSON events — {"stream","data"} chunks
        passed to `emit` as they arrive, then a final object that is the
        complete /execute response body (returned)."""
        final: dict | None = None
        try:
            async with client.stream(
                "POST",
                f"{base}/execute/stream",
                json=payload,
                headers=self._wire_headers(sandbox),
                timeout=httpx.Timeout(timeout + 30.0, read=timeout + 30.0),
            ) as resp:
                if resp.status_code == 403:
                    # Client path error (e.g. source_file escapes the
                    # workspace) — same mapping as _post_execute, so the
                    # streamed surface returns 400, not a 502 infra error.
                    text = (await resp.aread()).decode(errors="replace")
                    try:
                        message = json.loads(text).get("error", "forbidden path")
                    except ValueError:
                        message = "forbidden path"
                    raise ValueError(message)
                if resp.status_code != 200:
                    text = (await resp.aread()).decode(errors="replace")
                    if resp.status_code == 409:
                        # The typed stale-lease refusal, stream flavor.
                        try:
                            body = json.loads(text)
                        except ValueError:
                            body = None
                        if (
                            isinstance(body, dict)
                            and body.get("error") == "stale_lease"
                        ):
                            raise StaleLeaseError(
                                f"sandbox {sandbox.id} rejected a stale "
                                f"lease claim (held {body.get('held')!r}, "
                                f"offered {body.get('offered')!r})"
                            )
                    # Refusal before any run — exempt from fault billing
                    # like _post_execute's non-200 path.
                    error = ExecutorError(
                        f"sandbox {sandbox.id} ({base}) /execute/stream -> "
                        f"{resp.status_code}: {text[:500]}"
                    )
                    error.device_may_have_run = False
                    raise error
                buffer = ""
                async for text in resp.aiter_text():
                    buffer += text
                    while "\n" in buffer:
                        line, buffer = buffer.split("\n", 1)
                        if not line.strip():
                            continue
                        try:
                            event = json.loads(line)
                        except ValueError as e:
                            raise ExecutorError(
                                f"sandbox {sandbox.id} ({base}) sent a "
                                f"malformed stream event: {e}"
                            )
                        if "stream" in event:
                            await emit(
                                {
                                    "stream": event.get("stream", ""),
                                    "data": event.get("data", ""),
                                }
                            )
                        else:
                            final = event
        except httpx.HTTPError as e:
            raise ExecutorError(f"sandbox {sandbox.id} ({base}) unreachable: {e}")
        if final is None:
            raise ExecutorError(
                f"sandbox {sandbox.id} ({base}) stream ended without a result"
            )
        if "error" in final and "exit_code" not in final:
            raise ExecutorError(
                f"sandbox {sandbox.id} ({base}): {final['error']}"
            )
        return final

    async def _post_execute(
        self,
        client: httpx.AsyncClient,
        base: str,
        payload: dict,
        timeout: float,
        sandbox: Sandbox,
    ) -> dict:
        try:
            resp = await client.post(
                f"{base}/execute",
                json=payload,
                headers=self._wire_headers(sandbox),
                timeout=httpx.Timeout(timeout + 30.0),
            )
        except httpx.HTTPError as e:
            raise ExecutorError(f"sandbox {sandbox.id} ({base}) unreachable: {e}")
        if resp.status_code == 403:
            raise ValueError(resp.json().get("error", "forbidden path"))
        # The executor's typed stale-lease refusal: this claim's generation
        # was fenced and a successor holds the chips — never retried
        # against this host (the retry ladder acquires a fresh sandbox).
        self._raise_if_stale_lease(resp, sandbox)
        if resp.status_code != 200:
            # A non-200 from /execute is a refusal BEFORE any run (the
            # executor returns 200 even for violations and timeouts):
            # usage billing must not charge device time for it.
            error = ExecutorError(
                f"sandbox {sandbox.id} ({base}) /execute -> {resp.status_code}: "
                f"{resp.text[:500]}"
            )
            error.device_may_have_run = False
            raise error
        try:
            return resp.json()
        except ValueError as e:
            raise ExecutorError(
                f"sandbox {sandbox.id} ({base}) returned malformed JSON: {e}"
            )

    def _cache_sync(self, sandbox: Sandbox) -> SandboxCacheSync:
        """The sandbox's compile-cache sync state, riding in `meta` like the
        transfer manifests (generation turnover preserves the cache dir, so
        unlike those this state is never reset)."""
        sync = sandbox.meta.get("compile_cache")
        if not isinstance(sync, SandboxCacheSync):
            # harvest_allowed is re-evaluated INSIDE the sync at every
            # admission: on a shared cache dir the revoking tenant run is
            # on a different sandbox, so the revocation can land while
            # this sandbox's harvest is mid-flight awaiting the network.
            sync = SandboxCacheSync(
                self.compile_cache,
                harvest_allowed=self._harvest_still_trusted,
            )
            sandbox.meta["compile_cache"] = sync
        return sync

    async def _off_request_path(self, coro):
        """Run background pool work (refills, releases, session drops) with
        the trace context CLEARED: asyncio tasks snapshot their creator's
        contextvars, so a refill or post-response release created inside a
        request would otherwise keep attaching late spans/events to that
        request's (long-closed) trace — making its span set
        nondeterministic. Inside these tasks, child-span factories see no
        current span and no-op; work awaited ON a request path still
        traces normally."""
        tracing.current_span_var.set(None)
        return await coro

    async def _seed_compile_cache(
        self, sandbox: Sandbox, *, traced: bool = True
    ) -> None:
        """Push the fleet hot set into a fresh sandbox's cache dir (spawn
        path). Entries the host already holds move no bytes; a legacy
        executor (404 on the manifest route) is remembered and never probed
        again. Failures cost a recompile, never a spawn. The span is a
        child of the requesting trace for direct (in-request) spawns;
        background refills pass traced=False (a span finishing after its
        request's trace was read would make the span set nondeterministic)."""
        if not self.compile_cache.enabled:
            return
        sync = self._cache_sync(sandbox)
        try:
            with (
                self.tracer.span(
                    "compile_cache.seed", attributes={"sandbox": sandbox.id}
                )
                if traced
                else tracing.NOOP
            ) as span:
                stats = await sync.seed(self._http_client(), sandbox.host_urls)
                span.set_attribute("bytes_pushed", stats.pushed_bytes)
                span.set_attribute("files_pushed", stats.pushed_files)
                span.set_attribute("files_skipped", stats.skipped_files)
        except Exception:  # noqa: BLE001 — seeding is strictly best-effort
            logger.warning(
                "compile-cache seed failed for %s", sandbox.id, exc_info=True
            )
            return
        self.metrics.compile_cache_bytes.inc(
            stats.pushed_bytes, direction="seed"
        )
        self.metrics.compile_cache_files.inc(
            stats.pushed_files, direction="seed"
        )
        self.metrics.compile_cache_skipped_files.inc(
            stats.skipped_files, direction="seed"
        )
        # The first request served by this sandbox reports what seeding it
        # cost (Result.phases compile_cache_seeded_bytes).
        sync.pending_seed_bytes = stats.pushed_bytes
        if stats.pushed_files:
            logger.info(
                "seeded %d compile-cache entries (%d bytes) into %s",
                stats.pushed_files,
                stats.pushed_bytes,
                sandbox.id,
            )

    def _compile_cache_dir_scope(self) -> str:
        """The backend's trust statement about who can write a sandbox's
        cache dir (see SandboxBackend.compile_cache_dir_scope). Fail
        closed: a backend that declares nothing (or something unknown) is
        treated as "external" and never harvested."""
        scope = getattr(self.backend, "compile_cache_dir_scope", None)
        return scope if scope in ("private", "shared") else "external"

    def _harvest_still_trusted(self) -> bool:
        """Control-plane-level harvest trust AS OF NOW — the cache-dir
        scopes a sandbox's own taint can't speak for. Handed to every
        SandboxCacheSync so it is re-evaluated mid-harvest at each
        admission (the revoking event — a tenant run on a DIFFERENT
        sandbox sharing the dir — can land while a harvest is awaiting
        the network)."""
        scope = self._compile_cache_dir_scope()
        if scope == "external":
            return False
        return not (scope == "shared" and self._shared_cache_tainted)

    async def _harvest_compile_cache(self, sandbox: Sandbox) -> None:
        """Pull never-seen compiled kernels out of a sandbox's cache dir
        (turnover/teardown path, off the request hot path). The manifest's
        shas are negotiated against the store first, so a sandbox that only
        used seeded kernels moves zero bytes.

        Provenance-gated on the backend's cache-dir scope: with a PRIVATE
        dir, only sandboxes that have NEVER run tenant code (untainted —
        in practice the pre-warm runs) are harvested; with a SHARED dir
        (local backend default — the fleet-constant path jax's key
        hashing demands) any tenant run anywhere taints the whole dir,
        so harvest stops control-plane-wide at the first tenant execute
        (the backend reports a dir that was not empty at start as external,
        so the trusted-only epoch is airtight); an EXTERNAL dir (k8s
        PVC/hostPath, or a local dir with a past) is writable by
        parties this control plane never sees and is never harvested. A
        tainted dir is attacker-writable and its artifacts are serialized
        executables every seeded sandbox would run, so it gets no harvest
        HTTP at all — not even the manifest probe."""
        if not self.compile_cache.enabled:
            return
        if not self._harvest_still_trusted():
            return
        sync = self._cache_sync(sandbox)
        if sync.tainted:
            return
        try:
            with self.tracer.span(
                "compile_cache.harvest", attributes={"sandbox": sandbox.id}
            ) as span:
                stats = await sync.harvest(
                    self._http_client(), sandbox.host_urls
                )
                span.set_attribute("bytes_harvested", stats.new_bytes)
                span.set_attribute("files_harvested", stats.new_files)
                span.set_attribute("files_known", stats.known_files)
                span.set_attribute("conflicts", stats.conflicts)
        except Exception:  # noqa: BLE001 — harvest is strictly best-effort
            logger.warning(
                "compile-cache harvest failed for %s", sandbox.id,
                exc_info=True,
            )
            return
        self.metrics.compile_cache_bytes.inc(
            stats.new_bytes, direction="harvest"
        )
        self.metrics.compile_cache_files.inc(
            stats.new_files, direction="harvest"
        )
        self.metrics.compile_cache_skipped_files.inc(
            stats.known_files, direction="harvest"
        )
        self.metrics.compile_cache_conflicts.inc(stats.conflicts)
        if stats.new_files:
            logger.info(
                "harvested %d new compile-cache entries (%d bytes) from %s",
                stats.new_files,
                stats.new_bytes,
                sandbox.id,
            )

    def _transfer_state(self, sandbox: Sandbox) -> SandboxTransfer:
        """The sandbox's per-host manifest cache, riding in `meta` so it
        follows the sandbox through pool recycles and session parking."""
        state = sandbox.meta.get("transfer")
        if not isinstance(state, SandboxTransfer):
            state = SandboxTransfer(
                enabled=self.config.transfer_manifest_enabled,
                # What the backend that spawned the sandbox knows: its
                # hosts see the storage directory (backends/local.py).
                shares_storage=bool(sandbox.meta.get("shares_storage")),
            )
            sandbox.meta["transfer"] = state
        return state

    async def _upload_inputs(
        self,
        client: httpx.AsyncClient,
        hosts: list[str],
        transfer: SandboxTransfer,
        files: dict[str, str],
        stats: TransferStats,
    ) -> None:
        """The upload phase, delta-based: validate each DISTINCT object id
        exactly once (concurrently — `files` can map many paths to one id),
        then per host skip every path whose (rel, sha) already matches the
        manifest and stream only the rest. A session turn whose input files
        are unchanged uploads nothing at all."""
        rels: dict[str, str] = {}
        for path, object_id in files.items():
            rel = normalize_workspace_path(path)
            if rel.startswith("workspace/"):
                rel = rel[len("workspace/") :]
            rels[rel] = object_id
        unique_ids = sorted(set(rels.values()))

        async def sized(object_id: str) -> int:
            # size() doubles as the existence check — one stat per distinct
            # id covers both validation and byte accounting.
            try:
                return await self.storage.size(object_id)
            except StorageObjectNotFound:
                raise ValueError(f"unknown file object id: {object_id}") from None

        sizes = dict(
            zip(
                unique_ids,
                await asyncio.gather(*(sized(i) for i in unique_ids)),
            )
        )
        manifests = [transfer.host(base) for base in hosts]
        # State in doubt (runner killed mid-request earlier, or a failed
        # earlier resync): one manifest fetch per host — concurrently, like
        # the uploads — beats full re-uploads. Failure just leaves the
        # full-upload fallback.
        await asyncio.gather(
            *(
                self._resync_manifest(client, base, manifest)
                for base, manifest in zip(hosts, manifests)
                if manifest.entries is None and manifest.supports is not False
            )
        )
        uploads: list[tuple[str, str, str, HostManifest]] = []
        for base, manifest in zip(hosts, manifests):
            to_upload, skipped = manifest.delta(rels)
            stats.upload_skipped_files += len(skipped)
            stats.upload_skipped_bytes += sum(
                sizes[object_id] for object_id in skipped.values()
            )
            uploads.extend(
                (base, rel, object_id, manifest)
                for rel, object_id in to_upload.items()
            )
        # Input files never fully buffer in control-plane memory (a multi-GB
        # session file times N hosts would otherwise blow the heap).
        copied = await asyncio.gather(
            *(
                self._upload_file(client, base, rel, object_id, manifest)
                for base, rel, object_id, manifest in uploads
            )
        )
        stats.upload_files += len(uploads)
        stats.upload_bytes += sum(
            sizes[object_id] for _, _, object_id, _ in uploads
        )
        stats.upload_copied_files += sum(1 for by_host in copied if by_host)
        stats.upload_copied_bytes += sum(
            sizes[object_id]
            for (_, _, object_id, _), by_host in zip(uploads, copied)
            if by_host
        )

    async def _resync_manifest(
        self, client: httpx.AsyncClient, base: str, manifest: HostManifest
    ) -> None:
        """Recover a host's manifest from GET /workspace-manifest. A 404
        proves an old binary (remembered; never probed again); any other
        failure leaves the manifest unknown — full uploads now, retry on the
        next request."""
        try:
            resp = await client.get(f"{base}/workspace-manifest")
        except httpx.HTTPError:
            return
        if resp.status_code == 404:
            manifest.mark_legacy()
            return
        if resp.status_code != 200:
            return
        try:
            entries = resp.json().get("files", {})
        except ValueError:
            return
        if isinstance(entries, dict):
            manifest.resynced(
                {
                    rel: sha
                    for rel, sha in entries.items()
                    if isinstance(sha, str) and SHA256_HEX_RE.match(sha)
                }
            )

    async def _download_changed(
        self,
        client: httpx.AsyncClient,
        hosts: list[str],
        transfer: SandboxTransfer,
        bodies: list[dict],
        stats: TransferStats,
    ) -> dict[str, str]:
        """The download phase, hash-negotiated: each host's reported files
        fold into its manifest cache, then every changed path is fetched
        exactly once — host 0 wins path conflicts (it is the coordinator
        and, per JAX convention, the process that does singular side
        effects), and a path whose sha already exists() in storage records
        the mapping without moving bytes. A host answering without hashes
        (old binary) is marked legacy and downloads fully, exactly as the
        pre-manifest control plane did."""
        winner: dict[str, tuple[str, str | None]] = {}
        for base, body in zip(hosts, bodies):
            entries, has_hashes = parse_files_field(body.get("files", []))
            manifest = transfer.host(base)
            if not has_hashes:
                manifest.mark_legacy()
            else:
                deleted = body.get("deleted") or []
                manifest.apply_execute_response(
                    entries, deleted if isinstance(deleted, list) else []
                )
            for rel, sha in entries:
                winner.setdefault(rel, (base, sha))
        changed = await asyncio.gather(
            *(
                # The kill switch disables BOTH halves of the negotiation:
                # with transfer off, reported shas are ignored and every
                # changed file downloads fully, like the upload side.
                self._fetch_changed(
                    client, base, rel, sha if transfer.enabled else None, stats
                )
                for rel, (base, sha) in winner.items()
            )
        )
        return {f"/workspace/{rel}": object_id for rel, object_id in changed}

    async def _fetch_changed(
        self,
        client: httpx.AsyncClient,
        base: str,
        rel: str,
        sha: str | None,
        stats: TransferStats,
    ) -> tuple[str, str]:
        if sha is not None:
            try:
                size = await self.storage.size(sha)
            except (StorageObjectNotFound, ValueError):
                size = None
            if size is not None:
                # Hash negotiation: storage already holds these exact bytes
                # (the object id IS the sha) — record the mapping, move none.
                stats.download_skipped_files += 1
                stats.download_skipped_bytes += size
                return rel, sha
        rel, object_id, size = await self._download_file(client, base, rel)
        stats.download_files += 1
        stats.download_bytes += size
        return rel, object_id

    async def _upload_file(
        self,
        client: httpx.AsyncClient,
        base: str,
        rel: str,
        object_id: str,
        manifest: HostManifest,
    ) -> bool:
        """One input file into one host's workspace. True where the host's
        own server copied it from the storage directory, False where the
        bytes were streamed to it."""
        # A real content sha on a host that speaks the manifest protocol:
        # the id is a claim both sides can check. Old binaries and legacy
        # opaque ids get the plain streamed PUT.
        negotiable = manifest.supports is not False and bool(
            SHA256_HEX_RE.match(object_id)
        )
        if negotiable and manifest.copies:
            # The object's name IS the sha256 of its bytes, and the host
            # sees it: the server copies it inside the kernel.
            try:
                resp = await client.post(
                    f"{base}/copy-from-storage/workspace/{rel}",
                    headers={"X-Storage-Object": object_id},
                    # The answer comes when the whole file is copied: no
                    # byte on the wire meanwhile to restart the clock.
                    timeout=httpx.Timeout(30.0, read=300.0),
                )
            except httpx.HTTPError as e:
                raise ExecutorError(f"upload of {rel} failed: {e}")
            if resp.status_code in (200, 304, 413):
                self._upload_answered(resp, rel, object_id, manifest)
                return True
            # The object or the directory is not visible from there (or the
            # binary is from before the route): the declaration was wrong,
            # for this host's life. Stream this file.
            manifest.copies = False
        # `If-None-Match: <sha of the body being sent>` lets the server skip
        # the disk write (304) when the file already holds these bytes —
        # e.g. a path re-uploaded after the control plane lost its cache.
        headers = {"If-None-Match": object_id} if negotiable else {}

        async def stream():
            async with self.storage.reader(object_id) as reader:
                while True:
                    data = await reader.read(1 << 20)
                    if not data:
                        return
                    yield data

        try:
            resp = await client.put(
                f"{base}/workspace/{rel}", content=stream(), headers=headers
            )
        except httpx.HTTPError as e:
            raise ExecutorError(f"upload of {rel} failed: {e}")
        self._upload_answered(resp, rel, object_id, manifest)
        return False

    @staticmethod
    def _upload_answered(
        resp: httpx.Response, rel: str, object_id: str, manifest: HostManifest
    ) -> None:
        """What the host's answer to an upload means, streamed or copied."""
        if resp.status_code == 304:
            # Conditional hit: the host proved it already has this content.
            manifest.record_upload(rel, object_id)
            return
        if resp.status_code == 413:
            # The executor's workspace disk quota refused the upload: a
            # typed, deterministic violation (the host itself is fine —
            # the PUT was rejected before any damage).
            raise LimitExceededError(
                f"upload of {rel} exceeds the workspace disk quota",
                kind="disk_quota",
                continuable=True,
            )
        if resp.status_code != 200:
            raise ExecutorError(
                f"upload of {rel} failed: {resp.status_code} {resp.text[:200]}"
            )
        try:
            sha = resp.json().get("sha256")
        except ValueError:
            sha = None
        manifest.record_upload(rel, sha)

    async def _download_file(
        self, client: httpx.AsyncClient, base: str, rel: str
    ) -> tuple[str, str, int]:
        # Chunk-wise all the way: the executor serves the body via
        # sendfile(2) (never buffering the file in ITS memory) and the
        # control plane hashes it into Storage in bounded 1 MiB reads —
        # a multi-GB artifact never materializes whole on either side.
        try:
            async with self.storage.writer() as writer:
                async with client.stream("GET", f"{base}/workspace/{rel}") as resp:
                    if resp.status_code != 200:
                        raise ExecutorError(
                            f"download of {rel} failed: {resp.status_code}"
                        )
                    async for chunk in resp.aiter_bytes(1 << 20):
                        await writer.write(chunk)
        except httpx.HTTPError as e:
            raise ExecutorError(f"download of {rel} failed: {e}")
        assert writer.hash is not None
        return rel, writer.hash, writer.size

    def _release_soon(self, sandbox: Sandbox, lane: int, recyclable: bool) -> None:
        """Schedule the post-request release off the hot path (tracked so
        close() awaits it). `_releasing` is bumped SYNCHRONOUSLY — before
        the task first runs — so a next request arriving in the same event-
        loop window already sees this hold as supply-in-transit, not load."""
        self._releasing[lane] = self._releasing.get(lane, 0) + 1
        task = asyncio.get_running_loop().create_task(
            self._off_request_path(self._release(sandbox, lane, recyclable))
        )
        self._dispose_tasks.add(task)
        task.add_done_callback(self._dispose_tasks.discard)

    async def _release(self, sandbox: Sandbox, lane: int, recyclable: bool) -> None:
        """Post-request sandbox release for pool-acquired sandboxes: turnover
        plus the in-use bookkeeping waiters key off."""
        try:
            await self._turnover(sandbox, lane, recyclable)
        finally:
            self._releasing[lane] = max(0, self._releasing.get(lane, 0) - 1)
            self._in_use[lane] = max(0, self._in_use.get(lane, 0) - 1)
            self._notify_lane(lane)

    async def _turnover(
        self, sandbox: Sandbox, lane: int, recyclable: bool, *, extra_free: int = 0
    ) -> None:
        """Sandbox turnover (runs off the hot path): recycle the warm device
        process back into the pool when safe — the TPU lease survives and
        the next request pops a hot sandbox in milliseconds — else dispose
        it and refill the lane (VERDICT r2 #1).

        Off the request's path, but ON the chip holder's cycle: a queued
        turn waits for it. So it is a trace of its own, `pool.turnover`,
        sampled like a request, and its length rides on the recycled
        sandbox (`meta["turnover_s"]`, beside `pooled_at`) into the next
        turn's `phases.turnover_before`."""
        started = self.tracer.clock()
        with self.tracer.start_trace(
            "pool.turnover", attributes={"sandbox": sandbox.id, "lane": lane}
        ) as span:
            recycled = await self._turnover_traced(
                sandbox, lane, recyclable, extra_free, started
            )
            span.set_attribute(
                "outcome", "recycled" if recycled else "disposed"
            )

    async def _turnover_traced(
        self,
        sandbox: Sandbox,
        lane: int,
        recyclable: bool,
        extra_free: int,
        started: float,
    ) -> bool:
        recycled: Sandbox | None = None
        # Harvest BEFORE reset/dispose: kernels this generation compiled
        # must reach the fleet store even when the sandbox itself is about
        # to die. A broken/unreachable sandbox just yields an empty harvest.
        await self._harvest_compile_cache(sandbox)
        try:
            if (
                recyclable
                and not self._closed
                and self.config.executor_reuse_sandboxes
                # A fenced host never recycles: its lease is revoked and
                # its process is being (or has been) disposed — pooling it
                # would hand requests a host whose every dispatch dies on
                # the stale-lease check.
                and not sandbox.meta.get("lease_fenced")
                # Recycle only while the pool is short of SUPPLY: under a
                # concurrency burst on an unconstrained lane, many
                # in-flight sandboxes release at once and the surplus must
                # be disposed, or live processes would grow past the lane
                # target and stay there. Wedged pooled hosts don't count —
                # a healthy recycle must not be disposed because zombies
                # occupy the deque.
                and self._pool_supply(lane) < self._lane_target(lane, extra_free=extra_free)
            ):
                with self.tracer.span("sandbox.reset") as reset_span:
                    try:
                        recycled = await self.backend.reset(sandbox)
                    except Exception:  # noqa: BLE001 — recycle is best-effort
                        logger.exception("sandbox %s reset failed", sandbox.id)
                    # The executor's own stages of /reset (runner_reset,
                    # wipe), one block per host, under the backend call.
                    client_s = sandbox.meta.pop("reset_client_s", None)
                    if reset_span.recording and client_s is not None:
                        self.tracer.record_span(
                            "sandbox.reset_client",
                            trace_id=reset_span.trace_id,
                            parent_id=reset_span.span_id,
                            start_unix=reset_span.start_unix,
                            duration_s=client_s,
                        )
                    blocks = sandbox.meta.pop("reset_trace", None) or ()
                    for base, block in zip(sandbox.host_urls, blocks):
                        self._graft_sandbox_trace(
                            reset_span, base, {"trace": block}
                        )
                if recycled is not None:
                    # /reset wiped every host's workspace: the manifest
                    # cache restarts empty-known for the next generation
                    # (a stale entry would wrongly skip an upload).
                    self._transfer_state(recycled).reset()
                # Concurrent releases race the pool-short check above (all
                # pass it before any appends) — re-check after the await and
                # dispose the surplus, or a burst would leave the pool
                # permanently over target.
                if recycled is not None and not (
                    self._pool_supply(lane)
                    < self._lane_target(lane, extra_free=extra_free)
                    and not self._closed
                ):
                    recycled = None
            if recycled is not None:
                appending = self.tracer.clock()
                recycled.meta["pooled_at"] = self.scheduler.now()
                self._pool(lane).append(recycled)
                self.metrics.recycles.inc()
                self._notify_lane(lane)
                ended = self.tracer.clock()
                recycled.meta["turnover_s"] = ended - started
                self._record_timed("pool.append", appending, ended)
            else:
                await self._dispose(sandbox)
        finally:
            if recycled is None:
                self.fill_pool_soon(lane)
        return recycled is not None

    async def _dispose(self, sandbox: Sandbox) -> None:
        self._live_sandboxes.pop(sandbox.id, None)
        if self._store_shared:
            try:
                self.state_store.delete("hosts", sandbox.id)
            except Exception:  # noqa: BLE001
                logger.warning("host registry drop failed", exc_info=True)
        try:
            await self.backend.delete(sandbox)
        except Exception:  # noqa: BLE001
            logger.exception("failed to delete sandbox %s", sandbox.id)

    # ----------------------------------------------------------------- admin

    def live_hosts(self) -> list[tuple[int, Sandbox]]:
        """Every live sandbox with its lane — the device-health probe's
        inventory. Pooled, in-use, and session-parked sandboxes alike: the
        in-use ones are where mid-device-op wedges actually happen."""
        return list(self._live_sandboxes.values())

    def live_sandbox(self, sandbox_id: str) -> tuple[int, Sandbox] | None:
        """(lane, sandbox) for a live id, or None once disposed."""
        return self._live_sandboxes.get(sandbox_id)

    def statusz(self) -> dict:
        """The consolidated operator snapshot behind GET /statusz: one JSON
        joining what previously took a Prometheus query, a /healthz read,
        N sandbox ssh sessions, and a grep loop — lanes
        (queue pressure, pool depth, occupancy, breaker), hosts with their
        device-health verdicts, sessions, compile-cache store state, and
        the telemetry plane's own health (probe liveness, OTLP backlog)."""
        lanes: dict[str, dict] = {}
        lane_ids = (
            set(self._pools)
            | set(self._in_use)
            | set(self._session_held)
            | set(self._spawning)
        )
        detail = self.scheduler.lane_detail()
        lane_ids |= {int(lane) for lane in detail}
        breaker_states = self.breakers.states()
        for lane in sorted(lane_ids):
            entry: dict = {
                "pool_depth": len(self._pools.get(lane, ())),
                # Supply vs its target: pooled counts only non-wedged
                # hosts (pool_depth - pooled = zombies awaiting fencing),
                # pool_target is the autoscaler's capacity-clamped verdict.
                "pooled": self._pool_supply(lane),
                "pool_target": self._lane_target(lane),
                "in_use": self._in_use.get(lane, 0),
                "session_held": self._session_held.get(lane, 0),
                "spawning": self._spawning.get(lane, 0),
                "breaker": breaker_states.get(lane, "closed"),
            }
            entry.update(detail.get(str(lane), {}))
            lanes[str(lane)] = entry
        status = "ok"
        if self._draining:
            status = "draining"
        elif self.degraded():
            status = "degraded"
        body: dict = {
            "status": status,
            "inflight": self.inflight(),
            "lanes": lanes,
            "sessions": self.list_sessions(),
            # The durability plane: hibernated-session count (records a
            # next turn would restore), checkpoint admit/restore/conflict
            # totals, and the idle cost the plane exists to kill —
            # cumulative chip-seconds spent parked-idle across sessions.
            "session_durability": {
                **self.session_store.snapshot(),
                "idle_chip_seconds_total": round(self._idle_chip_seconds, 3),
            },
            "batching": {
                "enabled": self.batcher is not None,
                "window_ms": self.config.batch_window_ms,
                "max_jobs": self.config.batch_max_jobs,
            },
            "compile_cache": {
                "enabled": self.compile_cache.enabled,
                "entries": self.compile_cache.entry_count(),
                "bytes": self.compile_cache.total_bytes(),
            },
            # The warm-pool autoscaler's verdicts next to the demand
            # signals driving them (per-lane targets, arrival rates,
            # scale/reap counts; just the config echo when disabled).
            "autoscaler": self.autoscaler.snapshot(),
            # The metering plane's own view: per-tenant cumulative counters
            # plus ledger health (flushes, journal lines, tenant-table
            # occupancy). Bounded — the tenant table caps at
            # APP_USAGE_MAX_TENANTS with an _overflow row.
            "usage": self.usage.snapshot(),
            # The quota layer's verdict state: per-tenant window
            # consumption vs budget, in-flight counts, quarantine
            # sentences, and denial totals — the "who is being shed, and
            # why" view next to the usage it is computed from.
            "quotas": self.quotas.snapshot(),
            # The performance anomaly plane: per-(lane, phase) drift
            # verdicts with their quantiles and baselines, tenant latency
            # series, and the auto-profiling/profile-store state — "did
            # anything get slower than it used to be, and is there a
            # profile of it yet?".
            "perf": self.perf.snapshot(),
        }
        if self.device_health is not None:
            body["device_health"] = self.device_health.snapshot()
        else:
            body["device_health"] = {"enabled": False}
        # The wedge-recovery actuation state: lease generations per scope,
        # in-flight re-admission streaks, fence/readmission totals, and
        # the actuation budget — "is the detect→act loop closing, and is
        # anything quarantined right now?".
        body["recovery"] = {
            "fencing_enabled": self.config.device_fence_enabled,
            "fence_budget": {
                "max_per_window": self.config.device_fence_max_per_window,
                "window_seconds": self.config.device_fence_window_seconds,
            },
            **self.leases.snapshot(),
        }
        if self.otlp_exporter is not None:
            body["otlp"] = {"enabled": True, **self.otlp_exporter.stats()}
        else:
            body["otlp"] = {"enabled": False}
        # The scale-out view: which replica this is, who is on the ring,
        # and how much traffic was proxied/redirected to session owners.
        if self.session_router is not None:
            body["replicas"] = {"enabled": True, **self.session_router.snapshot()}
        elif self._store_shared:
            body["replicas"] = {
                "enabled": True,
                "self": self.replica_id,
                "store": type(self.state_store).__name__,
            }
        # The store-loss plane: the resilient wrapper's breaker verdict,
        # outage/degraded-op counters, and quota-journal backlog — "are we
        # serving from the shared store or from replica-local fallbacks?".
        store_health = getattr(self.state_store, "health", None)
        if callable(store_health):
            body["state_store"] = store_health()
        return body

    async def sweep_pool_health(self) -> int:
        """Probe every pooled sandbox's /healthz and dispose the
        unresponsive ones (refilling their lanes). Proactive failure
        detection: a pooled sandbox whose process died silently (OOM kill,
        node trouble) would otherwise cost the next request a failed
        attempt before the retry path replaced it. Returns disposed count."""
        client = self._http_client()
        removed = 0

        async def probe(url: str) -> bool:
            try:
                resp = await client.get(f"{url}/healthz", timeout=3.0)
                return resp.status_code == 200
            except Exception:  # noqa: BLE001 — unreachable = dead
                return False

        for lane, pool in list(self._pools.items()):
            for sandbox in list(pool):
                # Probe a sandbox's hosts concurrently: serialized 3s
                # timeouts across a multi-host slice would make one sweep
                # take minutes on a hung node.
                if all(
                    await asyncio.gather(
                        *(probe(url) for url in sandbox.host_urls)
                    )
                ):
                    continue
                try:
                    pool.remove(sandbox)
                except ValueError:
                    continue  # popped by a request while we probed
                logger.warning(
                    "pooled sandbox %s failed its health probe; disposing",
                    sandbox.id,
                )
                removed += 1
                # Dispose off the sweep path via the tracked-task pattern:
                # close() AWAITS _dispose_tasks (it CANCELS the sweeper
                # itself, and a cancel landing mid-teardown would leak the
                # sandbox's process past the loop).
                task = asyncio.get_running_loop().create_task(
                    self._dispose(sandbox)
                )
                self._dispose_tasks.add(task)
                task.add_done_callback(self._dispose_tasks.discard)
                self.fill_pool_soon(lane)
        return removed

    def start_health_sweeper(self, interval: float) -> asyncio.Task | None:
        """Run sweep_pool_health every `interval` seconds until close()."""
        return self._start_sweeper(
            self.sweep_pool_health, interval, "pool health sweep"
        )

    # ------------------------------------------------------------ autoscaling

    async def autoscale_sweep(self) -> int:
        """One autoscaler pass over every known lane: run the scale-down
        hysteresis, start spawn-ahead refills where demand says supply will
        lag, and reap excess idle warm sandboxes so shared chip capacity
        migrates to pressured lanes. Returns the number reaped."""
        if self._closed:
            return 0
        if self._store_shared:
            # Cross-replica supply is invisible to the event-driven kicks
            # (a PEER's release frees capacity this replica's waiters are
            # parked on): the sweep doubles as the bounded-staleness
            # refresh — republish our occupancy gauges and wake every
            # lane's head so it re-evaluates against the peers' current
            # holds.
            for lane in self._known_lanes():
                self._publish_occupancy(lane)
            self.scheduler.kick_all()
        if not self.autoscaler.enabled:
            return 0
        reaped = 0
        for lane in self._known_lanes():
            snapshot = self._lane_snapshot(lane)
            self.autoscaler.evaluate(lane, snapshot)
            target = self._lane_target(lane)
            in_use = (
                snapshot.in_use if self.config.executor_reuse_sandboxes else 0
            )
            if (
                snapshot.pooled + snapshot.spawning + snapshot.recovering
                + in_use < target
                and not self.breakers.is_open(lane)
            ):
                # Spawn-ahead: the target says this lane needs more warm
                # supply than it has (or will shortly have) — refill NOW,
                # before a request is waiting on the gap.
                self.fill_pool_soon(lane)
            reaped += self._reap_idle(lane, target)
        return reaped

    def _reap_idle(self, lane: int, target: int) -> int:
        """Dispose warm pooled sandboxes above the lane target that have
        sat idle past pool_idle_reap_seconds (oldest first). Only healthy
        hosts are considered on BOTH sides — wedged zombies neither count
        as the supply being trimmed nor get disposed here (that is the
        fencing layer's actuation, not the autoscaler's)."""
        pool = self._pools.get(lane)
        if not pool:
            return 0
        excess = self._pool_supply(lane) - max(0, target)
        if excess <= 0:
            return 0
        now = self.scheduler.now()
        idle_after = self.config.pool_idle_reap_seconds
        candidates = sorted(
            (
                sandbox
                for sandbox in pool
                if sandbox.meta.get("device_health")
                not in self._UNSERVABLE_HEALTH
                and now - float(sandbox.meta.get("pooled_at", now))
                >= idle_after
            ),
            key=lambda s: float(s.meta.get("pooled_at", now)),
        )
        reaped = 0
        for sandbox in candidates[:excess]:
            try:
                pool.remove(sandbox)
            except ValueError:
                continue  # popped by a request while we decided
            reaped += 1

            async def reap_one(victim: Sandbox) -> None:
                await self._dispose(victim)
                # The freed slot may be what a pressured CONSTRAINED lane
                # is waiting on — wake every lane's head, the shared-
                # substrate discipline of _notify_all_lanes.
                self._notify_all_lanes()

            task = asyncio.get_running_loop().create_task(reap_one(sandbox))
            self._dispose_tasks.add(task)
            task.add_done_callback(self._dispose_tasks.discard)
        if reaped:
            logger.info(
                "autoscale reap: disposed %d idle sandbox(es) on lane %d "
                "(target %d)",
                reaped,
                lane,
                target,
            )
            self.autoscaler.note_reaped(lane, reaped)
        return reaped

    def start_autoscaler(self, interval: float | None = None) -> asyncio.Task | None:
        """Run autoscale_sweep periodically until close(). None (no loop)
        with the kill switch on or a zero interval — targets then only
        ever move UP, on arrivals, and nothing is reaped. With a SHARED
        state store the loop still runs (even autoscale-disabled): it is
        the bounded-staleness refresh that re-publishes occupancy and
        wakes waiters parked behind a peer's since-released capacity."""
        if not self.autoscaler.enabled and not self._store_shared:
            return None
        if interval is None:
            interval = self.config.pool_autoscale_interval
        return self._start_sweeper(
            self.autoscale_sweep, interval, "autoscale sweep"
        )

    def lane_supply(self) -> dict[str, dict]:
        """Per-lane SUPPLY joined into GET /healthz next to the demand
        stats it already shows (queue depth / wait EWMA): the dynamic pool
        target and what currently backs it — so an operator can see supply
        next to the signals driving it without a /statusz round-trip.
        With the probe daemon attached, each row also carries the lane's
        device-health census (healthy/busy/suspect/wedged/recovering/
        draining counts — the wedge-recovery satellite: a fenced lane's
        quarantine is visible exactly where its queue pressure is)."""
        census: dict[int, dict[str, int]] = {}
        if self.device_health is not None:
            census = self.device_health.lane_census()
        rows: dict[str, dict] = {}
        for lane in sorted(self._known_lanes() | set(census)):
            row: dict = {
                "pool_target": self._lane_target(lane),
                "pooled": self._pool_supply(lane),
                "in_use": self._in_use.get(lane, 0),
                "spawning": self._spawning.get(lane, 0),
            }
            recovering = self._pool_standby(lane)
            draining = self._draining_count(lane)
            if recovering:
                row["recovering"] = recovering
            if draining:
                row["draining"] = draining
            if lane in census:
                row["device_health"] = census[lane]
            rows[str(lane)] = row
        return rows

    def start_compile_cache_prewarm(self) -> asyncio.Task | None:
        """Pre-warm the fleet compile-cache store from the examples/ kernel
        set (distilled: matmul/elementwise/reduction) after pool fill.

        Strictly a background nicety with attach-budget hygiene (the
        device-health roadmap discipline — a primer must never block a
        serving path): runs at `batch` priority so interactive work always
        outranks it, and while real work is queued on the lane it waits
        out the backlog (30s backoff) rather than occupying a slot —
        pre-warm is the store's only admission source, so it never gives
        up just because the lane is busy. It runs on EVERY control-plane
        start, warm persisted index or not:
        pre-warm runs are the store's only admission source, so this is
        where an evicted-but-still-prewarmed kernel gets re-admitted (one
        trusted recompile, with fresh recency). Surviving entries are NOT
        refreshed by the pass — they get seeded into the pre-warm sandbox,
        and harvest deliberately ignores seeded entries' re-observation
        (see SandboxCacheSync.harvest_host) — so on a warm store the
        sandboxes compile nothing and the whole pass costs a few
        batch-priority executes."""
        if not (
            self.config.compile_cache_enabled
            and self.config.compile_cache_prewarm
            and self.compile_cache.enabled
        ):
            return None
        if self._compile_cache_dir_scope() == "external":
            # Harvest is structurally off (shared PVC/hostPath volume:
            # nothing can vouch for the dir), so no pre-warm pass could
            # ever admit anything — running one would burn TPU time on
            # kernels whose artifacts the store must refuse, then warn
            # about an empty store as if something had failed.
            logger.info(
                "compile-cache pre-warm skipped: the backend's cache dir "
                "is externally writable, so harvest (the store's only "
                "admission source) is disabled"
            )
            return None
        if self._prewarm_started:
            return None
        self._prewarm_started = True
        task = asyncio.get_running_loop().create_task(
            self._prewarm_compile_cache()
        )
        self._fill_tasks.add(task)  # cancelled/awaited by close()
        task.add_done_callback(self._fill_tasks.discard)
        return task

    async def _execute_trusted(self, source_code: str, **kwargs) -> Result:
        """Run CONTROL-PLANE-AUTHORED code through the normal execute path
        without tainting the sandbox's compile-cache provenance — the only
        way a sandbox stays harvest-eligible (see _run_on_sandbox). Callers
        must pass literal, control-plane-owned source: anything derived from
        tenant input would reopen the cache-poisoning channel the taint
        exists to close."""
        token = _trusted_source_var.set(True)
        try:
            return await self.execute(source_code, **kwargs)
        finally:
            _trusted_source_var.reset(token)

    # Backoff between pre-warm attempts while real work is queued on the
    # lane, and between retries of an ineffective pass. Class attribute so
    # tests can shrink it.
    _PREWARM_BACKOFF_SECONDS = 30.0
    # A pass whose kernels all ran yet admitted NOTHING (store still empty)
    # landed on tainted recycled sandboxes — under sustained load with
    # reuse on, the pool can hold only tenant-tainted sandboxes, and a
    # trusted run there compiles fine but is harvest-ineligible. Retrying
    # gives the untainted-preference pool pop (_pop_pool_sandbox) fresh
    # spawns to land on; bounded so a deployment whose only sandbox is
    # tainted for life degrades to a loud warning, not an infinite loop.
    _PREWARM_MAX_PASSES = 5

    async def _prewarm_compile_cache(self) -> None:
        lane = self.config.default_chip_count
        for attempt in range(self._PREWARM_MAX_PASSES):
            if attempt:
                await asyncio.sleep(self._PREWARM_BACKOFF_SECONDS)
                if self._closed or self._draining:
                    return
            if (
                self._compile_cache_dir_scope() == "shared"
                and self._shared_cache_tainted
            ):
                # Tenant code beat the pre-warm to the shared cache dir:
                # the taint is control-plane-lifetime, so no later pass
                # can ever admit anything — retrying would just burn
                # sandbox time warning about it.
                logger.warning(
                    "compile-cache pre-warm stopped: tenant code already "
                    "ran against the shared cache dir, so harvest is off "
                    "for this control plane's lifetime (store has %d "
                    "entries)",
                    self.compile_cache.entry_count(),
                )
                return
            warmed = await self._prewarm_pass(lane)
            if warmed is None:
                return  # shutdown, or a kernel failed: retrying won't help
            # Harvest runs inside the release task execute() fires in its
            # finally (off the request hot path), so the last kernel's
            # admissions may still be in flight when the pass returns —
            # let in-flight releases settle before judging the pass by
            # the store's contents.
            pending = [t for t in self._dispose_tasks if not t.done()]
            if pending:
                await asyncio.gather(*pending, return_exceptions=True)
            if self.compile_cache.entry_count() > 0:
                logger.info(
                    "compile-cache pre-warm complete: %d kernels, store "
                    "holds %d entries (%d bytes)",
                    warmed,
                    self.compile_cache.entry_count(),
                    self.compile_cache.total_bytes(),
                )
                return
            logger.warning(
                "compile-cache pre-warm pass %d ran %d kernels but admitted "
                "nothing (tainted sandboxes or harvest failures); retrying",
                attempt + 1,
                warmed,
            )
        logger.warning(
            "compile-cache pre-warm gave up after %d ineffective passes: "
            "the fleet store is empty and has no other admission source",
            self._PREWARM_MAX_PASSES,
        )

    async def _prewarm_pass(self, lane: int) -> int | None:
        """One trusted run of every pre-warm kernel. Returns the number of
        kernels that ran, or None when the pass should never be retried
        (shutdown, or a kernel itself failed — e.g. jax missing from the
        sandbox image)."""
        warmed = 0
        for name, source in PREWARM_SOURCES:
            waiting_logged = False
            while self.scheduler.queued(lane) > 0:
                # Real requests are waiting for this lane: don't occupy a
                # sandbox slot for priming — wait for a quiet moment
                # instead of aborting forever. Pre-warm runs are the fleet
                # store's ONLY admission source, so a control plane
                # restarted under sustained load would otherwise serve its
                # whole lifetime with an empty store, recompiling every
                # kernel on every spawn. Logged once per wait, not per
                # 30s poll — sustained load would otherwise turn this
                # into an unbounded periodic log line.
                if not waiting_logged:
                    logger.info(
                        "compile-cache pre-warm waiting: lane-%d has "
                        "queued work",
                        lane,
                    )
                    waiting_logged = True
                await asyncio.sleep(self._PREWARM_BACKOFF_SECONDS)
                if self._closed or self._draining:
                    return None
            if self._closed or self._draining:
                return None
            try:
                result = await self._execute_trusted(source, priority="batch")
            except Exception as e:  # noqa: BLE001 — prewarm must never crash
                logger.warning(
                    "compile-cache pre-warm kernel %s failed: %r", name, e
                )
                return None
            if result.exit_code != 0:
                # e.g. jax missing in the sandbox image: pointless to
                # continue (and harmless to stop).
                logger.info(
                    "compile-cache pre-warm kernel %s exited %d; stopping",
                    name,
                    result.exit_code,
                )
                return None
            warmed += 1
        return warmed

    async def close(self) -> None:
        self._closed = True
        # Batching first: pending windows fail their futures (retryable),
        # in-flight dispatch tasks finish (they own sandbox release).
        if self.batcher is not None:
            await self.batcher.close()
        # Cancel in-flight pool refills — a spawn can take tens of seconds
        # (TPU warm-up) and shutdown must not wait for it; the backend kills
        # half-spawned sandboxes because they register before readiness.
        fills = list(self._fill_tasks)
        for task in fills:
            task.cancel()
        # Disposals run to completion so no subprocess outlives the loop.
        pending = fills + list(self._dispose_tasks)
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)
        sandboxes = [s for pool in self._pools.values() for s in pool]
        self._pools.clear()
        # Session sandboxes die with the service: sessions are affinity to a
        # live process, not durable state (files round-tripped through
        # Storage are what survives restarts — the reference's model).
        for session in self._sessions.values():
            if session.sandbox is not None and not session.closed:
                session.closed = True
                sandboxes.append(session.sandbox)
        self._sessions.clear()
        self._session_held.clear()
        await asyncio.gather(*(self._dispose(s) for s in sandboxes))
        self._live_sandboxes.clear()
        # The hot set survives restarts through the persisted index (the
        # per-harvest saves make this a formality, but a clean shutdown
        # should never depend on the last harvest having had new entries).
        self.compile_cache.save_index()
        # Final ledger flush: a clean shutdown loses ZERO attribution (the
        # flush-interval bound is for crashes only).
        self.usage.close()
        if self._client is not None and not self._client.is_closed:
            await self._client.aclose()
        await self.backend.close()
        # Retire this replica's shared-state footprint: peers must not
        # keep subtracting a dead replica's occupancy until the TTL ages
        # it out when the shutdown was orderly.
        if self._store_shared:
            try:
                for lane in list(
                    set(self._in_use) | set(self._session_held) | set(self._spawning)
                ):
                    self.state_store.delete(
                        "occupancy", f"{lane}/{self.replica_id}"
                    )
                self.state_store.delete("replicas", self.replica_id)
            except Exception:  # noqa: BLE001
                logger.warning("shared-state retirement failed", exc_info=True)
        self.state_store.close()
