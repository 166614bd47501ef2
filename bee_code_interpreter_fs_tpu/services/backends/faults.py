"""Deterministic fault injection for any `SandboxBackend`.

Chaos testing the pool requires failures that are (a) realistic — spawn
errors, slow readiness, refused recycles, hanging deletes, mid-execute
connection drops — and (b) **reproducible**, or a CI chaos run that fails
once can never be debugged. `FaultInjectingBackend` wraps a real backend
with a seeded fault plan: every fault category draws from its own
`random.Random` stream (seeded from the plan seed + category name), so the
spawn-failure sequence does not depend on how exec-drop rolls interleave
with it under concurrency.

The plan is configured as a compact spec string so one env var turns chaos
on in any deployment (``APP_EXECUTOR_FAULT_SPEC=spawn_fail:0.3,seed:7``):

    spawn_fail:<rate>    probability a spawn raises SandboxSpawnError
    slow_ready:<seconds> added latency before a successful spawn returns
    reset_fail:<rate>    probability a reset refuses (returns None)
    delete_hang:<seconds> added latency inside delete()
    exec_drop:<rate>     probability a sandbox HTTP request raises
                         ConnectError mid-flight (via the injectable httpx
                         transport the orchestrator asks backends for)
    violation:<rate>     probability a POST /execute answers with a
                         synthesized typed limit violation instead of
                         running (exercises the LimitExceededError path:
                         422 mapping, no-retry, breaker strikes, host
                         disposal) — kind set by violation_kind
    violation_kind:<kind> which violation to inject (default oom; one of
                         services.limits.VIOLATION_KINDS)
    attach_hang:<rate>   probability a HOST develops a wedged device attach
                         (drawn once per host, at its first GET
                         /device-stats): from then on its stats report an
                         attach pending whose age grows in real time and a
                         stale runner heartbeat — a HANG, not an error,
                         which is the real wedge semantics (rounds 3 to
                         5 on the TPU rig: attaches block for tens of
                         minutes; they do not fail). Drives the probe daemon's
                         healthy→suspect→wedged escalation deterministically.
    attach_hang_lane:<n> restrict attach_hang to hosts of ONE chip-count
                         lane (-1 = any lane, the default) — the chaos e2e
                         wedges one lane while proving the other keeps
                         serving.
    attach_hang_max:<n>  at most n hosts ever wedge (0 = unlimited): with
                         rate 1.0 this wedges exactly the FIRST n hosts a
                         probe touches, so a recovery test can wedge one
                         host deterministically while its dispose-and-
                         replace successor comes up clean.
    attach_hang_recover:<n> a wedged host's hang CLEARS after n wedged
                         /device-stats draws (0 = never, the default):
                         later probes pass through to the real stats.
                         This is the chaos-testable shape of a host that
                         relapses and then recovers — the re-admission
                         streak (clean probes after a fence) and its
                         suspect-relapse reset become drivable from a
                         seeded spec instead of hand-faked responses.
    slow_exec:<rate>     probability an execute dispatch (/execute,
                         /execute/stream, /execute-batch) is DELAYED by
                         slow_exec_seconds before reaching the sandbox —
                         a latency regression, not an error: the request
                         succeeds, only slower. This is the perf anomaly
                         plane's chaos signal (the drift detector must
                         flip the affected lane's exec series to
                         regressed while clean lanes stay normal).
    slow_exec_seconds:<s> the injected delay (default 0.25).
    slow_exec_lane:<n>   restrict slow_exec to hosts of ONE chip-count
                         lane (-1 = any lane, the default) — the perf e2e
                         regresses one lane while proving the other's
                         baseline holds.
    seed:<int>           the plan seed (default 0)

Rates are in [0, 1]; delays are seconds. Unknown keys fail loudly — a typo'd
chaos knob silently injecting nothing is itself a reliability bug.
"""

from __future__ import annotations

import asyncio
import logging
import random
import threading
import time
from collections.abc import Callable
from dataclasses import dataclass, fields

import httpx

from ..limits import VIOLATION_KINDS
from .base import Sandbox, SandboxBackend, SandboxSpawnError

logger = logging.getLogger(__name__)

SPAWN_FAIL = "spawn_fail"
SLOW_READY = "slow_ready"
RESET_FAIL = "reset_fail"
DELETE_HANG = "delete_hang"
EXEC_DROP = "exec_drop"
VIOLATION = "violation"
ATTACH_HANG = "attach_hang"
SLOW_EXEC = "slow_exec"


@dataclass(frozen=True)
class FaultSpec:
    spawn_fail: float = 0.0
    slow_ready: float = 0.0
    reset_fail: float = 0.0
    delete_hang: float = 0.0
    exec_drop: float = 0.0
    violation: float = 0.0
    violation_kind: str = "oom"
    attach_hang: float = 0.0
    attach_hang_lane: int = -1
    attach_hang_max: int = 0
    attach_hang_recover: int = 0
    slow_exec: float = 0.0
    slow_exec_seconds: float = 0.25
    slow_exec_lane: int = -1
    seed: int = 0

    @classmethod
    def parse(cls, text: str) -> "FaultSpec":
        """Parse ``key:value,key:value`` (whitespace tolerated). An empty
        string is the null plan (inject nothing)."""
        values: dict[str, float | int | str] = {}
        known = {f.name for f in fields(cls)}
        for item in text.split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, raw = item.partition(":")
            key = key.strip()
            if not sep or key not in known:
                raise ValueError(
                    f"bad fault spec item {item!r}: want one of "
                    f"{sorted(known)} as key:value"
                )
            try:
                if key in (
                    "seed",
                    "attach_hang_lane",
                    "attach_hang_max",
                    "attach_hang_recover",
                    "slow_exec_lane",
                ):
                    values[key] = int(raw)
                elif key == "violation_kind":
                    values[key] = raw.strip()
                else:
                    values[key] = float(raw)
            except ValueError:
                raise ValueError(
                    f"bad fault spec value for {key}: {raw!r}"
                ) from None
        spec = cls(**values)
        for name in (
            SPAWN_FAIL,
            RESET_FAIL,
            EXEC_DROP,
            VIOLATION,
            ATTACH_HANG,
            SLOW_EXEC,
        ):
            rate = getattr(spec, name)
            if not 0.0 <= rate <= 1.0:
                raise ValueError(f"fault rate {name} must be in [0,1]: {rate}")
        for name in (SLOW_READY, DELETE_HANG, "slow_exec_seconds"):
            if getattr(spec, name) < 0.0:
                raise ValueError(f"fault delay {name} must be >= 0")
        if spec.violation_kind not in VIOLATION_KINDS:
            raise ValueError(
                f"violation_kind must be one of {list(VIOLATION_KINDS)}: "
                f"{spec.violation_kind!r}"
            )
        return spec

    @property
    def active(self) -> bool:
        return any(
            getattr(self, f.name)
            for f in fields(self)
            if f.name
            not in (
                "seed",
                "violation_kind",
                "attach_hang_lane",
                "attach_hang_max",
                "attach_hang_recover",
                "slow_exec_seconds",
                "slow_exec_lane",
            )
        )


class ViolationTransport(httpx.AsyncBaseTransport):
    """httpx transport that answers a seeded fraction of POST /execute
    calls with a synthesized typed-limit-violation response — the body a
    real executor returns after its watchdog killed the runner group —
    without the request ever reaching a sandbox. This drives the whole
    control-plane classification path (LimitExceededError, 422 mapping,
    no-retry, breaker strike, host disposal) deterministically in chaos
    runs."""

    def __init__(
        self,
        rate: float,
        kind: str,
        rng: random.Random,
        on_fault: Callable[[str], None] | None = None,
        inner: httpx.AsyncBaseTransport | None = None,
    ) -> None:
        self.rate = rate
        self.kind = kind
        self.rng = rng
        self.on_fault = on_fault
        self.inner = inner or httpx.AsyncHTTPTransport()

    async def handle_async_request(self, request):
        if (
            request.method == "POST"
            and request.url.path == "/execute"
            and self.rng.random() < self.rate
        ):
            if self.on_fault is not None:
                self.on_fault(VIOLATION)
            # cpu_time is the one kind the in-process guard catches with the
            # runner surviving; every other kind is a watchdog group kill.
            killed = self.kind != "cpu_time"
            body = {
                "stdout": "",
                "stderr": f"Resource limit exceeded: {self.kind} (injected)",
                "exit_code": 137 if killed else 1,
                "stdout_truncated": False,
                "stderr_truncated": False,
                "violation": self.kind,
                "files": [],
                "deleted": [],
                "duration_s": 0.0,
                "warm": True,
                "runner_restarted": killed,
            }
            return httpx.Response(200, json=body, request=request)
        return await self.inner.handle_async_request(request)

    async def aclose(self) -> None:
        await self.inner.aclose()


class AttachHangTransport(httpx.AsyncBaseTransport):
    """httpx transport that gives a seeded subset of hosts a wedged device
    attach, as seen through ``GET /device-stats``: once a host is chosen
    (one draw at its first stats probe; optionally restricted to one lane),
    every later probe of that host gets a synthesized body whose
    ``attach_pending_s`` grows in REAL time from the moment the hang
    started, with a matching stale runner heartbeat. A hang, not an error —
    the executor's HTTP plane stays perfectly responsive while the device
    plane silently stops, which is exactly the wedge of rounds 3 to 5 (a
    device op that never completes, 50-76 minutes of manual recovery by
    host reboot) that the probe daemon must distinguish from ordinary
    busy/attaching states.
    Everything except /device-stats passes through untouched (detection is
    this PR's scope; the data plane keeps serving)."""

    def __init__(
        self,
        rate: float,
        lane: int,
        rng: random.Random,
        host_lanes: dict[str, int],
        on_fault: Callable[[str], None] | None = None,
        inner: httpx.AsyncBaseTransport | None = None,
        clock: Callable[[], float] = time.monotonic,
        max_hosts: int = 0,
        recover_draws: int = 0,
    ) -> None:
        self.rate = rate
        self.lane = lane
        self.rng = rng
        # "host:port" -> chip-count lane, recorded by the backend at spawn:
        # the lane restriction must hold even though a URL alone says
        # nothing about topology.
        self.host_lanes = host_lanes
        self.on_fault = on_fault
        self.inner = inner or httpx.AsyncHTTPTransport()
        self.clock = clock
        # At most this many hosts ever wedge (0 = unlimited): with rate 1.0
        # the FIRST max_hosts probed hosts wedge deterministically and the
        # dispose-and-replace successors come up clean — the recovery e2e's
        # wedge-one-host shape.
        self.max_hosts = max_hosts
        # A wedged host's hang clears after this many wedged stats draws
        # (0 = never): the chaos-testable relapse-then-recover host the
        # re-admission streak needs.
        self.recover_draws = recover_draws
        # "host:port" -> hang start (clock), or None for hosts that drew a
        # pass. One draw per host, remembered — a wedge does not flicker
        # (with recover_draws set it can only CLEAR, once, for good).
        self._hangs: dict[str, float | None] = {}
        self._wedged_draws: dict[str, int] = {}

    def _hang_started(self, request) -> float | None:
        key = f"{request.url.host}:{request.url.port}"
        if key not in self._hangs:
            lane = self.host_lanes.get(key)
            eligible = self.lane < 0 or (lane is not None and lane == self.lane)
            if eligible and self.max_hosts > 0:
                wedged_hosts = sum(
                    1 for start in self._hangs.values() if start is not None
                )
                eligible = wedged_hosts < self.max_hosts
            wedged = eligible and self.rng.random() < self.rate
            self._hangs[key] = self.clock() if wedged else None
            if wedged and self.on_fault is not None:
                self.on_fault(ATTACH_HANG)
        started = self._hangs[key]
        if started is not None and self.recover_draws > 0:
            draws = self._wedged_draws.get(key, 0)
            if draws >= self.recover_draws:
                return None  # the hang cleared: real stats from here on
            self._wedged_draws[key] = draws + 1
        return started

    async def handle_async_request(self, request):
        if (
            request.method == "GET"
            and request.url.path == "/device-stats"
        ):
            started = self._hang_started(request)
            if started is not None:
                age = max(0.0, self.clock() - started)
                body = {
                    "status": "ok",
                    "warm": False,
                    "warm_state": "pending",
                    "backend": "none",
                    "device_kind": "",
                    "device_count": 0,
                    "num_hosts": 1,
                    "uptime_s": age,
                    # THE wedge signature: an attach that has been pending
                    # for `age` seconds and counting, no runner heartbeat.
                    "attach_pending_s": age,
                    "attach_seconds": -1.0,
                    "op_in_flight": False,
                    "op_age_s": 0.0,
                    "op_timeout_s": 0.0,
                    "last_device_op_age_s": -1.0,
                    "runner_heartbeat_age_s": age,
                    "runner_alive": False,
                    "runner_pid": 0,
                    "rss_bytes": -1,
                    "runner_rss_bytes": -1,
                    "injected": ATTACH_HANG,
                }
                return httpx.Response(200, json=body, request=request)
        return await self.inner.handle_async_request(request)

    async def aclose(self) -> None:
        await self.inner.aclose()


class SlowExecTransport(httpx.AsyncBaseTransport):
    """httpx transport that DELAYS a seeded fraction of execute dispatches
    (/execute, /execute/stream, /execute-batch) before they reach the
    sandbox — a latency regression, not an error: the request succeeds,
    only slower. Optionally restricted to one chip-count lane via the
    backend's host→lane map, so a chaos leg can regress one lane while
    the control plane proves the others' baselines hold. This is the perf
    anomaly plane's chaos signal: the drift detector must flip the
    affected (lane, exec) series to regressed within one window."""

    _EXEC_PATHS = ("/execute", "/execute/stream", "/execute-batch")

    def __init__(
        self,
        rate: float,
        delay_s: float,
        lane: int,
        rng: random.Random,
        host_lanes: dict[str, int],
        on_fault: Callable[[str], None] | None = None,
        inner: httpx.AsyncBaseTransport | None = None,
    ) -> None:
        self.rate = rate
        self.delay_s = delay_s
        self.lane = lane
        self.rng = rng
        self.host_lanes = host_lanes
        self.on_fault = on_fault
        self.inner = inner or httpx.AsyncHTTPTransport()

    async def handle_async_request(self, request):
        if (
            request.method == "POST"
            and request.url.path in self._EXEC_PATHS
        ):
            key = f"{request.url.host}:{request.url.port}"
            lane = self.host_lanes.get(key)
            eligible = self.lane < 0 or (
                lane is not None and lane == self.lane
            )
            # The draw happens for EVERY dispatch (eligible or not) so the
            # seeded stream's consumption — and therefore every other
            # category's interleaving — does not depend on which lane a
            # request happened to land on.
            fired = self.rng.random() < self.rate
            if eligible and fired:
                if self.on_fault is not None:
                    self.on_fault(SLOW_EXEC)
                await asyncio.sleep(self.delay_s)
        return await self.inner.handle_async_request(request)

    async def aclose(self) -> None:
        await self.inner.aclose()


class DroppingTransport(httpx.AsyncBaseTransport):
    """httpx transport that raises `httpx.ConnectError` on a seeded fraction
    of requests before delegating to the real transport — the mid-execute
    connection drop no backend-level fault can produce (the request dies on
    the wire, not in the sandbox)."""

    def __init__(
        self,
        rate: float,
        rng: random.Random,
        on_fault: Callable[[str], None] | None = None,
        inner: httpx.AsyncBaseTransport | None = None,
    ) -> None:
        self.rate = rate
        self.rng = rng
        self.on_fault = on_fault
        self.inner = inner or httpx.AsyncHTTPTransport()

    async def handle_async_request(self, request):
        if self.rng.random() < self.rate:
            if self.on_fault is not None:
                self.on_fault(EXEC_DROP)
            raise httpx.ConnectError(
                f"injected connection drop ({request.url})", request=request
            )
        return await self.inner.handle_async_request(request)

    async def aclose(self) -> None:
        await self.inner.aclose()


STORE_DROP = "store_drop"
STORE_OUTAGE = "store_outage"


@dataclass(frozen=True)
class StoreFaultSpec:
    """Seeded fault plan for the shared StateStore, configured via
    ``APP_STATE_STORE_FAULT_SPEC`` with the same ``key:value,...`` grammar
    as the backend plan:

        drop:<rate>       probability any single store op raises
                          StateStoreUnavailableError (flaky network)
        outage_after:<n>  after n successful ops, the store goes HARD
                          down (every op fails) — 0 disables
        outage_ops:<n>    the outage clears after n failed ops (0 = it
                          never clears): the deterministic
                          outage-then-reconnect shape the degraded-mode
                          tests replay
        seed:<int>        the plan seed (default 0)

    A PARTITION (one replica loses the store while peers keep it) is
    staged by wrapping only that replica's store handle — the injector
    wraps a handle, not the server.
    """

    drop: float = 0.0
    outage_after: int = 0
    outage_ops: int = 0
    seed: int = 0

    @classmethod
    def parse(cls, text: str) -> "StoreFaultSpec":
        values: dict[str, float | int] = {}
        known = {f.name for f in fields(cls)}
        for item in text.split(","):
            item = item.strip()
            if not item:
                continue
            key, sep, raw = item.partition(":")
            key = key.strip()
            if not sep or key not in known:
                raise ValueError(
                    f"bad store fault spec item {item!r}: want one of "
                    f"{sorted(known)} as key:value"
                )
            try:
                values[key] = (
                    float(raw) if key == "drop" else int(raw)
                )
            except ValueError:
                raise ValueError(
                    f"bad store fault spec value for {key}: {raw!r}"
                ) from None
        spec = cls(**values)
        if not 0.0 <= spec.drop <= 1.0:
            raise ValueError(f"store drop rate must be in [0,1]: {spec.drop}")
        if spec.outage_after < 0 or spec.outage_ops < 0:
            raise ValueError("store outage counters must be >= 0")
        return spec

    @property
    def active(self) -> bool:
        return self.drop > 0.0 or self.outage_after > 0


class FaultInjectingStateStore:
    """Wraps any StateStore with the seeded StoreFaultSpec: per-op drop
    rolls from a dedicated stream plus a deterministic hard-outage window
    (``outage_after`` successes, then ``outage_ops`` failures, then
    healthy again). Duck-types the StateStore interface — components only
    call the ops, and ``make_state_store`` layers ResilientStateStore
    OUTSIDE this wrapper so degraded-mode policy sees the injected
    failures exactly as it would see real ones."""

    def __init__(
        self,
        inner,
        spec: StoreFaultSpec,
        *,
        on_fault: Callable[[str], None] | None = None,
    ) -> None:
        self.inner = inner
        self.spec = spec
        self.on_fault = on_fault
        self._rng = random.Random(f"{spec.seed}:store")
        self._lock = threading.Lock()
        self._ops = 0
        self._outage_left = 0
        self._in_outage = False
        if spec.active:
            logger.warning("state-store fault injection ACTIVE: %s", spec)

    @property
    def shared(self) -> bool:
        return self.inner.shared

    def _gate(self) -> None:
        # Imported here (not top-level) to keep the module import-light for
        # backend-only users; state_store imports THIS module lazily for
        # the same reason.
        from ..state_store import StateStoreUnavailableError

        with self._lock:
            if self._in_outage:
                if self.spec.outage_ops > 0:
                    self._outage_left -= 1
                    if self._outage_left <= 0:
                        # The outage clears AFTER this last failed op; the
                        # success counter restarts so a later window can
                        # re-trip deterministically.
                        self._in_outage = False
                        self._ops = 0
                if self.on_fault is not None:
                    self.on_fault(STORE_OUTAGE)
                raise StateStoreUnavailableError(
                    f"injected store outage (seed={self.spec.seed})"
                )
            if self.spec.outage_after > 0:
                self._ops += 1
                if self._ops > self.spec.outage_after:
                    self._in_outage = True
                    self._outage_left = self.spec.outage_ops
                    if self.on_fault is not None:
                        self.on_fault(STORE_OUTAGE)
                    raise StateStoreUnavailableError(
                        f"injected store outage (seed={self.spec.seed})"
                    )
            if self.spec.drop > 0.0 and self._rng.random() < self.spec.drop:
                if self.on_fault is not None:
                    self.on_fault(STORE_DROP)
                raise StateStoreUnavailableError(
                    f"injected store drop (seed={self.spec.seed})"
                )

    def get(self, ns, key):
        self._gate()
        return self.inner.get(ns, key)

    def put(self, ns, key, value):
        self._gate()
        return self.inner.put(ns, key, value)

    def delete(self, ns, key):
        self._gate()
        return self.inner.delete(ns, key)

    def items(self, ns):
        self._gate()
        return self.inner.items(ns)

    def incr(self, ns, key, delta=1.0):
        self._gate()
        return self.inner.incr(ns, key, delta)

    def mutate(self, ns, key, fn):
        self._gate()
        return self.inner.mutate(ns, key, fn)

    # TTL-lease helpers ride the gated primitives via the base-class
    # implementations on the INNER store — but they must go through OUR
    # gate, so delegate explicitly.
    def put_ttl(self, ns, key, value, ttl_seconds, *, now=None):
        self._gate()
        return self.inner.put_ttl(ns, key, value, ttl_seconds, now=now)

    def get_live(self, ns, key, *, now=None):
        self._gate()
        return self.inner.get_live(ns, key, now=now)

    def acquire_lease(self, ns, key, owner, ttl_seconds, *, now=None):
        self._gate()
        return self.inner.acquire_lease(ns, key, owner, ttl_seconds, now=now)

    def close(self):
        self.inner.close()


class FaultInjectingBackend(SandboxBackend):
    """Wraps any backend with the seeded fault plan above. Transparent when
    the plan is null; delete() never raises (base-class contract) even while
    injecting hangs."""

    def __init__(
        self,
        inner: SandboxBackend,
        spec: FaultSpec,
        *,
        on_fault: Callable[[str], None] | None = None,
    ) -> None:
        self.inner = inner
        self.spec = spec
        self.on_fault = on_fault
        self._rngs = {
            name: random.Random(f"{spec.seed}:{name}")
            for name in (
                SPAWN_FAIL,
                SLOW_READY,
                RESET_FAIL,
                DELETE_HANG,
                EXEC_DROP,
                VIOLATION,
                ATTACH_HANG,
                SLOW_EXEC,
            )
        }
        # "host:port" -> lane, recorded at spawn so the attach-hang
        # transport can honor a lane restriction.
        self._host_lanes: dict[str, int] = {}
        if spec.active:
            logger.warning("fault injection ACTIVE: %s", spec)

    def bind_breakers(self, board) -> None:
        """Pass the executor's breaker board through to the wrapped backend
        (the kubernetes pod-watch integration must keep working under an
        injected-fault wrapper)."""
        bind = getattr(self.inner, "bind_breakers", None)
        if bind is not None:
            bind(board)

    @property
    def compile_cache_dir_scope(self) -> str:
        """The wrapper injects faults, it doesn't change who can write the
        cache dir — delegate the trust statement to the real backend
        (fail-closed "external" if it declares nothing)."""
        scope = getattr(self.inner, "compile_cache_dir_scope", None)
        return scope if scope in ("private", "shared") else "external"

    @property
    def supports_lease_push(self) -> bool:
        """Whether this backend's sandboxes are real HTTP hosts the lease
        token can be POSTed to — delegated (the in-memory test fake says
        no, so chaos runs stay deterministic)."""
        return getattr(self.inner, "supports_lease_push", True)

    def lease_scope(self, chip_count: int, sandbox=None):
        """Hardware lease-scope naming — delegated (the wrapper changes
        fault behavior, not which chips a sandbox holds). None (falsy)
        when the inner backend declares nothing: the executor then uses
        its lane default."""
        scope_fn = getattr(self.inner, "lease_scope", None)
        if scope_fn is None:
            return None
        try:
            return scope_fn(chip_count, sandbox=sandbox)
        except TypeError:
            return scope_fn(chip_count)

    def _fire(self, name: str, rate: float) -> bool:
        if rate <= 0.0 or self._rngs[name].random() >= rate:
            return False
        if self.on_fault is not None:
            self.on_fault(name)
        return True

    # ---------------------------------------------------------------- backend

    async def spawn(self, chip_count: int = 0) -> Sandbox:
        if self._fire(SPAWN_FAIL, self.spec.spawn_fail):
            raise SandboxSpawnError(
                f"injected spawn failure (lane={chip_count}, "
                f"seed={self.spec.seed})"
            )
        if self.spec.slow_ready > 0.0:
            self._fire(SLOW_READY, 1.0)  # counted, never skipped
            await asyncio.sleep(self.spec.slow_ready)
        sandbox = await self.inner.spawn(chip_count)
        if self.spec.attach_hang > 0.0 or self.spec.slow_exec > 0.0:
            # Both lane-restrictable transports key off "host:port": record
            # the lane at spawn, where topology is still known.
            for url in sandbox.host_urls:
                parsed = httpx.URL(url)
                self._host_lanes[f"{parsed.host}:{parsed.port}"] = chip_count
        return sandbox

    def pool_capacity(self, chip_count: int) -> int | None:
        capacity_fn = getattr(self.inner, "pool_capacity", None)
        return capacity_fn(chip_count) if capacity_fn is not None else None

    async def reset(self, sandbox: Sandbox) -> Sandbox | None:
        if self._fire(RESET_FAIL, self.spec.reset_fail):
            return None
        return await self.inner.reset(sandbox)

    async def delete(self, sandbox: Sandbox) -> None:
        if self.spec.delete_hang > 0.0:
            self._fire(DELETE_HANG, 1.0)
            await asyncio.sleep(self.spec.delete_hang)
        await self.inner.delete(sandbox)

    async def close(self) -> None:
        await self.inner.close()

    # ------------------------------------------------------------- http hook

    def http_transport(self) -> httpx.AsyncBaseTransport | None:
        """Transport the orchestrator should build its sandbox HTTP client
        with (None = default). This is how exec_drop and violation reach
        the wire; both active stacks them (violation checked first)."""
        transport: httpx.AsyncBaseTransport | None = None
        if self.spec.exec_drop > 0.0:
            transport = DroppingTransport(
                self.spec.exec_drop, self._rngs[EXEC_DROP], self.on_fault
            )
        if self.spec.violation > 0.0:
            transport = ViolationTransport(
                self.spec.violation,
                self.spec.violation_kind,
                self._rngs[VIOLATION],
                self.on_fault,
                inner=transport,
            )
        if self.spec.attach_hang > 0.0:
            transport = AttachHangTransport(
                self.spec.attach_hang,
                self.spec.attach_hang_lane,
                self._rngs[ATTACH_HANG],
                self._host_lanes,
                self.on_fault,
                inner=transport,
                max_hosts=self.spec.attach_hang_max,
                recover_draws=self.spec.attach_hang_recover,
            )
        if self.spec.slow_exec > 0.0:
            transport = SlowExecTransport(
                self.spec.slow_exec,
                self.spec.slow_exec_seconds,
                self.spec.slow_exec_lane,
                self._rngs[SLOW_EXEC],
                self._host_lanes,
                self.on_fault,
                inner=transport,
            )
        return transport
