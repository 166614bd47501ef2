"""Device-health probe daemon: the detection half of wedge recovery.

Rounds 3 to 5 on the TPU rig are the production failure mode this module
exists for (50-76 minutes of manual recovery by host reboot each time): a
TPU attach that never completes, with
``/healthz`` answering "ok" the whole time — nothing distinguished *busy*
from *wedged*, and the recovery story was an operator in a shell. The
ROADMAP's fencing item needs observation before it can get actuation; this
daemon is that observation layer. A ``wedged`` verdict marks the host
(``sandbox.meta["device_health"]``), fires ``device_wedge_detected_total``,
records a transition trace — and now ACTS: the verdict is handed to the
executor's fencing actuator (``CodeExecutor.on_host_wedged`` — lease
revocation, lane drain, dispose-and-replace; every safety bound lives
there), and hosts on a fenced scope ride the ``recovering`` →
re-admission state machine here (``_recovery_overlay``): probed but
serving nothing until ``APP_DEVICE_PROBE_READMIT_STREAK`` consecutive
clean cycles, with suspect relapse resetting the streak.

Mechanics: every ``APP_DEVICE_PROBE_INTERVAL`` seconds, one cycle samples
``GET /device-stats`` on every live sandbox host (the executor's registry —
pooled, in-use, and session-parked sandboxes alike) and classifies each
host into a typed state:

- ``healthy`` — reachable, no device op in flight, nothing stalled.
- ``busy``    — an attach or device op is running inside its budget.
- ``suspect`` — something is past its budget (attach older than
  ``APP_DEVICE_PROBE_ATTACH_BUDGET``, an op older than its own declared
  timeout plus ``APP_DEVICE_PROBE_OP_GRACE``, or the host stopped answering
  probes) but not yet long enough to call dead.
- ``wedged``  — the stall has persisted ``APP_DEVICE_PROBE_WEDGE_AFTER``
  seconds past the budget: the device plane stopped making progress and no
  in-band mechanism is going to unstick it.

Ages come from the executor server's own monotonic clock (``/device-stats``
reports ages, not timestamps), so no cross-host clock math happens here.
Transitions touching suspect/wedged — entering trouble or recovering from
it; routine healthy<->busy flips stay silent — emit a
``device_health.transition`` span into the trace ring (recorded at ANY
sampling ratio — such transitions are rare and exactly what an operator
pulls up after an incident; only the tracing kill switch drops them, along
with the whole /traces surface) and the state surface feeds ``/statusz``,
the
``device_health_state`` gauge (host labels capped —
``APP_DEVICE_PROBE_MAX_HOST_LABELS`` — past which series aggregate per
lane), and the OTLP metrics export.

The probe daemon is itself observable: ``device_probe_last_poll_age_seconds``
and ``code_interpreter_device_probe_cycle_seconds`` expose a stalled or
slow probe loop (a wedge nobody is probing for is invisible).
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field

import httpx

from ..utils import tracing

logger = logging.getLogger(__name__)

HEALTHY = "healthy"
BUSY = "busy"
# The two actuation states the fencing layer added on top of PR 8's four
# classifications. RECOVERING: the host sits on a fenced lease scope and
# probes clean, but has not yet shown the configured consecutive-clean
# streak — it is probed and counted, never handed a request. DRAINING: the
# actuator fenced this host (lease revoked, drain + dispose in flight);
# the state is terminal — the host leaves the table when disposal lands.
RECOVERING = "recovering"
SUSPECT = "suspect"
WEDGED = "wedged"
DRAINING = "draining"
STATES = (HEALTHY, BUSY, RECOVERING, SUSPECT, WEDGED, DRAINING)

# Severity order for "did this transition get worse?" decisions.
_SEVERITY = {state: i for i, state in enumerate(STATES)}


@dataclass
class HostHealth:
    """One probed host's current classification and supporting evidence."""

    lane: int
    sandbox_id: str
    host: str
    state: str = HEALTHY
    since: float = 0.0  # probe clock: when `state` was entered
    reason: str = ""  # which signal produced the state
    stall_s: float = 0.0  # seconds past budget (suspect/wedged evidence)
    failures: int = 0  # consecutive probe failures
    last_success: float | None = None  # probe clock
    first_failure: float | None = None
    legacy: bool = False  # old executor binary: no /device-stats route
    stats: dict = field(default_factory=dict)  # last good /device-stats body

    def snapshot(self) -> dict:
        """The /statusz row for this host."""
        row = {
            "lane": self.lane,
            "sandbox": self.sandbox_id,
            "host": self.host,
            "state": self.state,
            "reason": self.reason,
            "stall_s": round(self.stall_s, 3),
            "probe_failures": self.failures,
        }
        if self.legacy:
            row["legacy"] = True
        stats = self.stats
        if stats:
            row["device_count"] = stats.get("device_count")
            row["device_kind"] = stats.get("device_kind") or stats.get(
                "backend"
            )
            row["warm_state"] = stats.get("warm_state")
            row["op_in_flight"] = bool(stats.get("op_in_flight"))
            row["attach_seconds"] = stats.get("attach_seconds")
            row["rss_bytes"] = stats.get("rss_bytes")
            row["runner_rss_bytes"] = stats.get("runner_rss_bytes")
            row["last_device_op_age_s"] = stats.get("last_device_op_age_s")
        return row


class DeviceHealthProbe:
    """Samples every live sandbox host and keeps the typed state machine.

    ``executor`` supplies the host inventory (``live_hosts()``) and the
    HTTP client (which carries the chaos backend's fault transport — the
    attach-hang injection reaches the probe exactly the way a real wedged
    host would). ``clock`` is injectable for deterministic tests.
    """

    def __init__(
        self,
        executor,
        *,
        config=None,
        metrics=None,
        tracer=None,
        clock=time.monotonic,
        walltime=time.time,
    ) -> None:
        self.executor = executor
        self.config = config or executor.config
        self.metrics = metrics or executor.metrics
        self.tracer = tracer or executor.tracer
        self.clock = clock
        self.walltime = walltime
        self.interval = max(0.0, self.config.device_probe_interval)
        self.timeout = max(0.1, self.config.device_probe_timeout)
        self.attach_budget = max(0.0, self.config.device_probe_attach_budget)
        self.op_grace = max(0.0, self.config.device_probe_op_grace)
        self.wedge_after = max(0.0, self.config.device_probe_wedge_after)
        self.max_host_labels = max(1, self.config.device_probe_max_host_labels)
        self._hosts: dict[str, HostHealth] = {}
        # Per-cycle recovery verdicts: lease scope -> [all_clean, lane].
        # Aggregated across the scope's hosts and settled ONCE per cycle
        # (note_probe per host would let a two-host scope double-count its
        # clean streak).
        self._scope_clean: dict[str, list] = {}
        self._task: asyncio.Task | None = None
        self._closed = False
        self._last_cycle_end: float | None = None
        self._cycles = 0
        self.metrics.bind_device_health(self)

    # ------------------------------------------------------------- lifecycle

    def start(self) -> asyncio.Task | None:
        """Run probe cycles on the configured cadence until stop().
        interval == 0 disables the daemon (returns None, no task)."""
        if self.interval <= 0 or self._task is not None:
            return self._task

        async def loop() -> None:
            # Probe work must never attach spans/events to whatever request
            # context was current when start() ran.
            tracing.current_span_var.set(None)
            # Probe first, then sleep: the daemon's first verdicts exist
            # one cycle after start, not one interval later — a wedge
            # present at boot is visible immediately.
            while not self._closed:
                try:
                    await self.probe_once()
                except Exception:  # noqa: BLE001 — keep probing
                    logger.exception("device-health probe cycle failed")
                await asyncio.sleep(self.interval)

        self._task = asyncio.get_running_loop().create_task(loop())
        return self._task

    async def stop(self) -> None:
        """Stop the probe loop. Restart-safe: a later start() begins a
        fresh loop (the overhead bench toggles the daemon A/B on one live
        stack)."""
        self._closed = True
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        self._closed = False

    # ----------------------------------------------------------- probe cycle

    async def probe_once(self) -> dict[str, str]:
        """One full cycle: sample every live host, classify, prune hosts
        that no longer exist. Returns {host_url: state} for tests."""
        start = self.clock()
        targets: list[tuple[int, object, str]] = []
        seen: set[str] = set()
        for lane, sandbox in self.executor.live_hosts():
            for url in sandbox.host_urls:
                if url in seen:
                    continue  # one sandbox can be re-pooled, not re-probed
                seen.add(url)
                targets.append((lane, sandbox, url))
        self._scope_clean = {}
        await asyncio.gather(
            *(self._probe_host(lane, sandbox, url) for lane, sandbox, url in targets)
        )
        # A disposed sandbox's host must leave the table (and the gauge) —
        # a wedged verdict on a host that no longer exists is stale noise.
        for url in list(self._hosts):
            if url not in seen:
                del self._hosts[url]
        # Settle recovery streaks AFTER the full cycle: one note per scope
        # per cycle, clean only when every host on the scope probed clean.
        self._settle_recovery()
        elapsed = max(0.0, self.clock() - start)
        self._last_cycle_end = self.clock()
        self._cycles += 1
        self.metrics.device_probe_cycle_seconds.observe(elapsed)
        return {url: h.state for url, h in self._hosts.items()}

    async def _probe_host(self, lane: int, sandbox, url: str) -> None:
        health = self._hosts.get(url)
        if health is None:
            health = HostHealth(
                lane=lane,
                sandbox_id=getattr(sandbox, "id", ""),
                host=url,
                since=self.clock(),
            )
            self._hosts[url] = health
        else:
            # The same URL can be a recycled sandbox in a new role.
            health.lane = lane
            health.sandbox_id = getattr(sandbox, "id", health.sandbox_id)
        stats: dict | None = None
        legacy = False
        try:
            resp = await self.executor._http_client().get(
                f"{url}/device-stats", timeout=self.timeout
            )
            if resp.status_code == 404:
                legacy = True  # old binary: no stats route, but it answered
            elif resp.status_code == 200:
                body = resp.json()
                if isinstance(body, dict):
                    stats = body
        except (httpx.HTTPError, ValueError):
            stats = None
        now = self.clock()
        if stats is None and not legacy:
            health.failures += 1
            if health.first_failure is None:
                health.first_failure = now
            state, reason, stall = self._classify_unreachable(health, now)
        else:
            health.failures = 0
            health.first_failure = None
            health.last_success = now
            health.legacy = legacy
            if legacy:
                # Can't see the device plane on an old binary; reachable is
                # all the evidence there is.
                state, reason, stall = HEALTHY, "legacy_binary", 0.0
            else:
                health.stats = stats
                state, reason, stall = self._classify(stats)
        state, reason = self._recovery_overlay(health, state, reason)
        self._apply(health, state, reason, stall, now)

    # ---------------------------------------------------- recovery actuation

    def _lease_state(self, health: HostHealth):
        """(registry, lease) for the host's sandbox, or (None, None) when
        fencing is not wired (no registry) or the sandbox is already gone."""
        registry = getattr(self.executor, "leases", None)
        entry = self.executor.live_sandbox(health.sandbox_id)
        if registry is None or entry is None:
            return None, None
        return registry, entry[1].meta.get("lease")

    def _recovery_overlay(
        self, health: HostHealth, state: str, reason: str
    ) -> tuple[str, str]:
        """Layer the fencing/recovery state machine over the raw
        classification. A fenced host reads DRAINING until its disposal
        prunes it from the table; a host on a recovering scope reads
        RECOVERING while it earns the clean-probe streak (its per-cycle
        verdict is banked for `_settle_recovery`), and a suspect/wedged
        relapse banks a reset instead."""
        registry, lease = self._lease_state(health)
        entry = self.executor.live_sandbox(health.sandbox_id)
        if entry is not None and entry[1].meta.get("lease_fenced"):
            return DRAINING, "fenced"
        if registry is None or lease is None or not registry.recovering(
            lease.scope
        ):
            return state, reason
        verdict = self._scope_clean.setdefault(
            lease.scope, [True, health.lane]
        )
        if state in (HEALTHY, BUSY):
            streak, need = registry.recovery_progress(lease.scope)
            return (
                RECOVERING,
                f"clean_streak_{min(streak + 1, need)}_of_{need}",
            )
        # Relapse (suspect/unreachable/wedged mid-streak): the streak
        # resets at settle time — and the host STAYS quarantined. A
        # suspect relapse must keep reading RECOVERING: the raw suspect
        # state is not in the pool's unservable set, so passing it through
        # would flip the host from standby to servable supply and hand a
        # tenant request to hardware that just showed stall symptoms —
        # exactly what the re-admission gate exists to prevent. Only a
        # WEDGED relapse passes through raw: it must re-trigger actuation
        # (budget-bounded), and wedged is unservable in its own right.
        verdict[0] = False
        if state == WEDGED:
            return state, reason
        return RECOVERING, f"relapse_{reason}" if reason else "relapse"

    def _settle_recovery(self) -> None:
        """Apply the cycle's per-scope verdicts to the lease registry and
        act on re-admissions: the scope's hosts flip to healthy NOW (the
        pool's supply gating reads the sandbox marks, and a woken waiter
        must see serving supply, not last cycle's quarantine), the
        re-admission counter fires, and every lane is kicked — waiters
        parked behind the recovering quarantine are exactly who this
        turnover is for."""
        registry = getattr(self.executor, "leases", None)
        if registry is None:
            return
        for scope, (clean, lane) in self._scope_clean.items():
            if not registry.note_probe(scope, clean=clean):
                continue
            self.metrics.host_readmitted.inc(lane=str(lane))
            for health in self._hosts.values():
                if health.state != RECOVERING:
                    continue
                _, lease = self._lease_state(health)
                if lease is None or lease.scope != scope:
                    continue
                health.state = HEALTHY
                health.reason = "readmitted"
                health.since = self.clock()
                self._mark_sandbox(health)
            self.tracer.record_span(
                "device_health.readmitted",
                trace_id=tracing.new_trace_id(),
                parent_id=None,
                start_unix=self.walltime(),
                duration_s=0.0,
                attributes={"lane": lane, "scope": scope},
            )
            kick = getattr(self.executor, "_notify_all_lanes", None)
            if kick is not None:
                kick()
        self._scope_clean = {}

    # -------------------------------------------------------- classification

    def _classify(self, stats: dict) -> tuple[str, str, float]:
        """Map one /device-stats body to (state, reason, stall seconds).
        `stall` is how far past its budget the slowest signal is — suspect
        at 0, wedged once it persists `wedge_after`."""

        def age(key: str) -> float:
            value = stats.get(key)
            return float(value) if isinstance(value, (int, float)) else 0.0

        # Attach (warm-up: jax import + libtpu init + device enumeration)
        # in flight: legitimate for minutes, wedged when it outlives the
        # budget — THE historical failure signature (rounds 3 to 5: a
        # device op that never completes).
        # warm_state "pending" alone counts too: an attach observed at age
        # zero is still an attach.
        attach_pending = age("attach_pending_s")
        if attach_pending > 0 or stats.get("warm_state") == "pending":
            stall = attach_pending - self.attach_budget
            if stall >= self.wedge_after:
                return WEDGED, "attach_stalled", stall
            if stall >= 0:
                return SUSPECT, "attach_over_budget", stall
            return BUSY, "attaching", 0.0
        # Device op in flight: budget is the op's OWN declared timeout plus
        # grace for the executor's kill/collect machinery. An op past that
        # means the timeout kill itself is stuck — the wedge, not the work.
        if stats.get("op_in_flight"):
            op_age = age("op_age_s")
            budget = age("op_timeout_s") + self.op_grace
            stall = op_age - budget
            if stall >= self.wedge_after:
                return WEDGED, "device_op_stalled", stall
            if stall >= 0:
                return SUSPECT, "device_op_over_budget", stall
            return BUSY, "device_op", 0.0
        if stats.get("warm_state") == "failed":
            # Warm-up failed: the host serves cold (or is about to be
            # disposed) — not wedged, but not healthy either.
            return SUSPECT, "warm_failed", 0.0
        if stats.get("warm_state") == "ready" and stats.get("runner_alive") is False:
            # The warm runner died SILENTLY while idle (OOM kill between
            # requests — the executor's waitid peek exposes the corpse):
            # the host would serve its next request cold and lose any
            # session state. Suspect, not wedged: the executor restarts
            # the runner in the background at next use.
            return SUSPECT, "runner_dead", 0.0
        # NOTE: runner_heartbeat_age_s is deliberately NOT thresholded
        # while the host is idle — an idle runner legitimately says
        # nothing for hours. Its age is meaningful evidence only inside
        # an attach or op window, where the attach/op stall rules above
        # already bound the same silence.
        return HEALTHY, "", 0.0

    def _classify_unreachable(
        self, health: HostHealth, now: float
    ) -> tuple[str, str, float]:
        """A host that stopped answering the stats probe entirely: suspect
        immediately, wedged once it has been dark past the wedge threshold.
        The baseline is the last successful probe (or the first failure for
        a host that never answered)."""
        base = (
            health.last_success
            if health.last_success is not None
            else health.first_failure
        )
        stall = max(0.0, now - (base if base is not None else now))
        if stall >= self.wedge_after:
            return WEDGED, "unreachable", stall
        return SUSPECT, "unreachable", stall

    # ------------------------------------------------------------ transition

    def _apply(
        self, health: HostHealth, state: str, reason: str, stall: float, now: float
    ) -> None:
        health.reason = reason
        health.stall_s = max(0.0, stall)
        previous = health.state
        if state == previous:
            self._mark_sandbox(health)
            if state == WEDGED:
                # Re-assert the verdict every cycle it stands: a fence the
                # actuator DEFERRED (budget exhausted, breaker open) gets
                # retried once the window slides, without needing a fresh
                # transition.
                self._actuate_wedge(health)
            return
        health.state = state
        health.since = now
        self._mark_sandbox(health)
        if state == WEDGED:
            self._actuate_wedge(health)
        # healthy<->busy flips are NORMAL OPERATION (every probe cycle that
        # catches a host mid-op produces one): they update state silently.
        # Only transitions touching recovering/suspect/wedged/draining —
        # entering trouble, recovering from it, or being fenced — are
        # incidents worth a log line and a span; anything louder floods the
        # log and evicts real request traces from the ring under ordinary
        # load.
        interesting = (
            _SEVERITY[state] >= _SEVERITY[RECOVERING]
            or _SEVERITY[previous] >= _SEVERITY[RECOVERING]
        )
        if not interesting:
            logger.debug(
                "device health: %s (lane=%d) %s -> %s",
                health.host,
                health.lane,
                previous,
                state,
            )
            return
        logger.log(
            logging.WARNING if _SEVERITY[state] > _SEVERITY[previous] else logging.INFO,
            "device health: %s (lane=%d, sandbox=%s) %s -> %s (%s, stall=%.1fs)",
            health.host,
            health.lane,
            health.sandbox_id,
            previous,
            state,
            reason or "recovered",
            health.stall_s,
        )
        # Suspect/wedged transitions are rare and exactly what an incident
        # review pulls up: record_span bypasses head sampling (a fresh
        # trace id, zero-duration span), so they are retrievable via
        # /traces at ANY sample ratio. Only the tracing kill switch
        # (APP_TRACING_ENABLED=0) drops them — it disables the whole
        # /traces surface, and the wedge stays visible through the
        # counter, /statusz, and the log line above.
        self.tracer.record_span(
            "device_health.transition",
            trace_id=tracing.new_trace_id(),
            parent_id=None,
            start_unix=self.walltime(),
            duration_s=0.0,
            attributes={
                "lane": health.lane,
                "host": health.host,
                "sandbox": health.sandbox_id,
                "from": previous,
                "to": state,
                "reason": reason,
                "stall_s": round(health.stall_s, 3),
            },
            status="error" if state == WEDGED else "ok",
        )
        if state == WEDGED:
            self.metrics.device_wedges.inc(chip_count=str(health.lane))

    def _actuate_wedge(self, health: HostHealth) -> None:
        """Hand the wedged verdict to the executor's fencing actuator —
        detect→act is one hop now. The actuator owns every safety bound
        (kill switch, per-lane budget, breaker state, dedupe), so calling
        it is always safe; absent actuator = detection-only (PR 8)."""
        actuate = getattr(self.executor, "on_host_wedged", None)
        if actuate is not None:
            actuate(health.sandbox_id, reason=health.reason or "wedged")

    def _mark_sandbox(self, health: HostHealth) -> None:
        """Stamp the verdict onto the sandbox itself — the handle the
        fencing layer (and /statusz consumers holding a Sandbox) will read.
        Detection only: nothing here disposes or drains."""
        entry = self.executor.live_sandbox(health.sandbox_id)
        if entry is not None:
            entry[1].meta["device_health"] = health.state

    # -------------------------------------------------------------- surfaces

    def last_poll_age(self) -> float:
        """Seconds since the last completed cycle (-1 = never completed) —
        the probe daemon's own liveness gauge."""
        if self._last_cycle_end is None:
            return -1.0
        return max(0.0, self.clock() - self._last_cycle_end)

    def gauge_samples(self) -> dict[tuple[str, ...], float]:
        """device_health_state{lane,host,state} feed, scrape-time. Under the
        host-label cap: one-hot per host. Past it: every series collapses
        to lane level (host="_overflow", value = hosts of that lane in that
        state) — the same cardinality discipline as the scheduler's tenant
        cap, applied to hosts."""
        hosts = list(self._hosts.values())
        overflow = len(hosts) > self.max_host_labels
        samples: dict[tuple[str, ...], float] = {}
        for health in hosts:
            host_label = "_overflow" if overflow else health.host
            if overflow:
                key = (str(health.lane), host_label, health.state)
                samples[key] = samples.get(key, 0.0) + 1.0
            else:
                for state in STATES:
                    key = (str(health.lane), host_label, state)
                    samples[key] = 1.0 if state == health.state else 0.0
        return samples

    def states(self) -> dict[str, str]:
        return {url: h.state for url, h in self._hosts.items()}

    def lane_census(self) -> dict[int, dict[str, int]]:
        """Per-lane state counts for the /healthz lane rows (satellite: an
        operator watching /healthz should see a lane's wedged/recovering
        hosts next to its queue and supply numbers, without a /statusz
        round-trip). Only states with a nonzero count appear — a healthy
        fleet's rows stay as small as before."""
        census: dict[int, dict[str, int]] = {}
        for health in self._hosts.values():
            lane = census.setdefault(health.lane, {})
            lane[health.state] = lane.get(health.state, 0) + 1
        return census

    def snapshot(self) -> dict:
        """The /statusz device-health block: per-host rows plus a state
        census and the probe's own liveness."""
        hosts = [h.snapshot() for h in self._hosts.values()]
        hosts.sort(key=lambda row: (row["lane"], row["host"]))
        census: dict[str, int] = {state: 0 for state in STATES}
        for health in self._hosts.values():
            census[health.state] = census.get(health.state, 0) + 1
        return {
            "enabled": self.interval > 0,
            "interval_s": self.interval,
            "thresholds": {
                "attach_budget_s": self.attach_budget,
                "op_grace_s": self.op_grace,
                "wedge_after_s": self.wedge_after,
            },
            "cycles": self._cycles,
            "last_poll_age_s": round(self.last_poll_age(), 3),
            "states": census,
            "hosts": hosts,
        }
