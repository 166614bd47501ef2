"""Per-chip lease fencing: generation tokens and gated re-admission.

The device-health probe (PR 8) can SAY a host is wedged; nothing could
safely ACT on that verdict, because disposal alone does not protect the
replacement — the repo's own outage history (rounds 3 to 5: a device op
that never completes, 50-76 minutes of manual recovery by host reboot) is
precisely a stale claim wedging a chip for the next holder: a zombie runner still
holding libtpu, a late-arriving dispatch, a retry racing a dispose. This
module is the fencing primitive that makes dispose-and-replace safe:

- **Generation tokens** — every sandbox spawn mints a monotonic generation
  per lease *scope* (the physical chip-set the sandbox attaches: the
  backend's `lease_scope`, or the chip-count lane by default). The token is
  pushed to the sandbox's executor at attach (`POST /lease`) and stamped on
  every dispatch (`x-lease-token`); an executor holding a NEWER token
  rejects a stale claim with a typed ``409 stale_lease`` before taking any
  lock — a claim minted for a fenced predecessor can never reach the
  successor's device plane, not even to queue behind it.
- **Fencing** — a wedged verdict revokes the host's lease. The control
  plane refuses to dispatch against a revoked lease (typed
  ``StaleLeaseError``, a clean refusal that bills nothing), and the scope's
  next mint is strictly newer, so the successor's executor can tell every
  pre-fence token apart from its own.
- **Gated re-admission** — a fenced scope enters ``recovering``: hosts on
  it (the replacement lands on the same hardware) are probed but serve
  nothing until ``APP_DEVICE_PROBE_READMIT_STREAK`` consecutive clean
  probes; a suspect/wedged relapse resets the streak. Re-admission fires
  ``host_readmitted_total`` and wakes the lanes that were waiting out the
  quarantine.

Scopes deliberately name HARDWARE, not sandboxes: on the local backend
every warm sandbox holds the same physical TPU, so one scope per lane is
exactly the chip-set; on Kubernetes a backend can expose finer scopes via
``lease_scope(chip_count)``. Keying recovery by scope is what makes "the
replacement on the same hardware must re-earn trust" expressible at all.

Event-loop discipline like the scheduler: plain synchronous state driven
from the executor's loop; the clock is injectable so every fencing test
runs with zero sleeps.
"""

from __future__ import annotations

import logging
import time
from collections.abc import Callable
from dataclasses import dataclass, field

from .errors import StateStoreDegradedError
from .state_store import STORE_UNAVAILABLE_ERRORS

logger = logging.getLogger(__name__)

# What a shared-store op can throw when the store is gone: the raw
# transport/file errors (registry wired with a bare store) plus the typed
# degraded refusal (registry wired with the ResilientStateStore wrapper,
# whose FENCED policy fails lease writes closed).
_STORE_DOWN = (StateStoreDegradedError, *STORE_UNAVAILABLE_ERRORS)


@dataclass
class Lease:
    """One sandbox's claim on its scope's chips. Identity object: the
    executor compares `wire_token` strings for equality, the control plane
    checks `revoked` before every dispatch."""

    scope: str
    generation: int
    sandbox_id: str = ""
    revoked: bool = False
    revoke_reason: str = ""

    @property
    def wire_token(self) -> str:
        """The token as it rides the wire (`x-lease-token` header and the
        `POST /lease` body): scope-qualified so a mis-routed dispatch is
        diagnosable from the 409 body alone."""
        return f"{self.scope}:{self.generation}"


@dataclass
class _ScopeRecovery:
    """A fenced scope's re-admission state: how many consecutive clean
    probes its current hardware has shown, out of how many required."""

    streak: int = 0
    need: int = 1
    since: float = 0.0
    relapses: int = 0
    reason: str = ""


class LeaseRegistry:
    """Mints, revokes, and re-admits per-scope generation leases."""

    def __init__(
        self,
        *,
        readmit_streak: int = 3,
        clock: Callable[[], float] = time.monotonic,
        store=None,
        walltime: Callable[[], float] = time.time,
    ) -> None:
        self.readmit_streak = max(1, readmit_streak)
        self.clock = clock
        self.walltime = walltime
        # Shared-state seam (services/state_store.py): with a SHARED store
        # wired, generations mint from one fleet-wide counter per scope
        # (ns="lease_gen") and fences publish a generation FLOOR per scope
        # (ns="lease_fence") — every replica's leases at-or-below the
        # floor are stale, so a host fenced by replica A is refused by
        # replica B's dispatch and pool-pop paths even though B never saw
        # the fence happen. A private store (the default) leaves every
        # path byte-for-byte as before.
        self._store = store if store is not None and store.shared else None
        self._generations: dict[str, int] = {}
        self._recovering: dict[str, _ScopeRecovery] = {}
        # Degraded-mode state (shared store unreachable): last-seen fence
        # floors from successful reads (floors only rise, so a stale value
        # can only under-refuse — and mints fail closed, so nothing new is
        # granted off it), plus floor publishes a fence performed during
        # the outage still owes the fleet (max-merged in, so replay in any
        # order against any peer's concurrent raise is safe).
        self._floor_cache: dict[str, int] = {}
        self._pending_floors: dict[str, int] = {}
        self.fences_total = 0
        self.readmissions_total = 0
        self.degraded_mint_refusals = 0

    # ---------------------------------------------------------------- leases

    def mint(self, scope: str, sandbox_id: str = "") -> Lease:
        """A fresh lease for `scope`, strictly newer than every lease the
        scope ever issued — the monotonicity the executor-side stale check
        rests on. In shared mode the generation comes from the fleet-wide
        counter, so replicas can never mint the same generation twice."""
        if self._store is not None:
            try:
                generation = int(self._store.incr("lease_gen", scope))
            except StateStoreDegradedError:
                self.degraded_mint_refusals += 1
                raise
            except STORE_UNAVAILABLE_ERRORS as e:
                # FAIL CLOSED, always — even when the registry holds a bare
                # store with no resilience wrapper. A partitioned replica
                # minting off its last-seen counter could reissue a
                # generation a peer already granted (or fenced): the one
                # degraded behavior this module can never allow.
                self.degraded_mint_refusals += 1
                raise StateStoreDegradedError(
                    f"lease mint for scope {scope!r} refused: shared "
                    f"generation counter unreachable ({e})",
                    subsystem="leases",
                ) from e
            self._flush_pending_floors()
            self._generations[scope] = max(
                self._generations.get(scope, 0), generation
            )
        else:
            generation = self._generations.get(scope, 0) + 1
            self._generations[scope] = generation
        return Lease(scope=scope, generation=generation, sandbox_id=sandbox_id)

    def current_generation(self, scope: str) -> int:
        return self._generations.get(scope, 0)

    def fence(self, lease: Lease, *, reason: str = "wedged") -> None:
        """Revoke the lease and put its scope into recovering. Idempotent:
        fencing an already-revoked lease changes nothing (the probe may
        re-report a wedge while the dispose is still in flight)."""
        if lease.revoked:
            return
        lease.revoked = True
        lease.revoke_reason = reason
        self.fences_total += 1
        # Burn the generation forward so even a mint racing this fence can
        # never reissue the revoked token.
        self._generations[lease.scope] = max(
            self._generations.get(lease.scope, 0), lease.generation
        )
        self._recovering[lease.scope] = _ScopeRecovery(
            streak=0,
            need=self.readmit_streak,
            since=self.clock(),
            reason=reason,
        )
        if self._store is not None:
            # Publish the generation FLOOR and the recovering record
            # SEPARATELY: the floor is permanent (every lease at-or-below
            # it is stale forever — a peer's pooled host that idled
            # through the whole recovery window must still be refused
            # after re-admission, because its process sat through the
            # wedge), while the recovering record lives only until the
            # clean-probe streak completes (whichever replica's probes
            # complete it).
            def _raise_floor(current):
                floor = lease.generation
                if isinstance(current, (int, float)):
                    floor = max(floor, int(current))
                return floor, None

            def _fence_record(current):
                return (
                    {
                        "reason": reason,
                        "since_wall": self.walltime(),
                        "streak": 0,
                        "need": self.readmit_streak,
                        "relapses": 0,
                    },
                    None,
                )

            try:
                self._store.mutate("lease_floor", lease.scope, _raise_floor)
                self._store.mutate("lease_fence", lease.scope, _fence_record)
            except _STORE_DOWN as e:
                # The LOCAL half already happened (revocation, generation
                # burn, recovering record) — this replica refuses the host
                # either way. What the outage withheld is the FLEET's view:
                # queue the floor raise and replay it on the next healthy
                # store op (floors max-merge, so late replay against a
                # peer's newer floor is a no-op). Until then a peer may
                # keep serving this scope off pre-fence leases — the same
                # exposure as the fence simply racing the outage.
                self._pending_floors[lease.scope] = max(
                    self._pending_floors.get(lease.scope, 0),
                    lease.generation,
                )
                logger.warning(
                    "lease fence for scope=%s could not publish to the "
                    "shared store (%s): floor %d queued for replay on "
                    "reconnect",
                    lease.scope,
                    e,
                    lease.generation,
                )
        logger.warning(
            "lease fenced: scope=%s generation=%d sandbox=%s (%s); "
            "re-admission needs %d clean probes",
            lease.scope,
            lease.generation,
            lease.sandbox_id,
            reason,
            self.readmit_streak,
        )

    @staticmethod
    def revoked(lease: Lease | None) -> bool:
        return lease is not None and lease.revoked

    def stale(self, lease: Lease | None) -> bool:
        """Is this lease no longer honorable? Locally revoked, or (shared
        mode) at-or-below the scope's published fence floor — the check
        that makes "a host fenced by replica A is never granted by
        replica B" true: B's pool-pop and dispatch paths consult this
        even though B never observed A's fence."""
        if lease is None:
            return False
        if lease.revoked:
            return True
        if self._store is not None:
            # A fence this replica performed during an outage refuses its
            # scope immediately, before the floor ever lands remotely.
            pending = self._pending_floors.get(lease.scope)
            if pending is not None and lease.generation <= pending:
                return True
            # Deliberately UNCACHED (unlike the breaker's 0.25s remote
            # cache): this read is the only thing standing between a
            # peer's fence and this replica granting the fenced host — a
            # freshness window here would be a grant-a-wedged-host window.
            # WAL readers never block on writers, so the cost is one
            # ~tens-of-µs point read per dispatch/pool-candidate.
            try:
                floor = self._store.get("lease_floor", lease.scope)
            except _STORE_DOWN:
                # Store gone: serve off the last floor a healthy read saw.
                # Floors only rise, so the cache can only UNDER-refuse —
                # and the thing it could miss (a peer's fence during the
                # outage) cannot strand a wedge on THIS replica: mints are
                # refused store-down, so no new local lease lands on the
                # scope, and existing leases predate the peer's fence by
                # construction.
                floor = self._floor_cache.get(lease.scope)
            else:
                if isinstance(floor, (int, float)):
                    self._floor_cache[lease.scope] = int(floor)
                self._flush_pending_floors()
            if isinstance(floor, (int, float)) and lease.generation <= floor:
                # The floor survives re-admission on purpose: the scope's
                # HARDWARE re-earned trust, but a pre-fence lease names a
                # sandbox process that sat through the wedge — only
                # post-fence generations serve.
                return True
        return False

    def _flush_pending_floors(self) -> None:
        """Replay floor raises a store-down fence left owing, on the first
        healthy store op that notices them. Max-merge makes replay order
        irrelevant; a relapse mid-flush just leaves the remainder queued."""
        if not self._pending_floors:
            return
        for scope, generation in list(self._pending_floors.items()):

            def _raise_floor(current, generation=generation):
                floor = generation
                if isinstance(current, (int, float)):
                    floor = max(floor, int(current))
                return floor, None

            try:
                self._store.mutate("lease_floor", scope, _raise_floor)
            except _STORE_DOWN:
                return
            self._pending_floors.pop(scope, None)
            self._floor_cache[scope] = max(
                self._floor_cache.get(scope, 0), generation
            )
            logger.info(
                "replayed queued fence floor: scope=%s floor=%d",
                scope,
                generation,
            )

    # ------------------------------------------------------------ recovering

    def recovering(self, scope: str) -> bool:
        if self._store is not None:
            # Shared mode: the store is authoritative. A local mirror
            # whose shared record is gone means a PEER's probes completed
            # the streak — drop the mirror so this replica's gates open
            # too (its lanes re-evaluate on the next sweep kick).
            try:
                record = self._store.get("lease_fence", scope)
            except _STORE_DOWN:
                return scope in self._recovering
            if record is not None:
                return True
            if getattr(self._store, "degraded", False):
                # A degraded wrapper answers reads from its last-known
                # cache: an absence there is NOT evidence a peer finished
                # the streak — keep the local mirror authoritative until
                # a healthy read says otherwise.
                return scope in self._recovering
            self._recovering.pop(scope, None)
            return False
        return scope in self._recovering

    def recovery_progress(self, scope: str) -> tuple[int, int]:
        """(clean streak so far, streak required); (0, 0) when the scope is
        not recovering."""
        state = self._recovering.get(scope)
        if state is None:
            return 0, 0
        return state.streak, state.need

    def note_probe(self, scope: str, *, clean: bool) -> bool:
        """One probe verdict for a recovering scope's hardware. Clean
        (healthy/busy) probes advance the streak; a suspect/wedged relapse
        resets it — the fenced hardware must prove a CONSECUTIVE run of
        good behavior, not a lucky sample. Returns True exactly once, when
        the streak completes and the scope re-admits."""
        state = self._recovering.get(scope)
        if self._store is not None:
            # Shared mode: the store's record is AUTHORITATIVE, and the
            # whole read-advance-write runs inside ONE store mutation —
            # both replicas' probes advance a single streak over the same
            # hardware, and a peer's concurrent relapse can never be lost
            # to a get-then-write interleave (the scope must prove a
            # CONSECUTIVE clean run, fleet-wide).
            def step(current):
                if current is None:
                    return None, ("absent", None)
                record = dict(current) if isinstance(current, dict) else {}
                if not clean:
                    record["streak"] = 0
                    record["relapses"] = int(record.get("relapses", 0) or 0) + 1
                    return record, ("relapse", record)
                streak = int(record.get("streak", 0) or 0) + 1
                need = int(record.get("need", self.readmit_streak) or 1)
                if streak >= need:
                    return None, ("readmit", record)
                record["streak"] = streak
                return record, ("advance", record)

            try:
                verdict, record = self._store.mutate(
                    "lease_fence", scope, step
                )
            except _STORE_DOWN:
                # Store down: keep the consecutive-streak contract alive on
                # the LOCAL mirror so this replica's own probes still gate
                # its own re-admission. On reconnect the shared record —
                # still standing with its pre-outage streak — is
                # authoritative again, so the fleet may ask the hardware
                # for a few extra clean probes. Conservative by design:
                # degraded mode must never re-admit EARLIER than the
                # healthy path would.
                return self._note_probe_local(scope, clean)
            if verdict == "absent":
                if state is not None:
                    # A peer's probe completed the streak: mirror the
                    # re-admission here so this replica settles its lanes.
                    del self._recovering[scope]
                    self.readmissions_total += 1
                    logger.info(
                        "lease scope %s re-admitted (completed by a peer "
                        "replica's probes)",
                        scope,
                    )
                    return True
                return False
            # Mirror the post-step record locally (statusz/progress reads).
            if state is None:
                state = _ScopeRecovery(
                    since=self.clock(),
                    reason=str(record.get("reason", "") or ""),
                )
                self._recovering[scope] = state
            state.need = int(record.get("need", self.readmit_streak) or 1)
            state.relapses = int(record.get("relapses", 0) or 0)
            if verdict == "relapse":
                if state.streak:
                    logger.info(
                        "lease scope %s relapsed mid-recovery "
                        "(streak was %d/%d)",
                        scope,
                        state.streak,
                        state.need,
                    )
                state.streak = 0
                return False
            if verdict == "advance":
                state.streak = int(record.get("streak", 0) or 0)
                return False
            # verdict == "readmit": the mutation already deleted the
            # shared record — finish locally.
            del self._recovering[scope]
            self.readmissions_total += 1
            logger.info(
                "lease scope %s re-admitted after %d clean probes "
                "(%.1fs in recovery, %d relapse(s))",
                scope,
                state.need,
                max(0.0, self.clock() - state.since),
                state.relapses,
            )
            return True
        # Private-store path from here: today's single-process semantics.
        return self._note_probe_local(scope, clean)

    def _note_probe_local(self, scope: str, clean: bool) -> bool:
        """The registry-local streak step: the private-store semantics,
        doubling as the degraded-mode fallback while a shared store is
        unreachable."""
        state = self._recovering.get(scope)
        if state is None:
            return False
        if not clean:
            if state.streak:
                logger.info(
                    "lease scope %s relapsed mid-recovery (streak was %d/%d)",
                    scope,
                    state.streak,
                    state.need,
                )
            state.streak = 0
            state.relapses += 1
            return False
        state.streak += 1
        if state.streak < state.need:
            return False
        del self._recovering[scope]
        self.readmissions_total += 1
        logger.info(
            "lease scope %s re-admitted after %d clean probes "
            "(%.1fs in recovery, %d relapse(s))",
            scope,
            state.need,
            max(0.0, self.clock() - state.since),
            state.relapses,
        )
        return True

    # -------------------------------------------------------------- surfaces

    def snapshot(self) -> dict:
        """The /statusz recovery block's lease half: per-scope generations
        and any in-flight re-admission streaks."""
        now = self.clock()
        recovering = {
            scope: {
                "streak": state.streak,
                "need": state.need,
                "relapses": state.relapses,
                "for_s": round(max(0.0, now - state.since), 3),
                "reason": state.reason,
            }
            for scope, state in sorted(self._recovering.items())
        }
        if self._store is not None:
            # Peers' standing fences surface here too: an operator reading
            # ANY replica's /statusz sees every scope the fleet is
            # quarantining, not just the ones this process fenced.
            wall = self.walltime()
            try:
                fences = self._store.items("lease_fence")
            except _STORE_DOWN:
                fences = {}  # statusz stays serveable through an outage
            for scope, record in sorted(fences.items()):
                if scope in recovering or not isinstance(record, dict):
                    continue
                since = record.get("since_wall")
                recovering[scope] = {
                    "streak": int(record.get("streak", 0) or 0),
                    "need": int(record.get("need", self.readmit_streak) or 1),
                    "relapses": int(record.get("relapses", 0) or 0),
                    "for_s": round(
                        max(0.0, wall - since)
                        if isinstance(since, (int, float))
                        else 0.0,
                        3,
                    ),
                    "reason": str(record.get("reason", "") or ""),
                }
        return {
            "readmit_streak": self.readmit_streak,
            "fences_total": self.fences_total,
            "readmissions_total": self.readmissions_total,
            "degraded_mint_refusals": self.degraded_mint_refusals,
            "pending_fence_floors": dict(sorted(self._pending_floors.items())),
            "generations": dict(sorted(self._generations.items())),
            "recovering": recovering,
        }
