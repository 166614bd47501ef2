"""Store-loss tolerance: the control plane must survive the shared store
dying. Covers the ResilientStateStore wrapper's per-namespace degraded
policies (shadow / fenced / journal / fail_closed), the health breaker's
transitions and heal (journal replay, shadow drop), the seeded
store-outage fault injector's determinism, and the subsystem halves —
lease mints failing closed with fence floors queued for replay, quota
fleet windows failing open, session restore refusing typed.
"""

import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.faults import (
    FaultInjectingStateStore,
    StoreFaultSpec,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.errors import StateStoreDegradedError
from bee_code_interpreter_fs_tpu.services.leases import LeaseRegistry
from bee_code_interpreter_fs_tpu.services.quotas import _FleetWindows
from bee_code_interpreter_fs_tpu.services.session_store import (
    SESSION_NS,
    SessionStore,
)
from bee_code_interpreter_fs_tpu.services.state_store import (
    InMemoryStateStore,
    ResilientStateStore,
    StateStoreUnavailableError,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage


class FlakyStore(InMemoryStateStore):
    """An in-memory store with a kill switch: `down=True` makes every op
    raise the transport error — the deterministic outage the wrapper and
    the subsystems are exercised against."""

    def __init__(self) -> None:
        super().__init__(shared=True)
        self.down = False
        self.ops = 0

    def _gate(self):
        self.ops += 1
        if self.down:
            raise StateStoreUnavailableError("store is down (test)")

    def get(self, ns, key):
        self._gate()
        return super().get(ns, key)

    def put(self, ns, key, value):
        self._gate()
        return super().put(ns, key, value)

    def delete(self, ns, key):
        self._gate()
        return super().delete(ns, key)

    def items(self, ns):
        self._gate()
        return super().items(ns)

    def incr(self, ns, key, delta=1.0):
        self._gate()
        return super().incr(ns, key, delta)

    def mutate(self, ns, key, fn):
        self._gate()
        return super().mutate(ns, key, fn)


class Clock:
    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now


def resilient(**kwargs):
    # `inner=` hands several wrappers (replicas) one store.
    inner = kwargs.pop("inner", None) or FlakyStore()
    clock = kwargs.pop("clock", None) or Clock()
    kwargs.setdefault("failure_threshold", 2)
    kwargs.setdefault("cooldown", 5.0)
    wrapper = ResilientStateStore(inner, clock=clock, **kwargs)
    return wrapper, inner, clock


# ------------------------------------------------------- per-namespace policy


def test_shadow_namespaces_fail_open_replica_local():
    store, inner, clock = resilient()
    store.put("wfq", "tenant-a", {"tag": 3.0})
    inner.down = True
    # Fail open: reads fall back (shadow starts empty — fleet coherence is
    # what the outage costs), writes land replica-locally and keep working.
    assert store.get("wfq", "tenant-a") is None
    store.put("wfq", "tenant-a", {"tag": 7.0})
    assert store.get("wfq", "tenant-a") == {"tag": 7.0}
    assert store.mutate(
        "breaker", "lane-4", lambda cur: ({"state": "open"}, "ok")
    ) == "ok"
    assert store.items("breaker") == {"lane-4": {"state": "open"}}
    assert store.degraded and store.degraded_ops > 0
    # The inner store never saw the degraded writes.
    inner.down = False
    assert inner.get("wfq", "tenant-a") == {"tag": 3.0}


def test_fenced_reads_serve_cache_writes_refuse():
    store, inner, clock = resilient()
    store.put("lease_floor", "host-1", 12)
    assert store.get("lease_floor", "host-1") == 12  # primes the cache
    store.items("lease_floor")
    inner.down = True
    # Reads serve the last-known value (floors only rise: stale can only
    # under-refuse)...
    assert store.get("lease_floor", "host-1") == 12
    assert store.items("lease_floor") == {"host-1": 12}
    # ...while every write fails closed with the typed error.
    with pytest.raises(StateStoreDegradedError) as exc:
        store.put("lease_floor", "host-1", 13)
    assert exc.value.subsystem == "leases"
    assert exc.value.retry_after >= 1.0
    with pytest.raises(StateStoreDegradedError):
        store.incr("lease_gen", "host-1")
    with pytest.raises(StateStoreDegradedError):
        store.mutate("lease_fence", "host-1", lambda cur: ({}, None))


def test_fail_closed_namespace_refuses_everything():
    store, inner, clock = resilient()
    store.put("session_durable", "t/sess", {"seq": 3})
    inner.down = True
    for op in (
        lambda: store.get("session_durable", "t/sess"),
        lambda: store.items("session_durable"),
        lambda: store.put("session_durable", "t/sess", {"seq": 4}),
        lambda: store.delete("session_durable", "t/sess"),
    ):
        with pytest.raises(StateStoreDegradedError) as exc:
            op()
        assert exc.value.subsystem == "sessions"


def test_journal_incrs_replay_on_reconnect():
    store, inner, clock = resilient()
    store.incr("quota_win", "t|chip|100", 5.0)
    inner.down = True
    # Fail open: accrual keeps counting replica-locally...
    assert store.incr("quota_win", "t|chip|100", 2.0) == 2.0
    assert store.incr("quota_win", "t|chip|100", 3.0) == 5.0
    assert store.health()["journal_depth"] == 2
    # ...and the journal replays into the real store on the first healthy
    # op (increments are commutative — nothing double-counts, nothing is
    # lost).
    inner.down = False
    clock.now += 6.0  # past the breaker cooldown: next op probes through
    store.get("wfq", "anything")
    assert inner.get("quota_win", "t|chip|100") == 10.0
    assert store.health()["journal_depth"] == 0
    assert store.journal_replays == 1
    assert not store.degraded


def test_ttl_helpers_follow_namespace_policy():
    """put_ttl/get_live ride the __ttl__: sidecar namespace — policy must
    strip the prefix (a lease_fence TTL record is still FENCED)."""
    store, inner, clock = resilient()
    store.put_ttl("replicas", "r1", {"load": 2}, 30.0, now=0.0)
    inner.down = True
    # replicas is SHADOW: heartbeats keep working replica-locally.
    store.put_ttl("replicas", "r1", {"load": 5}, 30.0, now=1.0)
    assert store.get_live("replicas", "r1", now=2.0) == {"load": 5}
    with pytest.raises(StateStoreDegradedError):
        store.put_ttl("lease_fence", "host-1", {"reason": "wedged"}, 30.0)


# ------------------------------------------------------- breaker transitions


def test_breaker_opens_stops_hammering_and_heals():
    store, inner, clock = resilient(failure_threshold=2, cooldown=5.0)
    inner.down = True
    store.get("wfq", "k")
    store.get("wfq", "k")
    assert store.degraded and store.outages == 1
    # Breaker open: degraded ops stop touching the dead store entirely.
    before = inner.ops
    for _ in range(10):
        store.get("wfq", "k")
    assert inner.ops == before
    # Cooldown elapses -> half-open probe-through; the store is back, one
    # success heals.
    inner.down = False
    clock.now += 6.0
    store.get("wfq", "k")
    assert not store.degraded
    assert store.health()["state"] == "closed"
    # A second outage counts as a new outage (transition-edged).
    inner.down = True
    store.get("wfq", "k")
    assert store.outages == 2


def test_probe_forces_the_health_question():
    store, inner, clock = resilient()
    inner.down = True
    store.get("wfq", "k")
    store.get("wfq", "k")
    assert store.degraded
    inner.down = False
    assert store.probe() is False  # breaker still open, probe refused
    clock.now += 6.0
    assert store.probe() is True
    assert not store.degraded


# ------------------------------------------------- seeded outage injection


def test_store_fault_spec_outage_is_deterministic():
    spec = StoreFaultSpec.parse("outage_after:3,outage_ops:2,seed:7")
    outcomes = []
    store = FaultInjectingStateStore(InMemoryStateStore(shared=True), spec)
    for i in range(12):
        try:
            store.put("ns", f"k{i}", i)
            outcomes.append(1)
        except StateStoreUnavailableError:
            outcomes.append(0)
    # Periodic and reproducible: 3 healthy ops, then the tripping op plus
    # outage_ops more fail (3 failures), repeat.
    assert outcomes == [1, 1, 1, 0, 0, 0, 1, 1, 1, 0, 0, 0]


def test_store_fault_spec_drop_rate_seeded():
    spec = StoreFaultSpec.parse("drop:0.5,seed:1337")
    runs = []
    for _ in range(2):
        store = FaultInjectingStateStore(
            InMemoryStateStore(shared=True),
            StoreFaultSpec.parse("drop:0.5,seed:1337"),
        )
        outcome = []
        for i in range(20):
            try:
                store.incr("ns", "k")
                outcome.append(1)
            except StateStoreUnavailableError:
                outcome.append(0)
        runs.append(outcome)
    assert runs[0] == runs[1]  # same seed, same plan
    assert 0 < sum(runs[0]) < 20  # actually dropping, not all-or-nothing
    assert spec.active


def test_partition_wraps_one_replica_only():
    """An asymmetric partition: replica A's handle is faulted, replica B's
    is not — B keeps full service against the same backing state."""
    backing = InMemoryStateStore(shared=True)
    a = FaultInjectingStateStore(
        backing, StoreFaultSpec.parse("drop:1.0,seed:7")
    )
    b = backing
    with pytest.raises(StateStoreUnavailableError):
        a.put("ns", "k", 1)
    b.put("ns", "k", 2)
    assert b.get("ns", "k") == 2


# ------------------------------------------------------------ lease half


def test_lease_mint_fails_closed_during_outage():
    store, inner, clock = resilient()
    registry = LeaseRegistry(store=store)
    lease = registry.mint("host-1")
    assert lease.generation == 1
    inner.down = True
    with pytest.raises(StateStoreDegradedError):
        registry.mint("host-1")
    assert registry.degraded_mint_refusals == 1
    # The existing lease keeps serving: not revoked, floor cache empty.
    assert not registry.stale(lease)
    # Store heals (breaker cooldown elapses): minting resumes on the
    # fleet counter, strictly newer.
    inner.down = False
    clock.now += 6.0
    assert registry.mint("host-1").generation == 2


def test_fence_during_outage_queues_floor_and_replays():
    store, inner, clock = resilient()
    registry = LeaseRegistry(store=store)
    lease = registry.mint("host-1")
    inner.down = True
    registry.fence(lease, reason="wedged")
    # The local half landed: the lease is refused HERE immediately, off
    # the pending floor, before the store ever hears about it.
    assert lease.revoked
    assert registry.stale(lease)
    assert registry.snapshot()["pending_fence_floors"] == {"host-1": 1}
    # Reconnect: the next healthy lease op flushes the floor to the fleet.
    inner.down = False
    clock.now += 6.0
    registry.mint("host-2")
    assert registry.snapshot()["pending_fence_floors"] == {}
    assert inner.get("lease_floor", "host-1") == 1


def test_stale_serves_cached_floor_during_outage():
    store, inner, clock = resilient()
    registry_a = LeaseRegistry(store=store)
    lease_old = registry_a.mint("host-1")
    lease_new = registry_a.mint("host-1")
    # A peer's fence raised the floor past the old lease; a healthy stale()
    # read caches it.
    inner.put("lease_floor", "host-1", 1)
    assert registry_a.stale(lease_old)
    assert not registry_a.stale(lease_new)
    inner.down = True
    # Outage: the cached floor still refuses the stale lease and still
    # serves the live one.
    assert registry_a.stale(lease_old)
    assert not registry_a.stale(lease_new)


@pytest.mark.parametrize("replicas", [2, 3])
def test_zero_double_grants_across_replicas_through_outage(replicas):
    """Generations minted by several replicas around an outage never
    collide (fencing tokens stay unique), and every replica refuses to mint
    while the store is down."""
    inner = FlakyStore()
    clocks = [Clock() for _ in range(replicas)]
    # Every replica shares the same inner store through its own wrapper.
    registries = [
        LeaseRegistry(store=resilient(inner=inner, clock=clock)[0])
        for clock in clocks
    ]
    minted = [registry.mint("host-1") for registry in registries]
    inner.down = True
    for registry in registries:
        with pytest.raises(StateStoreDegradedError):
            registry.mint("host-1")
    assert [r.degraded_mint_refusals for r in registries] == [1] * replicas
    inner.down = False
    for clock in clocks:
        clock.now += 6.0
    minted += [registry.mint("host-1") for registry in reversed(registries)]
    generations = [lease.generation for lease in minted]
    assert len(set(generations)) == len(generations)
    assert generations == sorted(generations)


# ------------------------------------------------------------ quota half


@pytest.mark.parametrize("replicas, adds", [(1, 1), (3, 5)])
def test_fleet_windows_fail_open_and_reconcile(replicas, adds):
    clock = Clock(now=1000.0)
    inner = FlakyStore()
    stores = [resilient(inner=inner, clock=clock)[0] for _ in range(replicas)]
    fleets = [_FleetWindows(store, walltime=clock) for store in stores]
    fleets[0].add("tenant-a", "chip", 10.0, window=80.0)
    assert fleets[0].used("tenant-a", "chip", 80.0) == 10.0
    inner.down = True
    # Outage: accrual fails OPEN — publish keeps succeeding against the
    # wrapper (journal), the fleet view degrades to whatever the shadow
    # holds, and nothing raises on the admit path.
    for fleet in fleets:
        for _ in range(adds):
            fleet.add("tenant-a", "chip", 1.0, window=80.0)
    clock.now += 1.0  # age past the items() read TTL
    # Each replica sees its own shadow, not its peers'.
    assert [f.used("tenant-a", "chip", 80.0) for f in fleets] == [
        float(adds)
    ] * replicas
    # The wrapper absorbed it: no raw failure.
    assert [f.publish_errors for f in fleets] == [0] * replicas
    # Reconnect: journaled deltas replay; within one window a FRESH handle
    # (no replica-local state) reads the full accrual of every replica.
    inner.down = False
    clock.now += 6.0  # past the breaker cooldown
    for store in stores:
        store.get("wfq", "poke")  # heal + replay
        assert store.health()["journal_depth"] == 0
    clock.now += 1.0
    fresh = _FleetWindows(inner, walltime=clock)
    assert fresh.used("tenant-a", "chip", 80.0) == 10.0 + replicas * adds


def test_fleet_windows_bare_store_outage_counts_publish_errors():
    """Against a BARE store (resilience wrapper off) the fleet half still
    fails open — deltas are lost to the fleet but admission never breaks."""
    clock = Clock(now=1000.0)
    inner = FlakyStore()
    fleet = _FleetWindows(inner, walltime=clock)
    inner.down = True
    fleet.add("tenant-a", "chip", 5.0, window=80.0)
    clock.now += 1.0
    assert fleet.used("tenant-a", "chip", 80.0) == 0.0
    assert fleet.publish_errors >= 1
    assert fleet.snapshot()["publish_errors"] == fleet.publish_errors


# ---------------------------------------------------------- session half


class WallClock:
    def __init__(self, now=1000.0):
        self.now = now

    def __call__(self):
        return self.now


async def test_session_restore_fails_closed_observers_fail_open(tmp_path):
    store, inner, clock = resilient()
    sessions = SessionStore(
        tmp_path / "session-store",
        store,
        Storage(tmp_path / "objects"),
        clock=WallClock(),
    )
    ws = {"a.txt": await Storage(tmp_path / "objects").write(b"bytes")}
    assert (
        await sessions.save(
            "t1", "sess-a", lane=4, seq=1, interp_state={}, workspace=ws
        )
        == "admitted"
    )
    assert sessions.hibernated_by_lane() == {4: 1}
    inner.down = True
    # Restore fails CLOSED with the typed error (restoring blind would
    # fork the session when the checkpoint reappears)...
    with pytest.raises(StateStoreDegradedError) as exc:
        await sessions.load("t1", "sess-a")
    assert exc.value.subsystem == "sessions"
    # ...while observational surfaces fail open (sweep survives, counts
    # serve the last-known view, hibernated supply stays visible).
    assert sessions.sweep_expired() == 0
    assert sessions.entry_count() == 0
    assert sessions.hibernated_by_lane() == {4: 1}  # cached view
    # Save degrades to the existing "error" outcome, never an exception.
    assert (
        await sessions.save(
            "t1", "sess-b", lane=2, seq=1, interp_state={}, workspace=ws
        )
        == "error"
    )
    inner.down = False
    clock.now += 6.0
    record = await sessions.load("t1", "sess-a")
    assert record is not None and record["seq"] == 1
    assert inner.get(SESSION_NS, "t1/sess-a") is not None


# ---------------------------------------------------------- executor level


@pytest.mark.parametrize("replicas", [2, 3])
async def test_replicas_keep_serving_through_store_outage(tmp_path, replicas):
    """The store-loss drill at the executor: control-plane replicas over
    ONE shared store, each behind its own resilience wrapper, with their
    sandboxes warm when the store dies. Every turn sent during the outage is
    served, a mint is refused by every replica (fail closed), and no (scope,
    generation) pair is granted twice before, during or after it."""
    inner = FlakyStore()
    clocks = [Clock() for _ in range(replicas)]
    executors = []
    minted: list[tuple[str, int]] = []

    def recording(registry):
        mint = registry.mint

        def recorded(scope, sandbox_id=""):
            lease = mint(scope, sandbox_id)
            minted.append((lease.scope, lease.generation))
            return lease

        registry.mint = recorded

    async def post_execute(client, base, payload, timeout, sandbox):
        return {
            "stdout": "ok\n",
            "stderr": "",
            "exit_code": 0,
            "files": [],
            "warm": True,
        }

    for index in range(replicas):
        config = Config(
            file_storage_path=str(tmp_path / f"replica-{index}" / "storage"),
            usage_journal_path=str(tmp_path / f"replica-{index}" / "usage"),
            executor_pod_queue_target_length=1,
            compile_cache_prewarm=False,
            replica_self=f"replica-{index}",
        )
        executor = CodeExecutor(
            FakeBackend(),
            Storage(config.file_storage_path),
            config,
            state_store=resilient(inner=inner, clock=clocks[index])[0],
        )
        executor._post_execute = post_execute
        recording(executor.leases)
        executors.append(executor)
    try:
        # Store up: every replica serves (its sandbox minted on the fleet
        # counter), and mints on one scope draw unique generations.
        for executor in executors:
            assert (await executor.execute("print(1)")).exit_code == 0
            executor.leases.mint("shared-scope")

        inner.down = True
        for _ in range(8):
            for executor in executors:
                assert (await executor.execute("print(1)")).exit_code == 0
        for executor in executors:
            with pytest.raises(StateStoreDegradedError):
                executor.leases.mint("shared-scope")
            # The turns were served DEGRADED: the replica's own serving path
            # found the store down, not only the mint above.
            health = executor.state_store.health()
            assert health["outages"] == 1 and health["degraded_ops"] > 1

        inner.down = False
        for executor, clock in zip(executors, clocks):
            clock.now += 6.0  # past the breaker cooldown
            executor.leases.mint("shared-scope")
            assert (await executor.execute("print(1)")).exit_code == 0
        assert len(minted) == len(set(minted))
    finally:
        for executor in executors:
            await executor.close()
