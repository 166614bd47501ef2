"""Pluggable shared control-plane state: the seam that lets N stateless
replicas cooperate behind one Service.

Every hot path below the control plane already scales (delta transfer,
compile cache, fused batch lanes, demand-adaptive pools) — the remaining
throughput ceiling is the control plane being ONE asyncio process, because
four kinds of state pin it there: scheduler WFQ tags, circuit-breaker
verdicts, lease generations/fence floors, and host/occupancy bookkeeping.
This module extracts that state behind one tiny interface with three
implementations:

- ``InMemoryStateStore`` — plain dicts under a lock. The default. With a
  private (non-shared) instance the components skip every cross-replica
  path, so a single replica with ``APP_STATE_STORE`` unset runs today's
  behavior byte-for-byte. A single instance can also be handed to several
  in-process control planes (``shared=True``) — the deterministic harness
  the replica e2e tests run on.
- ``SQLiteStateStore`` — a file-backed store (stdlib ``sqlite3``, WAL mode)
  whose writes ride ``BEGIN IMMEDIATE`` transactions: advisory locking and
  compare-and-swap across PROCESSES with zero external service
  dependencies. N replicas point ``APP_STATE_STORE`` at one path on a
  shared volume and cooperate instead of double-granting lanes or
  double-fencing hosts. SINGLE-NODE by construction: WAL coordinates
  readers/writers through a shared-memory file, which does not work
  across hosts on network filesystems — replicas sharing this store must
  share a node (k8s/replicas.yaml pins them with podAffinity); a
  multi-node control plane needs a network-store adapter behind this
  same interface.
- ``RespStateStore`` — that network-store adapter: a dependency-free
  Redis-protocol (RESP2) client over blocking stdlib sockets. The same
  ``mutate``/``incr``/CAS/TTL-lease interface maps onto ``SET NX PX``
  per-key advisory locks plus value+generation envelopes — no ``WATCH``
  transactions, no server-side Lua — so it speaks to real Redis, KeyDB,
  Dragonfly, or the in-repo stdlib stub (services/resp_stub.py the tests
  and the kill-the-store bench leg run against). Replicas on DIFFERENT
  nodes point ``APP_STATE_STORE=redis://host:port`` at one server and the
  control plane finally leaves the single-node boundary.

The interface is deliberately small — namespaced get/put/delete/items plus
two atomic primitives (``incr`` for monotonic generations, ``mutate`` for
read-modify-write like WFQ tag assignment), and TTL-lease helpers layered
on them — so a fourth impl inherits the whole contract (and the
tests/unit/test_state_store_contract.py suite) for free.

**Store loss is survivable.** A shared store is a dependency the fleet did
not have before, so ``make_state_store`` wraps every shared impl in
``ResilientStateStore``: a health breaker (the PR 1 circuit-breaker
semantics — consecutive-failure threshold, cooldown, half-open
probe-through) plus a per-namespace degraded-mode policy:

- *shadow* (scheduler WFQ tags, breaker verdicts, occupancy/host gauges,
  replica heartbeats) — fail OPEN into a replica-local in-memory shadow:
  fairness and fail-fast keep working per replica, merely losing fleet
  coherence until reconnect.
- *fenced* (lease generations/floors/fence records) — reads serve the
  last-known cached value (floors only rise, so a stale floor only
  under-refuses); WRITES FAIL CLOSED with a typed error — a partitioned
  replica minting generations off a stale counter could double-grant a
  chip a peer already granted or fenced. Existing leases keep serving.
- *journal* (fleet quota accrual) — fail OPEN: ``incr`` deltas apply to
  the shadow AND append to a replay journal; on reconnect the journal
  replays into the real store (increments are commutative, so accrual
  reconciles regardless of who reconnects first).
- *fail_closed* (durable session checkpoints) — every op raises the typed
  error: restoring a session blind against an unreadable checkpoint index
  would fork its state across replicas. Surfaces as HTTP 503 +
  Retry-After / gRPC UNAVAILABLE + ``x-store-degraded``.

Values are JSON-serializable objects. Keys and namespaces are strings.
All operations are synchronous and fast (dict ops, single-row SQLite
statements, or single-RTT RESP commands against a LAN store); they are
called from the event loop exactly like the scheduler state they replace.
"""

from __future__ import annotations

import json
import logging
import socket
import sqlite3
import threading
import time
from collections.abc import Callable

from .errors import StateStoreDegradedError

logger = logging.getLogger(__name__)


class StateStoreUnavailableError(RuntimeError):
    """The backing store service cannot be reached (connect refused/reset,
    timeout, half-written reply): a TRANSPORT failure, not a data error.
    ``ResilientStateStore`` converts a run of these into degraded mode;
    anything holding a raw store treats one as 'skip the cross-replica
    path this once'."""


# What a degraded-mode wrapper (or a component holding a raw store) treats
# as "the store is gone", as opposed to a bug: transport failures, sqlite's
# file-level errors (the RWX volume vanished, the db is locked past the
# busy timeout), and OS-level IO errors.
STORE_UNAVAILABLE_ERRORS = (
    StateStoreUnavailableError,
    sqlite3.OperationalError,
    sqlite3.DatabaseError,
    OSError,
)


class StateStore:
    """Abstract namespaced KV with atomic increment and read-modify-write.

    ``shared`` is the wiring contract: components consult the store on
    their cross-replica paths ONLY when it is True. A private in-memory
    store (the default) leaves every hot path exactly as it was before
    this interface existed.
    """

    shared: bool = False

    def get(self, ns: str, key: str):
        raise NotImplementedError

    def put(self, ns: str, key: str, value) -> None:
        raise NotImplementedError

    def delete(self, ns: str, key: str) -> None:
        raise NotImplementedError

    def items(self, ns: str) -> dict:
        raise NotImplementedError

    def incr(self, ns: str, key: str, delta: float = 1.0) -> float:
        raise NotImplementedError

    def mutate(self, ns: str, key: str, fn: Callable):
        """Atomically apply ``fn(current_value_or_None)`` which returns
        ``(new_value, result)``; the new value is stored (or the key
        deleted when new_value is None) and ``result`` returned. The
        whole read-modify-write holds the store's write lock — two
        replicas can never interleave inside it."""
        raise NotImplementedError

    # ------------------------------------------------------------- TTL leases
    # Layered on the primitives above (one sidecar namespace per ns, all
    # mutations through `mutate`) so every impl — including a fourth one —
    # inherits identical TTL semantics without schema changes. Expiry is
    # lazy (checked at read/acquire time against the injectable wall
    # clock); nothing sweeps in the background.

    @staticmethod
    def _ttl_ns(ns: str) -> str:
        return f"__ttl__:{ns}"

    def put_ttl(
        self,
        ns: str,
        key: str,
        value,
        ttl_seconds: float,
        *,
        now: float | None = None,
    ) -> None:
        """Store ``value`` readable via ``get_live`` until the TTL lapses."""
        wall = time.time() if now is None else now
        self.put(self._ttl_ns(ns), key, [wall + max(0.0, ttl_seconds), value])

    def get_live(self, ns: str, key: str, *, now: float | None = None):
        """The value if its TTL has not lapsed, else None (the lapsed
        record is dropped on the way out)."""
        wall = time.time() if now is None else now
        envelope = self.get(self._ttl_ns(ns), key)
        if not isinstance(envelope, list) or len(envelope) != 2:
            return None
        expires, value = envelope
        if not isinstance(expires, (int, float)) or wall >= expires:
            self.delete(self._ttl_ns(ns), key)
            return None
        return value

    def acquire_lease(
        self,
        ns: str,
        key: str,
        owner: str,
        ttl_seconds: float,
        *,
        now: float | None = None,
    ) -> bool:
        """Atomic TTL lease: True when ``owner`` holds the lease after the
        call — it was free, lapsed, or already theirs (re-acquire extends).
        The read-check-write rides ``mutate``, so two replicas racing an
        expired lease can never both win."""
        wall = time.time() if now is None else now
        deadline = wall + max(0.0, ttl_seconds)

        def claim(current):
            if isinstance(current, list) and len(current) == 2:
                expires, holder = current
                if (
                    isinstance(expires, (int, float))
                    and wall < expires
                    and holder != owner
                ):
                    return current, False
            return [deadline, owner], True

        return bool(self.mutate(self._ttl_ns(ns), key, claim))

    def close(self) -> None:
        pass


class InMemoryStateStore(StateStore):
    """Dict-backed store. Private by default (``shared=False``): a single
    replica's components then bypass every cross-replica code path. Pass
    ``shared=True`` when one instance is deliberately handed to several
    in-process control planes (tests, the replica bench)."""

    def __init__(self, *, shared: bool = False) -> None:
        self.shared = shared
        self._data: dict[str, dict[str, object]] = {}
        self._lock = threading.RLock()

    def _ns(self, ns: str) -> dict:
        return self._data.setdefault(ns, {})

    def get(self, ns: str, key: str):
        with self._lock:
            return self._ns(ns).get(key)

    def put(self, ns: str, key: str, value) -> None:
        with self._lock:
            self._ns(ns)[key] = value

    def delete(self, ns: str, key: str) -> None:
        with self._lock:
            self._ns(ns).pop(key, None)

    def items(self, ns: str) -> dict:
        with self._lock:
            return dict(self._ns(ns))

    def incr(self, ns: str, key: str, delta: float = 1.0) -> float:
        with self._lock:
            table = self._ns(ns)
            current = table.get(key)
            value = (float(current) if isinstance(current, (int, float)) else 0.0) + delta
            table[key] = value
            return value

    def mutate(self, ns: str, key: str, fn: Callable):
        with self._lock:
            new_value, result = fn(self._ns(ns).get(key))
            if new_value is None:
                self._ns(ns).pop(key, None)
            else:
                self._ns(ns)[key] = new_value
            return result


class SQLiteStateStore(StateStore):
    """File-backed shared store: one SQLite database on a volume every
    replica mounts. WAL mode keeps readers off the writers' lock;
    ``BEGIN IMMEDIATE`` gives ``incr``/``mutate`` cross-process atomicity
    (SQLite's own file locking is the advisory lock — no lockfile
    protocol to get wrong). Connections are per-thread (sqlite3 objects
    are not thread-safe, and replicas may be driven from worker threads).

    Busy handling: a writer that finds the database locked retries inside
    sqlite's busy timeout (5s) — under control-plane write rates (tag
    assignments, breaker transitions, occupancy gauges) contention is
    microseconds, not seconds."""

    shared = True

    def __init__(self, path: str) -> None:
        self.path = path
        self._local = threading.local()
        # Create the schema once, eagerly, so a malformed path fails at
        # boot (where the operator can see it), not mid-request.
        conn = self._conn()
        with conn:  # implicit transaction
            conn.execute(
                "CREATE TABLE IF NOT EXISTS kv ("
                "  ns TEXT NOT NULL, key TEXT NOT NULL, value TEXT NOT NULL,"
                "  PRIMARY KEY (ns, key))"
            )

    def _conn(self) -> sqlite3.Connection:
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = sqlite3.connect(self.path, timeout=5.0)
            conn.execute("PRAGMA journal_mode=WAL")
            conn.execute("PRAGMA synchronous=NORMAL")
            self._local.conn = conn
        return conn

    def get(self, ns: str, key: str):
        row = self._conn().execute(
            "SELECT value FROM kv WHERE ns=? AND key=?", (ns, key)
        ).fetchone()
        return json.loads(row[0]) if row is not None else None

    def put(self, ns: str, key: str, value) -> None:
        conn = self._conn()
        with conn:
            conn.execute(
                "INSERT INTO kv (ns, key, value) VALUES (?, ?, ?) "
                "ON CONFLICT (ns, key) DO UPDATE SET value=excluded.value",
                (ns, key, json.dumps(value)),
            )

    def delete(self, ns: str, key: str) -> None:
        conn = self._conn()
        with conn:
            conn.execute("DELETE FROM kv WHERE ns=? AND key=?", (ns, key))

    def items(self, ns: str) -> dict:
        rows = self._conn().execute(
            "SELECT key, value FROM kv WHERE ns=?", (ns,)
        ).fetchall()
        return {key: json.loads(value) for key, value in rows}

    def incr(self, ns: str, key: str, delta: float = 1.0) -> float:
        conn = self._conn()
        try:
            conn.execute("BEGIN IMMEDIATE")
            row = conn.execute(
                "SELECT value FROM kv WHERE ns=? AND key=?", (ns, key)
            ).fetchone()
            current = 0.0
            if row is not None:
                try:
                    loaded = json.loads(row[0])
                    if isinstance(loaded, (int, float)):
                        current = float(loaded)
                except ValueError:
                    pass
            value = current + delta
            conn.execute(
                "INSERT INTO kv (ns, key, value) VALUES (?, ?, ?) "
                "ON CONFLICT (ns, key) DO UPDATE SET value=excluded.value",
                (ns, key, json.dumps(value)),
            )
            conn.commit()
            return value
        except BaseException:
            conn.rollback()
            raise

    def mutate(self, ns: str, key: str, fn: Callable):
        conn = self._conn()
        try:
            conn.execute("BEGIN IMMEDIATE")
            row = conn.execute(
                "SELECT value FROM kv WHERE ns=? AND key=?", (ns, key)
            ).fetchone()
            current = json.loads(row[0]) if row is not None else None
            new_value, result = fn(current)
            if new_value is None:
                conn.execute(
                    "DELETE FROM kv WHERE ns=? AND key=?", (ns, key)
                )
            else:
                conn.execute(
                    "INSERT INTO kv (ns, key, value) VALUES (?, ?, ?) "
                    "ON CONFLICT (ns, key) DO UPDATE SET value=excluded.value",
                    (ns, key, json.dumps(new_value)),
                )
            conn.commit()
            return result
        except BaseException:
            conn.rollback()
            raise

    def close(self) -> None:
        conn = getattr(self._local, "conn", None)
        if conn is not None:
            conn.close()
            self._local.conn = None


class RespStateStore(StateStore):
    """Dependency-free Redis-protocol (RESP2) adapter: the multi-node
    shared store. Works against real Redis/KeyDB/Dragonfly or the in-repo
    stdlib stub (services/resp_stub.py).

    Layout per namespace: each value lives at ``k:{ns}:{key}`` as a JSON
    ``[generation, value]`` envelope, and a per-namespace index set
    ``i:{ns}`` names the live keys (``items`` = SMEMBERS + MGET — RESP has
    no namespaced scan that is O(namespace), and KEYS is O(database)).

    Atomicity WITHOUT WATCH/MULTI or server-side Lua: every write runs
    under a per-key advisory lock taken with ``SET l:{ns}:{key} token NX
    PX`` (single-node Redlock). The generation in the envelope is the
    belt-and-suspenders half of the CAS: a writer that lost its lock
    mid-section (TTL lapse under a stop-the-world pause) detects the
    stomp — the lock token re-check fails OR the generation moved — and
    retries the whole read-modify-write instead of writing a lost update.
    The lock TTL (default 2s) is ~4 orders of magnitude above the
    critical section (a handful of single-RTT commands), so lapses are a
    pathology bound, not a working path.

    Connections are per-thread (replicas may be driven from worker
    threads); every transport failure closes the connection and raises
    ``StateStoreUnavailableError`` — the resilience wrapper's cue."""

    shared = True

    def __init__(
        self,
        url: str,
        *,
        op_timeout: float = 2.0,
        lock_ttl_ms: int = 2000,
        lock_retry_s: float = 0.002,
    ) -> None:
        self.url = url
        rest = url.split("://", 1)[1]
        path = ""
        if "/" in rest:
            rest, path = rest.split("/", 1)
        host, _, port = rest.rpartition(":")
        if not host:
            host, port = rest, ""
        self.host = host or "127.0.0.1"
        self.port = int(port or 6379)
        self.db = int(path) if path.strip().isdigit() else 0
        self.op_timeout = max(0.1, float(op_timeout))
        self.lock_ttl_ms = max(100, int(lock_ttl_ms))
        self.lock_retry_s = max(0.0005, float(lock_retry_s))
        self._local = threading.local()
        self._conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()
        self._token_seq = 0
        self._token_lock = threading.Lock()

    # ------------------------------------------------------------- transport

    def _connect(self) -> tuple[socket.socket, object]:
        try:
            sock = socket.create_connection(
                (self.host, self.port), timeout=self.op_timeout
            )
            sock.settimeout(self.op_timeout)
            reader = sock.makefile("rb")
        except OSError as e:
            raise StateStoreUnavailableError(
                f"resp store {self.host}:{self.port} unreachable: {e}"
            ) from e
        with self._conns_lock:
            self._conns.add(sock)
        self._local.conn = (sock, reader)
        if self.db:
            self._cmd("SELECT", str(self.db))
        return sock, reader

    def _drop_conn(self) -> None:
        conn = getattr(self._local, "conn", None)
        self._local.conn = None
        if conn is not None:
            sock, reader = conn
            with self._conns_lock:
                self._conns.discard(sock)
            try:
                reader.close()
                sock.close()
            except OSError:
                pass

    @staticmethod
    def _encode(parts: tuple) -> bytes:
        out = [b"*%d\r\n" % len(parts)]
        for part in parts:
            data = part if isinstance(part, bytes) else str(part).encode()
            out.append(b"$%d\r\n%s\r\n" % (len(data), data))
        return b"".join(out)

    def _read_reply(self, reader):
        line = reader.readline()
        if not line.endswith(b"\r\n"):
            raise StateStoreUnavailableError(
                "resp store connection closed mid-reply"
            )
        kind, body = line[:1], line[1:-2]
        if kind == b"+":
            return body.decode()
        if kind == b"-":
            # A server-side refusal (wrong type, OOM, LOADING...): the
            # caller cannot make progress against this store right now —
            # same handling as a transport loss.
            raise StateStoreUnavailableError(
                f"resp server error: {body.decode(errors='replace')}"
            )
        if kind == b":":
            return int(body)
        if kind == b"$":
            length = int(body)
            if length < 0:
                return None
            data = reader.read(length + 2)
            if len(data) != length + 2:
                raise StateStoreUnavailableError(
                    "resp store connection closed mid-bulk"
                )
            return data[:-2]
        if kind == b"*":
            count = int(body)
            if count < 0:
                return None
            return [self._read_reply(reader) for _ in range(count)]
        raise StateStoreUnavailableError(
            f"unparseable resp reply kind {kind!r}"
        )

    def _cmd(self, *parts):
        conn = getattr(self._local, "conn", None)
        if conn is None:
            conn = self._connect()
        sock, reader = conn
        try:
            sock.sendall(self._encode(parts))
            return self._read_reply(reader)
        except StateStoreUnavailableError:
            self._drop_conn()
            raise
        except OSError as e:
            self._drop_conn()
            raise StateStoreUnavailableError(
                f"resp store {self.host}:{self.port} io failure: {e}"
            ) from e

    def ping(self) -> bool:
        return self._cmd("PING") == "PONG"

    # ------------------------------------------------------------ data layout

    @staticmethod
    def _dk(ns: str, key: str) -> str:
        return f"k:{ns}:{key}"

    @staticmethod
    def _ik(ns: str) -> str:
        return f"i:{ns}"

    @staticmethod
    def _lk(ns: str, key: str) -> str:
        return f"l:{ns}:{key}"

    @staticmethod
    def _decode_envelope(raw) -> tuple[int, object]:
        if raw is None:
            return 0, None
        try:
            envelope = json.loads(raw)
        except ValueError:
            return 0, None
        if isinstance(envelope, list) and len(envelope) == 2:
            generation, value = envelope
            if isinstance(generation, int):
                return generation, value
        return 0, None

    def get(self, ns: str, key: str):
        _, value = self._decode_envelope(self._cmd("GET", self._dk(ns, key)))
        return value

    def items(self, ns: str) -> dict:
        members = self._cmd("SMEMBERS", self._ik(ns)) or []
        keys = sorted(m.decode() for m in members)
        if not keys:
            return {}
        raws = self._cmd("MGET", *(self._dk(ns, k) for k in keys))
        out = {}
        for key, raw in zip(keys, raws):
            if raw is None:
                # A crashed writer's index stray: retire it lazily.
                self._cmd("SREM", self._ik(ns), key)
                continue
            _, value = self._decode_envelope(raw)
            out[key] = value
        return out

    # ------------------------------------------------------------ write path

    def _next_token(self) -> str:
        with self._token_lock:
            self._token_seq += 1
            return f"{id(self)}:{threading.get_ident()}:{self._token_seq}"

    def _locked_rmw(self, ns: str, key: str, fn: Callable):
        """The CAS core every write rides: per-key ``SET NX PX`` lock,
        read envelope, apply, verify the lock survived, write the
        generation-bumped envelope, release. A lost lock (or a moved
        generation) retries the whole section."""
        lock_key = self._lk(ns, key)
        data_key = self._dk(ns, key)
        deadline = time.monotonic() + self.op_timeout
        while True:
            token = self._next_token()
            while (
                self._cmd(
                    "SET", lock_key, token, "NX", "PX", str(self.lock_ttl_ms)
                )
                != "OK"
            ):
                if time.monotonic() >= deadline:
                    raise StateStoreUnavailableError(
                        f"lock {lock_key} contended past the "
                        f"{self.op_timeout:.1f}s op budget"
                    )
                time.sleep(self.lock_retry_s)
            try:
                generation, current = self._decode_envelope(
                    self._cmd("GET", data_key)
                )
                new_value, result = fn(current)
                holder = self._cmd("GET", lock_key)
                if holder is None or holder.decode() != token:
                    # TTL lapsed mid-section and someone else may have
                    # written: discard this attempt entirely.
                    continue
                if new_value is None:
                    self._cmd("DEL", data_key)
                    self._cmd("SREM", self._ik(ns), key)
                else:
                    self._cmd(
                        "SET",
                        data_key,
                        json.dumps([generation + 1, new_value]),
                    )
                    self._cmd("SADD", self._ik(ns), key)
                return result
            finally:
                holder = self._cmd("GET", lock_key)
                if holder is not None and holder.decode() == token:
                    self._cmd("DEL", lock_key)

    def put(self, ns: str, key: str, value) -> None:
        self._locked_rmw(ns, key, lambda _current: (value, None))

    def delete(self, ns: str, key: str) -> None:
        self._locked_rmw(ns, key, lambda _current: (None, None))

    def incr(self, ns: str, key: str, delta: float = 1.0) -> float:
        def bump(current):
            base = (
                float(current) if isinstance(current, (int, float)) else 0.0
            )
            return base + delta, base + delta

        return float(self._locked_rmw(ns, key, bump))

    def mutate(self, ns: str, key: str, fn: Callable):
        return self._locked_rmw(ns, key, fn)

    def close(self) -> None:
        self._drop_conn()
        with self._conns_lock:
            conns, self._conns = set(self._conns), set()
        for sock in conns:
            try:
                sock.close()
            except OSError:
                pass


# ---------------------------------------------------------------- resilience

# Degraded-mode policy per namespace: what each subsystem's state does
# while the shared store is unreachable. The choice is the availability/
# safety call each subsystem's invariants force — see the module
# docstring and README "Multi-replica deployment" for the rationale.
SHADOW = "shadow"
FENCED = "fenced"
JOURNAL = "journal"
FAIL_CLOSED = "fail_closed"

DEGRADED_POLICY = {
    "wfq": SHADOW,
    "breaker": SHADOW,
    "occupancy": SHADOW,
    "replicas": SHADOW,
    "hosts": SHADOW,
    "lease_gen": FENCED,
    "lease_floor": FENCED,
    "lease_fence": FENCED,
    "quota_win": JOURNAL,
    "session_durable": FAIL_CLOSED,
}

_SUBSYSTEM_BY_NS = {
    "lease_gen": "leases",
    "lease_floor": "leases",
    "lease_fence": "leases",
    "session_durable": "sessions",
}

# Replay-journal bound: quota accrual is fail-open BY POLICY, so past this
# many buffered deltas the oldest drop (counted) rather than growing
# without bound through an unbounded outage.
_JOURNAL_CAP = 100_000


class ResilientStateStore(StateStore):
    """Degraded-mode wrapper every SHARED store ships inside: the PR 1
    circuit-breaker semantics (consecutive-failure threshold, cooldown,
    half-open probe-through) guard the inner store, and while it is out
    each namespace follows its DEGRADED_POLICY — shadow (fail open,
    replica-local), fenced (stale reads, fail-closed writes), journal
    (fail open + replay on reconnect), or fail_closed (typed refusal).

    The health probe IS the traffic: with the breaker open, ops serve
    degraded without touching the store; once the cooldown elapses
    (half-open) the next op probes through, and one success heals —
    replaying the accrual journal and dropping the shadow. Heartbeats and
    occupancy gauges tick every ~2s, so an idle replica still reconnects
    within one cooldown of the store returning. ``probe()`` exists for
    paths that want to force the question (bench, tests, statusz)."""

    shared = True

    def __init__(
        self,
        inner: StateStore,
        *,
        failure_threshold: int = 3,
        cooldown: float = 5.0,
        clock: Callable[[], float] = time.monotonic,
        on_event: Callable[[str], None] | None = None,
    ) -> None:
        from .circuit_breaker import CLOSED, CircuitBreaker

        self.inner = inner
        self._closed_state = CLOSED
        self._breaker = CircuitBreaker(
            failure_threshold=failure_threshold,
            cooldown=cooldown,
            clock=clock,
            name="state_store",
        )
        self._cooldown = cooldown
        self._on_event = on_event
        self._lock = threading.RLock()
        self._shadow = InMemoryStateStore(shared=True)
        # FENCED namespaces: last-known reads, maintained write-through
        # while healthy. Floors only rise, so serving a stale floor can
        # only under-refuse — and mints fail closed, so nothing NEW is
        # granted off stale state.
        self._read_cache: dict[tuple[str, str], object] = {}
        self._items_cache: dict[str, dict] = {}
        # JOURNAL namespaces: (ns, key, delta) increments to replay.
        self._journal: list[tuple[str, str, float]] = []
        self._was_degraded = False
        self.outages = 0
        self.degraded_ops = 0
        self.journal_replays = 0
        self.journal_dropped = 0

    # ---------------------------------------------------------------- policy

    @staticmethod
    def _policy(ns: str) -> str:
        base = ns[len("__ttl__:"):] if ns.startswith("__ttl__:") else ns
        return DEGRADED_POLICY.get(base, SHADOW)

    @staticmethod
    def _subsystem(ns: str) -> str:
        base = ns[len("__ttl__:"):] if ns.startswith("__ttl__:") else ns
        return _SUBSYSTEM_BY_NS.get(base, base)

    def _refuse(self, ns: str, op: str) -> StateStoreDegradedError:
        retry_after = max(1.0, self._breaker.retry_after() or self._cooldown)
        return StateStoreDegradedError(
            f"shared state store is degraded: {op} on ns={ns!r} fails "
            f"closed (subsystem {self._subsystem(ns)}); retry in "
            f"{retry_after:.1f}s",
            subsystem=self._subsystem(ns),
            retry_after=retry_after,
        )

    # ----------------------------------------------------------- degradation

    @property
    def degraded(self) -> bool:
        return self._was_degraded or (
            self._breaker.state != self._closed_state
        )

    def _emit(self, event: str) -> None:
        if self._on_event is not None:
            try:
                self._on_event(event)
            except Exception:  # noqa: BLE001 — metrics must not fail state ops
                pass

    def _on_failure(self, error: Exception) -> None:
        with self._lock:
            first = not self._was_degraded
            self._was_degraded = True
            self._breaker.record_failure()
        if first:
            self.outages += 1
            self._emit("outage")
            logger.warning(
                "shared state store unreachable (%s): entering degraded "
                "mode — shadow/journal for fail-open namespaces, typed "
                "refusals for fail-closed ones",
                error,
            )

    def _on_success(self) -> None:
        if not self._was_degraded:
            self._breaker.record_success()
            return
        with self._lock:
            journal, self._journal = self._journal, []
            self._was_degraded = False
            self._breaker.record_success()
            # Drop the shadow wholesale: fail-open state written during
            # the outage was replica-local by definition; the store's own
            # copy (peers kept writing it) is the fleet truth again.
            self._shadow = InMemoryStateStore(shared=True)
        replayed = 0
        try:
            for ns, key, delta in journal:
                self.inner.incr(ns, key, delta)
                replayed += 1
        except STORE_UNAVAILABLE_ERRORS as e:
            # Mid-replay relapse: requeue what has not landed (increments
            # are commutative — replay order never matters).
            with self._lock:
                self._journal = list(journal[replayed:]) + self._journal
            self._on_failure(e)
            return
        self.journal_replays += 1
        self._emit("replay")
        logger.info(
            "shared state store reconnected: replayed %d journaled "
            "accrual increment(s), dropped the degraded shadow",
            replayed,
        )

    def _degraded(self, ns: str) -> None:
        self.degraded_ops += 1
        self._emit("degraded_op")

    # -------------------------------------------------------------- core ops

    def _run(self, ns: str, op: str, inner_fn: Callable, degraded_fn: Callable):
        if not self._breaker.allow():
            self._degraded(ns)
            return degraded_fn()
        try:
            result = inner_fn()
        except STORE_UNAVAILABLE_ERRORS as e:
            self._on_failure(e)
            self._degraded(ns)
            return degraded_fn()
        self._on_success()
        return result

    def get(self, ns: str, key: str):
        policy = self._policy(ns)

        def degraded():
            if policy == FAIL_CLOSED:
                raise self._refuse(ns, "get")
            if policy == FENCED:
                return self._read_cache.get((ns, key))
            return self._shadow.get(ns, key)

        value = self._run(ns, "get", lambda: self.inner.get(ns, key), degraded)
        if policy == FENCED and not self.degraded:
            self._read_cache[(ns, key)] = value
        return value

    def items(self, ns: str) -> dict:
        policy = self._policy(ns)

        def degraded():
            if policy == FAIL_CLOSED:
                raise self._refuse(ns, "items")
            if policy == FENCED:
                return dict(self._items_cache.get(ns, {}))
            return self._shadow.items(ns)

        value = self._run(ns, "items", lambda: self.inner.items(ns), degraded)
        if policy == FENCED and not self.degraded:
            self._items_cache[ns] = dict(value)
        return value

    def put(self, ns: str, key: str, value) -> None:
        policy = self._policy(ns)

        def degraded():
            if policy in (FENCED, FAIL_CLOSED):
                raise self._refuse(ns, "put")
            self._shadow.put(ns, key, value)

        result = self._run(
            ns, "put", lambda: self.inner.put(ns, key, value), degraded
        )
        if policy == FENCED and not self.degraded:
            self._read_cache[(ns, key)] = value
        return result

    def delete(self, ns: str, key: str) -> None:
        policy = self._policy(ns)

        def degraded():
            if policy in (FENCED, FAIL_CLOSED):
                raise self._refuse(ns, "delete")
            self._shadow.delete(ns, key)

        return self._run(
            ns, "delete", lambda: self.inner.delete(ns, key), degraded
        )

    def incr(self, ns: str, key: str, delta: float = 1.0) -> float:
        policy = self._policy(ns)

        def degraded():
            if policy in (FENCED, FAIL_CLOSED):
                raise self._refuse(ns, "incr")
            value = self._shadow.incr(ns, key, delta)
            if policy == JOURNAL:
                with self._lock:
                    self._journal.append((ns, key, float(delta)))
                    if len(self._journal) > _JOURNAL_CAP:
                        self._journal.pop(0)
                        self.journal_dropped += 1
            return value

        return self._run(
            ns, "incr", lambda: self.inner.incr(ns, key, delta), degraded
        )

    def mutate(self, ns: str, key: str, fn: Callable):
        policy = self._policy(ns)

        def degraded():
            if policy in (FENCED, FAIL_CLOSED):
                raise self._refuse(ns, "mutate")
            # Shadow mutations are replica-local RMW: correct within this
            # process, reconciled by dropping the shadow on reconnect.
            return self._shadow.mutate(ns, key, fn)

        return self._run(
            ns, "mutate", lambda: self.inner.mutate(ns, key, fn), degraded
        )

    # -------------------------------------------------------------- surfaces

    def probe(self) -> bool:
        """Force the health question now (bench/tests/operator paths):
        one cheap read against the inner store, success heals (journal
        replay and all), failure counts a breaker strike."""
        if not self._breaker.allow():
            return False
        try:
            self.inner.get("__health__", "probe")
        except STORE_UNAVAILABLE_ERRORS as e:
            self._on_failure(e)
            return False
        self._on_success()
        return True

    def health(self) -> dict:
        """Operator view (joined into GET /statusz's store block)."""
        return {
            "inner": type(self.inner).__name__,
            "state": self._breaker.state,
            "degraded": self.degraded,
            "outages": self.outages,
            "degraded_ops": self.degraded_ops,
            "journal_depth": len(self._journal),
            "journal_replays": self.journal_replays,
            "journal_dropped": self.journal_dropped,
            "retry_after_s": round(self._breaker.retry_after(), 3),
        }

    def close(self) -> None:
        self.inner.close()
        self._shadow.close()


def resolve_replica_id(config) -> str:
    """This process's replica identity for multi-writer sharding and the
    affinity ring: ``APP_REPLICA_SELF``, else POD_NAME (k8s downward API),
    else the hostname — but ONLY when the deployment is actually
    replicated (a replica peer set or a shared store is configured).
    Single-replica deployments return "" and keep every legacy file name
    byte-for-byte."""
    replicated = bool(getattr(config, "replica_peers", "")) or (
        (getattr(config, "state_store", "") or "").strip() not in ("", "memory")
    )
    if not replicated:
        return ""
    explicit = getattr(config, "replica_self", "") or ""
    if explicit:
        return explicit
    import os
    import socket

    return os.environ.get("POD_NAME") or socket.gethostname()


def make_state_store(config) -> StateStore:
    """Build the configured store. ``APP_STATE_STORE`` grammar:

    - empty / ``"memory"`` — a PRIVATE InMemoryStateStore: single-replica
      mode, every cross-replica path skipped (today's behavior).
    - ``"sqlite:///path/to/state.db"`` (or a bare filesystem path) — the
      shared SQLite store; point every replica at the same file.
    - ``"redis://host:port[/db]"`` — the RESP store; point every replica
      at the same server (Redis-compatible, or services/resp_stub.py).

    Shared stores ship wrapped in ResilientStateStore (degraded-mode
    serving) unless ``state_store_resilient`` is off, and in the seeded
    fault injector when ``state_store_fault_spec`` is set. The private
    in-memory default is returned BARE — zero new layers, zero network
    calls, byte-for-byte the single-replica wire path.
    """
    spec = (getattr(config, "state_store", "") or "").strip()
    if spec in ("", "memory"):
        return InMemoryStateStore()
    if spec.startswith("redis://"):
        store: StateStore = RespStateStore(
            spec,
            op_timeout=float(getattr(config, "state_store_timeout", 2.0)),
        )
    else:
        path = spec
        if path.startswith("sqlite://"):
            path = path[len("sqlite://"):]
            # sqlite:///abs/path leaves /abs/path; sqlite://rel leaves rel.
        try:
            store = SQLiteStateStore(path)
        except sqlite3.Error as e:
            raise ValueError(
                f"APP_STATE_STORE={spec!r} is not a usable sqlite path: {e}"
            ) from e
    fault_spec = (
        getattr(config, "state_store_fault_spec", "") or ""
    ).strip()
    if fault_spec:
        # Imported lazily: faults.py imports this module at top level.
        from .backends.faults import (
            FaultInjectingStateStore,
            StoreFaultSpec,
        )

        store = FaultInjectingStateStore(
            store, StoreFaultSpec.parse(fault_spec)
        )
    if getattr(config, "state_store_resilient", True):
        store = ResilientStateStore(
            store,
            failure_threshold=int(
                getattr(config, "state_store_failure_threshold", 3)
            ),
            cooldown=float(
                getattr(config, "state_store_probe_cooldown", 5.0)
            ),
        )
    return store
