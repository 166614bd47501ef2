"""Performance anomaly plane: latency baselines, drift verdicts, profiles.

The third observability plane, alongside device-health (PR 8) and usage
metering (PR 9). Those answer "is the hardware alive?" and "who consumed
what?"; nothing answered "did the service get SLOWER than it used to be?"
— a 3x latency regression on one lane's hot kernel was invisible until a
human read histograms. This module turns the existing per-request phase
timings into standing verdicts:

- **Streaming latency baselines** — per-(lane, phase) and per-tenant
  p50/p95/p99 via bounded streaming quantile sketches (dep-free,
  fake-clock injectable). Each series keeps a cumulative sketch (the
  /perf quantile read) and a per-window sketch that rolls every
  ``APP_PERF_WINDOW_SECONDS``.
- **EWMA-banded drift detection** — each closed window's drift quantile
  is compared against an EWMA baseline learned from NORMAL windows only
  (a regression must not poison the baseline it is measured against) and
  classified ``normal | degraded | regressed``. Transitions touching
  ``regressed`` emit a head-sampling-proof ``perf.regression``
  record_span (the device-health transition discipline) and fire
  ``perf_regression_total{lane,phase}``.
- **Auto-triggered profiling** — a regressed (lane, phase) verdict, or a
  single request landing past the cumulative p99 band, ARMS the JAX
  profiler for the next matching request whose tenant has not opted out
  (``APP_PERF_PROFILE_TENANT_OPT_OUT``). The executor harvests the
  resulting profile.zip into the bounded content-addressed
  :class:`ProfileStore` (LRU by last access, byte/entry caps, persisted
  index — the compile-cache store discipline), retrievable via
  ``GET /profiles`` with trace-id cross-links. Control-plane-induced
  captures bill ZERO transfer bytes (the PR 9 trusted-run rule).

Cardinality discipline: lane×phase series are naturally bounded (lanes ×
the four latency phases) and additionally capped by
``APP_PERF_MAX_SERIES``; tenant series cap at ``APP_PERF_MAX_TENANTS``
with an ``_overflow`` row — the scheduler/ledger/device-health rule.

Kill switch: ``APP_PERF_OBSERVER_ENABLED=0`` constructs a disabled
observer — ``record``/``take_profile_arm`` no-op, no perf keys enter
Result.phases, the wire payload never asks sandboxes for device-memory
samples, ``/perf`` and ``/profiles`` answer 404, and no perf metric
family registers — today's behavior byte-for-byte.
"""

from __future__ import annotations

import fcntl
import hashlib
import json
import logging
import math
import os
import time
import uuid
from dataclasses import dataclass, field

from ..utils import tracing

logger = logging.getLogger(__name__)

NORMAL = "normal"
DEGRADED = "degraded"
REGRESSED = "regressed"
PERF_STATES = (NORMAL, DEGRADED, REGRESSED)

OVERFLOW_TENANT = "_overflow"

# The latency phases worth baselining: the Result.phases allowlist keys
# (services/code_executor.py LATENCY_PHASES). Anything else in phases is a
# byte count or coordinate, not a latency.
OBSERVED_PHASES = ("queue_wait", "upload", "exec", "download")


class StreamingQuantile:
    """Bounded streaming quantile sketch over geometric log-buckets.

    Values land in buckets at geometric boundaries
    ``min_value * growth**i``; a quantile read walks the cumulative counts
    and answers the bucket's geometric midpoint. Memory is a fixed array
    of ``max_buckets`` ints per sketch — no sample retention, no heap
    growth with traffic — and the relative error is bounded by the bucket
    growth factor (~4% at the default 1.08). Deterministic: the same value
    stream always produces the same quantiles, which is what makes the
    drift detector's verdicts replayable in tests and chaos legs.
    """

    __slots__ = ("min_value", "_log_growth", "max_buckets", "counts",
                 "count", "sum", "max_value", "_underflow")

    def __init__(
        self,
        min_value: float = 1e-4,
        growth: float = 1.08,
        max_buckets: int = 256,
    ) -> None:
        self.min_value = max(1e-9, float(min_value))
        self._log_growth = math.log(max(1.000001, float(growth)))
        self.max_buckets = max(8, int(max_buckets))
        self.counts: dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.max_value = 0.0
        self._underflow = 0  # values at/below min_value

    def add(self, value: float) -> None:
        if not isinstance(value, (int, float)) or value != value or value < 0:
            return
        value = float(value)
        self.count += 1
        self.sum += value
        if value > self.max_value:
            self.max_value = value
        if value <= self.min_value:
            self._underflow += 1
            return
        index = min(
            self.max_buckets - 1,
            int(math.log(value / self.min_value) / self._log_growth) + 1,
        )
        self.counts[index] = self.counts.get(index, 0) + 1

    def quantile(self, q: float) -> float:
        """The q-quantile estimate (0 <= q <= 1), 0.0 on an empty sketch."""
        if self.count <= 0:
            return 0.0
        rank = max(1, math.ceil(min(1.0, max(0.0, q)) * self.count))
        if rank <= self._underflow:
            return self.min_value
        seen = self._underflow
        for index in sorted(self.counts):
            seen += self.counts[index]
            if seen >= rank:
                if index >= self.max_buckets - 1:
                    # Overflow bucket: the observed max is the honest answer.
                    return self.max_value
                lower = self.min_value * math.exp((index - 1) * self._log_growth)
                upper = self.min_value * math.exp(index * self._log_growth)
                return (lower + upper) / 2.0
        return self.max_value

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0


@dataclass
class SeriesState:
    """One latency series (a (lane, phase) pair, or a tenant): cumulative
    quantiles for the /perf read, the rolling window sketch the drift
    detector classifies, and the EWMA baseline it classifies against."""

    key: str
    cumulative: StreamingQuantile = field(default_factory=StreamingQuantile)
    window: StreamingQuantile = field(default_factory=StreamingQuantile)
    window_start: float = 0.0
    windows: int = 0
    baseline: float | None = None  # EWMA of normal windows' drift quantile
    state: str = NORMAL
    state_since: float = 0.0
    last_window_value: float = 0.0
    regressions: int = 0

    def snapshot(self, quantiles: tuple[float, ...] = (0.5, 0.95, 0.99)) -> dict:
        row: dict = {
            "state": self.state,
            "count": self.cumulative.count,
            "windows": self.windows,
            "baseline_s": round(self.baseline, 6) if self.baseline else None,
            "last_window_s": round(self.last_window_value, 6),
            "regressions": self.regressions,
        }
        for q in quantiles:
            row[f"p{int(q * 100)}_s"] = round(self.cumulative.quantile(q), 6)
        return row


class ProfileStore:
    """Bounded content-addressed store for harvested profile artifacts.

    The compile-cache store discipline: bytes are content-addressed
    (SHA-256 of the zip; identical captures dedup to one object), entries
    evict LRU-by-last-access under byte AND entry caps, and a JSON index
    persists across restarts so ``GET /profiles`` survives a control-plane
    bounce. All IO is small and synchronous (profiles are a few hundred KB
    and arrive at regression cadence, not request cadence).
    """

    def __init__(
        self,
        directory: str,
        *,
        max_bytes: int = 256 << 20,
        max_entries: int = 256,
        walltime=time.time,
    ) -> None:
        self.dir = directory
        self.max_bytes = max(1 << 20, int(max_bytes))
        self.max_entries = max(1, int(max_entries))
        self.walltime = walltime
        # id -> meta dict; insertion order irrelevant (LRU via last_access).
        self._entries: dict[str, dict] = {}
        self.evictions = 0
        os.makedirs(self.dir, exist_ok=True)
        self._load_index()

    # ----------------------------------------------------------- persistence

    @property
    def index_path(self) -> str:
        return os.path.join(self.dir, "index.json")

    def _object_path(self, profile_id: str) -> str:
        return os.path.join(self.dir, f"{profile_id}.zip")

    def _load_index(self) -> None:
        try:
            with open(self.index_path, encoding="utf-8") as f:
                body = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            return
        entries = body.get("entries")
        if not isinstance(entries, dict):
            return
        for profile_id, meta in entries.items():
            if not isinstance(meta, dict):
                continue
            # An index row whose bytes are gone is a stale pointer, not an
            # artifact — drop it rather than 500 the later GET.
            if os.path.exists(self._object_path(str(profile_id))):
                self._entries[str(profile_id)] = meta

    def _persist_index(self) -> None:
        # Multi-writer safety (two control-plane replicas sharing one
        # store volume): a whole-file rewrite would last-writer-wins a
        # concurrent peer's entries out of the index, stranding its zips
        # as unlisted orphans. Merge the on-disk index first — rows we
        # don't know, whose bytes exist, are a peer's live captures and
        # are adopted (both into the write and into this process's view,
        # so GET /profiles on any replica lists the fleet's captures).
        # The object files themselves are content-addressed tmp+rename
        # writes, so concurrent writers can never tear them.
        #
        # The merge read and the rename must be ONE critical section: a
        # peer persisting between them would have its newest entry merged
        # by nobody and clobbered by our rename (a lost update the merge
        # alone cannot prevent). flock serializes writers — correct on the
        # documented single-node store posture (the same bound as the
        # SQLite StateStore; flock does not span NFS reliably) and a
        # best-effort no-op where the FS refuses it.
        lock = None
        try:
            lock = open(os.path.join(self.dir, "index.lock"), "a")
            fcntl.flock(lock.fileno(), fcntl.LOCK_EX)
        except OSError:
            if lock is not None:
                lock.close()
            lock = None
        try:
            try:
                with open(self.index_path, encoding="utf-8") as f:
                    disk = json.load(f).get("entries")
                if isinstance(disk, dict):
                    for profile_id, meta in disk.items():
                        if (
                            str(profile_id) not in self._entries
                            and isinstance(meta, dict)
                            and os.path.exists(
                                self._object_path(str(profile_id))
                            )
                        ):
                            self._entries[str(profile_id)] = meta
            except (FileNotFoundError, json.JSONDecodeError, OSError):
                pass
            # UNIQUE tmp name per write: two processes sharing one tmp path
            # could truncate each other mid-write and rename a torn file
            # into place. A PID suffix is NOT unique across pods
            # (containerized replicas on a shared volume are typically all
            # PID 1) — use a random token.
            tmp = f"{self.index_path}.{uuid.uuid4().hex[:12]}.tmp"
            try:
                with open(tmp, "w", encoding="utf-8") as f:
                    json.dump({"version": 1, "entries": self._entries}, f,
                              sort_keys=True)
                os.replace(tmp, self.index_path)
            except OSError:
                logger.warning(
                    "profile store index persist failed", exc_info=True
                )
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        finally:
            if lock is not None:
                lock.close()

    # ------------------------------------------------------------------- api

    def add(self, data: bytes, meta: dict) -> str | None:
        """Store one artifact; returns its content-addressed id, or None
        when the bytes could not be made durable (full/unwritable volume)
        — the caller must NOT treat the artifact as captured then. A
        repeat capture with identical bytes refreshes the existing
        entry's recency and meta instead of duplicating the object."""
        profile_id = hashlib.sha256(data).hexdigest()[:32]
        now = self.walltime()
        entry = self._entries.get(profile_id)
        if entry is None:
            try:
                tmp = self._object_path(profile_id) + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, self._object_path(profile_id))
            except OSError:
                logger.warning("profile store write failed", exc_info=True)
                return None
            entry = {"size_bytes": len(data), "captured_at": round(now, 3)}
            self._entries[profile_id] = entry
        entry.update({
            k: v for k, v in meta.items()
            if isinstance(k, str) and v is not None
        })
        entry["last_access"] = round(now, 3)
        self._evict()
        self._persist_index()
        return profile_id

    def get(self, profile_id: str) -> tuple[bytes, dict] | None:
        entry = self._entries.get(profile_id)
        if entry is None:
            return None
        try:
            with open(self._object_path(profile_id), "rb") as f:
                data = f.read()
        except OSError:
            # Bytes vanished under the index (operator rm): self-heal.
            self._entries.pop(profile_id, None)
            self._persist_index()
            return None
        entry["last_access"] = round(self.walltime(), 3)
        self._persist_index()
        return data, entry

    def list(self) -> list[dict]:
        """Every entry's meta (id included), newest capture first."""
        rows = [
            {"id": profile_id, **meta}
            for profile_id, meta in self._entries.items()
        ]
        rows.sort(key=lambda row: row.get("captured_at", 0.0), reverse=True)
        return rows

    def total_bytes(self) -> int:
        return sum(int(m.get("size_bytes", 0)) for m in self._entries.values())

    def entry_count(self) -> int:
        return len(self._entries)

    def _evict(self) -> None:
        while self._entries and (
            len(self._entries) > self.max_entries
            or self.total_bytes() > self.max_bytes
        ):
            victim = min(
                self._entries,
                key=lambda pid: self._entries[pid].get("last_access", 0.0),
            )
            self._entries.pop(victim, None)
            self.evictions += 1
            try:
                os.unlink(self._object_path(victim))
            except OSError:
                pass


def summarize_profile(data: bytes, *, top_n: int = 10) -> dict:
    """An xprof VERDICT instead of a raw zip: parse the JAX profiler
    artifact's trace-event JSON (``*.trace.json[.gz]`` members — the
    TensorBoard/Perfetto feed) and report what an operator actually asks a
    profile: which ops dominated, what share of the wall the device was
    busy, and where the big idle gaps sat. Stdlib-only (zipfile/gzip/json)
    — no xprof/TensorBoard dependency; artifacts without a parseable trace
    (or on an old jaxlib layout) degrade to a member listing, never a 500.

    A capture that holds no device process (a CPU run; a turn that ran no
    device program) says ``device_plane: false`` and gives no busy share, no
    device ops and no idle gaps: host events are not the device's.

    The capture shares its clock with the host: the warm runner's stages
    (``runner.*``, every profiled turn's capture holds them) and the numpy
    shim's (``shim.load``, ``shim.h2d``, ``shim.materialize``, ``shim.wait``,
    ``shim.d2h``) are annotations of its host plane. ONE rule says what the
    host was doing while the device sat idle: every idle microsecond, from
    the capture's first event to its last, belongs to the innermost such
    annotation that covers it, and an idle stretch that crosses an
    annotation's boundary is split there. ``idle_by`` sums the idle time by
    annotation (``none`` where none covers it) and, with ``device_busy_ms``,
    adds up to ``span_ms``; ``idle_gaps`` lists the five longest stretches,
    each ``during`` its annotation; the stages' own lengths are listed
    (``runner_stages``; ``shim_stages``: count and total of each).

    Durations in the trace-event format are microseconds; everything here
    reports milliseconds."""
    import gzip
    import io
    import zipfile

    try:
        archive = zipfile.ZipFile(io.BytesIO(data))
        members = archive.namelist()
    except Exception:  # noqa: BLE001 — corrupt artifact, not a server error
        return {"verdict": "unparseable", "detail": "not a zip archive"}
    events: list[dict] = []
    parsed_member = None
    for name in members:
        if not name.endswith((".trace.json", ".trace.json.gz")):
            continue
        try:
            raw = archive.read(name)
            if name.endswith(".gz"):
                raw = gzip.decompress(raw)
            trace = json.loads(raw)
        except Exception:  # noqa: BLE001
            continue
        found = trace.get("traceEvents")
        if isinstance(found, list):
            events = [e for e in found if isinstance(e, dict)]
            parsed_member = name
            break
    if not events:
        return {
            "verdict": "unparseable",
            "detail": "no trace-event JSON member found",
            "members": members[:50],
        }
    # pid -> process name from the metadata events; device pids are the
    # ones the profiler labels with a device/TPU/GPU identity.
    process_names: dict = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            args = e.get("args")
            if isinstance(args, dict):
                process_names[e.get("pid")] = str(args.get("name", ""))
    device_pids = {
        pid
        for pid, name in process_names.items()
        if any(tag in name.lower() for tag in ("device", "tpu", "gpu", "xla"))
    }
    ops: dict[str, list[float]] = {}
    device_spans: list[tuple[float, float]] = []
    # The warm runner's and the shim's stage annotations (host plane):
    # (start, end, name).
    stages: list[tuple[float, float, str]] = []
    t_min = math.inf
    t_max = -math.inf
    for e in events:
        if e.get("ph") != "X":
            continue
        ts = e.get("ts")
        dur = e.get("dur")
        if not isinstance(ts, (int, float)) or not isinstance(
            dur, (int, float)
        ):
            continue
        t_min = min(t_min, float(ts))
        t_max = max(t_max, float(ts) + float(dur))
        name = str(e.get("name", "?"))
        if e.get("pid") in device_pids:
            device_spans.append((float(ts), float(ts) + float(dur)))
            bucket = ops.setdefault(name, [0.0, 0.0])
            bucket[0] += float(dur)
            bucket[1] += 1.0
        elif name.startswith(("runner.", "shim.")):
            stages.append((float(ts), float(ts) + float(dur), name))
    if not math.isfinite(t_min):
        return {
            "verdict": "no complete events in trace",
            "member": parsed_member,
            "members": members[:50],
        }
    stages.sort()
    runner_stages = [
        {
            "name": name,
            "offset_ms": round((start - t_min) / 1e3, 3),
            "duration_ms": round((end - start) / 1e3, 3),
        }
        for start, end, name in stages
        if name.startswith("runner.")
    ]
    shim_totals: dict[str, list[float]] = {}  # name -> [count, microseconds]
    for start, end, name in stages:
        if name.startswith("shim."):
            total = shim_totals.setdefault(name, [0, 0.0])
            total[0] += 1
            total[1] += end - start
    shim_stages = {
        name: {"count": count, "total_ms": round(total / 1e3, 3)}
        for name, (count, total) in shim_totals.items()
    }
    if not device_spans:
        summary = {
            "verdict": (
                "no device plane in the "
                f"{max(t_max - t_min, 0.0) / 1e3:.1f}ms capture: nothing ran "
                "on a device, or the capture is a CPU's"
            ),
            "member": parsed_member,
            "device_plane": False,
            "span_ms": round(max(t_max - t_min, 0.0) / 1e3, 3),
        }
        if runner_stages:
            summary["runner_stages"] = runner_stages
        if shim_stages:
            summary["shim_stages"] = shim_stages
        return summary

    def split(start: float, end: float) -> list[tuple[float, float, str | None]]:
        """The idle stretch [start, end) as (start, length, annotation): cut
        wherever an annotation begins or ends inside it, each piece named by
        the innermost annotation that covers it (the one that began last)."""
        inside = [s for s in stages if s[0] < end and s[1] > start]
        cuts = sorted(
            {start, end}
            | {min(max(edge, start), end) for s in inside for edge in s[:2]}
        )
        pieces: list[tuple[float, float, str | None]] = []
        for begin, until in zip(cuts, cuts[1:]):
            middle = (begin + until) / 2
            covering = [s for s in inside if s[0] <= middle < s[1]]
            name = max(covering, key=lambda s: (s[0], -s[1]))[2] if covering else None
            if pieces and pieces[-1][2] == name:
                pieces[-1] = (pieces[-1][0], pieces[-1][1] + until - begin, name)
            else:
                pieces.append((begin, until - begin, name))
        return pieces

    # Busy wall = the union of device spans (ops overlap across cores);
    # idle time is the capture's window less that union: before the first
    # device op, the holes between ops, after the last.
    device_spans.sort()
    busy_us = 0.0
    gaps: list[tuple[float, float, str | None]] = []
    cur_start, cur_end = device_spans[0]
    if cur_start > t_min:
        gaps.extend(split(t_min, cur_start))
    for start, end in device_spans[1:]:
        if start <= cur_end:
            cur_end = max(cur_end, end)
            continue
        busy_us += cur_end - cur_start
        gaps.extend(split(cur_end, start))
        cur_start, cur_end = start, end
    busy_us += cur_end - cur_start
    if t_max > cur_end:
        gaps.extend(split(cur_end, t_max))
    idle_by: dict[str, float] = {}
    for _start, length, name in gaps:
        idle_by[name or "none"] = idle_by.get(name or "none", 0.0) + length
    span_us = max(t_max - t_min, 1e-9)
    total_op_us = sum(total for total, _count in ops.values()) or 1e-9
    gaps.sort(key=lambda g: g[1], reverse=True)
    top_ops = sorted(
        ops.items(), key=lambda item: item[1][0], reverse=True
    )[:top_n]
    busy_share = busy_us / span_us
    verdict = (
        f"device busy {busy_share:.0%} of the {span_us / 1e3:.1f}ms capture"
        + (
            f"; largest idle gap {gaps[0][1] / 1e3:.1f}ms"
            if gaps
            else "; no idle gaps"
        )
        + (f"; top op: {top_ops[0][0]}" if top_ops else "")
    )
    idle_gaps = []
    for start, length, during in gaps[:5]:
        gap = {
            "offset_ms": round((start - t_min) / 1e3, 3),
            "duration_ms": round(length / 1e3, 3),
        }
        if during is not None:
            gap["during"] = during
        idle_gaps.append(gap)
    summary = {
        "verdict": verdict,
        "member": parsed_member,
        "device_plane": True,
        "span_ms": round(span_us / 1e3, 3),
        "device_busy_ms": round(busy_us / 1e3, 3),
        "device_op_wall_share": round(busy_share, 4),
        "top_ops": [
            {
                "name": name,
                "total_ms": round(total / 1e3, 3),
                "count": int(count),
                "share": round(total / total_op_us, 4),
            }
            for name, (total, count) in top_ops
        ],
        "idle_gaps": idle_gaps,
        "idle_by": {
            name: round(total / 1e3, 3)
            for name, total in sorted(idle_by.items(), key=lambda kv: -kv[1])
        },
    }
    if runner_stages:
        summary["runner_stages"] = runner_stages
    if shim_stages:
        summary["shim_stages"] = shim_stages
    return summary


@dataclass
class ProfileArm:
    """One armed auto-profile: the next eligible request on `lane` runs
    with the JAX profiler on. Consumed exactly once."""

    lane: int
    reason: str
    armed_at: float
    source_key: str = ""


class PerfObserver:
    """Streaming latency baselines + drift verdicts + profiling triggers.

    All state mutation happens on the control plane's event loop (the
    scheduler/ledger discipline); windows roll LAZILY on record() — no
    daemon task, and an idle series simply keeps its last verdict (no
    data is not a regression). `clock` is injectable for fake-clock tests;
    `walltime` stamps spans and store entries.
    """

    def __init__(
        self,
        config=None,
        *,
        metrics=None,
        tracer=None,
        clock=time.monotonic,
        walltime=time.time,
    ) -> None:
        from ..config import Config

        self.config = config or Config()
        self.metrics = metrics
        self.tracer = tracer
        self.clock = clock
        self.walltime = walltime
        self.enabled = bool(self.config.perf_observer_enabled)
        self.window_s = max(0.05, self.config.perf_window_seconds)
        self.min_samples = max(1, self.config.perf_min_window_samples)
        self.alpha = min(1.0, max(0.01, self.config.perf_baseline_alpha))
        self.degraded_factor = max(1.0, self.config.perf_degraded_factor)
        self.regressed_factor = max(
            self.degraded_factor, self.config.perf_regressed_factor
        )
        self.drift_quantile = min(
            0.999, max(0.5, self.config.perf_drift_quantile)
        )
        # Absolute slack under every band: sub-millisecond phases jitter by
        # whole multiples without meaning anything — a "3x regression" on a
        # 0.2ms upload is scheduler noise, not an incident.
        self.min_band_s = max(0.0, self.config.perf_min_band_seconds)
        self.max_series = max(8, self.config.perf_max_series)
        self.max_tenants = max(1, self.config.perf_max_tenants)
        self.auto_profile = bool(self.config.perf_profile_auto)
        self.p99_factor = max(1.0, self.config.perf_p99_outlier_factor)
        self.profile_interval = max(
            0.0, self.config.perf_profile_min_interval_seconds
        )
        self._opt_out = {
            str(t) for t in (self.config.perf_profile_tenant_opt_out or ())
        }
        self._series: dict[tuple[int, str], SeriesState] = {}
        self._tenants: dict[str, SeriesState] = {}
        # lane -> pending arm (one per lane: a second trigger before the
        # first consumes just refreshes the reason).
        self._arms: dict[int, ProfileArm] = {}
        # lane -> last profile consumption (throttle: a standing regression
        # must not profile every request on the lane).
        self._last_profiled: dict[int, float] = {}
        self.profiles_captured = 0
        self.started_at = walltime()
        self.store: ProfileStore | None = None
        if not self.enabled:
            return
        base = self.config.perf_profile_store_path or os.path.join(
            self.config.file_storage_path, ".profiles"
        )
        self.store = ProfileStore(
            base,
            max_bytes=self.config.perf_profile_store_max_bytes,
            max_entries=self.config.perf_profile_store_max_entries,
            walltime=walltime,
        )

    # --------------------------------------------------------------- recording

    def record_request(
        self, lane: int, phases: dict, tenant: str | None = None
    ) -> None:
        """Fold one finished request's phase latencies into the baselines
        (the executor calls this once per LOGICAL request, serial and
        batched alike). Tenant series track end-to-end request latency
        (the phase sum) — the per-tenant SLO read."""
        if not self.enabled or not isinstance(phases, dict):
            return
        total = 0.0
        for phase in OBSERVED_PHASES:
            value = phases.get(phase)
            if isinstance(value, (int, float)) and value >= 0:
                total += float(value)
                self.record(lane, phase, float(value))
        if tenant is not None and total > 0:
            self._record_tenant(tenant, total)

    def record(self, lane: int, phase: str, seconds: float) -> None:
        """One latency sample for a (lane, phase) series: roll the window
        if due, classify, feed the sketches, and check the p99 band."""
        if not self.enabled:
            return
        key = (int(lane), str(phase))
        series = self._series.get(key)
        if series is None:
            if len(self._series) >= self.max_series:
                return  # bounded: past the cap new series are not tracked
            series = SeriesState(key=f"lane-{lane}/{phase}")
            series.window_start = self.clock()
            series.state_since = self.clock()
            self._series[key] = series
        self._roll_if_due(series, lane=lane, phase=phase)
        # The p99 outlier trigger reads the CUMULATIVE sketch BEFORE this
        # sample lands (a sample must not raise the very band it is
        # measured against).
        if (
            self.auto_profile
            and series.cumulative.count >= self.min_samples
        ):
            band = series.cumulative.quantile(0.99) * self.p99_factor
            if band > self.min_band_s and seconds > band:
                self.arm_profile(
                    lane,
                    reason=f"p99_outlier:{phase}",
                    source_key=series.key,
                )
        series.cumulative.add(seconds)
        series.window.add(seconds)

    def _record_tenant(self, tenant: str, seconds: float) -> None:
        label = tenant
        if label not in self._tenants and len(self._tenants) >= self.max_tenants:
            label = OVERFLOW_TENANT
        series = self._tenants.get(label)
        if series is None:
            series = SeriesState(key=f"tenant/{label}")
            series.window_start = self.clock()
            series.state_since = self.clock()
            self._tenants[label] = series
        self._roll_if_due(series)
        series.cumulative.add(seconds)
        series.window.add(seconds)

    # ----------------------------------------------------------- drift windows

    def _roll_if_due(
        self, series: SeriesState, *, lane: int | None = None,
        phase: str | None = None,
    ) -> None:
        now = self.clock()
        if now - series.window_start < self.window_s:
            return
        window = series.window
        series.window = StreamingQuantile()
        series.window_start = now
        if window.count < self.min_samples:
            # Too thin to judge — keep the standing verdict and baseline.
            return
        series.windows += 1
        value = window.quantile(self.drift_quantile)
        series.last_window_value = value
        baseline = series.baseline
        if baseline is None:
            # First full window IS the baseline; by definition normal.
            series.baseline = value
            self._transition(series, NORMAL, lane=lane, phase=phase,
                             window_value=value)
            return
        degraded_band = baseline * self.degraded_factor + self.min_band_s
        regressed_band = baseline * self.regressed_factor + self.min_band_s
        if value > regressed_band:
            state = REGRESSED
        elif value > degraded_band:
            state = DEGRADED
        else:
            state = NORMAL
        if state == NORMAL:
            # The baseline learns ONLY from normal windows: a standing
            # regression must be measured against the healthy past, not
            # slowly become the new normal.
            series.baseline = baseline + self.alpha * (value - baseline)
        self._transition(series, state, lane=lane, phase=phase,
                         window_value=value)

    def _transition(
        self, series: SeriesState, state: str, *, lane: int | None,
        phase: str | None, window_value: float,
    ) -> None:
        previous = series.state
        if state == previous:
            return
        series.state = state
        series.state_since = self.clock()
        # The device-health transition discipline: only transitions touching
        # trouble are incident material. normal<->degraded flips log at
        # INFO; anything touching REGRESSED gets the head-sampling-proof
        # span and (entering) the counter + an arm.
        touching_regressed = REGRESSED in (state, previous)
        logger.log(
            logging.WARNING if state == REGRESSED else logging.INFO,
            "perf drift: %s %s -> %s (window %s=%.4fs baseline=%.4fs)",
            series.key,
            previous,
            state,
            f"p{int(self.drift_quantile * 100)}",
            window_value,
            series.baseline or 0.0,
        )
        if not touching_regressed:
            return
        if self.tracer is not None:
            self.tracer.record_span(
                "perf.regression",
                trace_id=tracing.new_trace_id(),
                parent_id=None,
                start_unix=self.walltime(),
                duration_s=0.0,
                attributes={
                    "series": series.key,
                    "lane": lane if lane is not None else -1,
                    "phase": phase or "",
                    "from": previous,
                    "to": state,
                    "window_s": round(window_value, 6),
                    "baseline_s": round(series.baseline or 0.0, 6),
                },
                status="error" if state == REGRESSED else "ok",
            )
        if state != REGRESSED:
            return
        series.regressions += 1
        if self.metrics is not None and lane is not None:
            self.metrics.record_perf_regression(
                lane=str(lane), phase=phase or ""
            )
        if lane is not None:
            self.arm_profile(
                lane,
                reason=f"regression:{phase or series.key}",
                source_key=series.key,
            )

    # -------------------------------------------------------- profile arming

    def arm_profile(self, lane: int, *, reason: str, source_key: str = "") -> None:
        """Arm the JAX profiler for the next eligible request on `lane`.
        Throttled: within perf_profile_min_interval_seconds of the last
        consumed capture on the lane, new triggers are dropped (a standing
        regression would otherwise profile every request)."""
        if not self.enabled or not self.auto_profile:
            return
        now = self.clock()
        last = self._last_profiled.get(lane)
        if last is not None and now - last < self.profile_interval:
            return
        existing = self._arms.get(lane)
        if existing is not None:
            existing.reason = reason  # refresh, never queue a second
            return
        self._arms[lane] = ProfileArm(
            lane=lane, reason=reason, armed_at=now, source_key=source_key
        )
        logger.info("auto-profile armed (lane=%d, reason=%s)", lane, reason)

    def take_profile_arm(self, lane: int, tenant: str | None) -> str | None:
        """Consume the lane's pending arm for a CONSENTING tenant; returns
        the trigger reason, or None (nothing armed / tenant opted out — an
        opt-out tenant's request passes through untouched and the arm waits
        for the next eligible one)."""
        if not self.enabled or not self.auto_profile:
            return None
        arm = self._arms.get(lane)
        if arm is None:
            return None
        if tenant is not None and tenant in self._opt_out:
            return None
        del self._arms[lane]
        self._last_profiled[lane] = self.clock()
        return arm.reason

    def note_profile_captured(
        self, data: bytes, *, lane: int, reason: str,
        tenant: str | None = None, trace_id: str | None = None,
    ) -> str | None:
        """Harvest one auto-captured profile.zip into the store; returns
        the profile id (the /profiles/{id} handle), or None when the
        store could not make it durable — the caller then leaves the
        artifact in the request's files instead of destroying the only
        copy, and nothing counts as captured."""
        if not self.enabled or self.store is None:
            return None
        profile_id = self.store.add(
            data,
            {
                "lane": lane,
                "reason": reason,
                "tenant": tenant,
                "trace_id": trace_id,
            },
        )
        if profile_id is None:
            return None
        self.profiles_captured += 1
        if self.metrics is not None:
            self.metrics.record_perf_profile(reason=reason.split(":", 1)[0])
        logger.info(
            "auto-profile captured (lane=%d, reason=%s, id=%s, trace=%s)",
            lane, reason, profile_id, trace_id,
        )
        return profile_id

    # ---------------------------------------------------------------- surfaces

    def state_gauge_samples(self) -> dict[tuple[str, ...], float]:
        """perf_state{lane,phase,state} one-hot feed (scrape-time)."""
        samples: dict[tuple[str, ...], float] = {}
        for (lane, phase), series in self._series.items():
            for state in PERF_STATES:
                samples[(str(lane), phase, state)] = (
                    1.0 if series.state == state else 0.0
                )
        return samples

    def store_gauge_samples(self) -> dict[tuple[str, ...], float]:
        if self.store is None:
            return {}
        return {
            ("bytes",): float(self.store.total_bytes()),
            ("entries",): float(self.store.entry_count()),
        }

    def lane_phase_states(self) -> dict[str, str]:
        """{"<lane>/<phase>": state} — the tests' and /statusz's quick read."""
        return {
            f"{lane}/{phase}": series.state
            for (lane, phase), series in self._series.items()
        }

    def snapshot(self) -> dict:
        """The GET /perf body (and the /statusz perf section)."""
        body: dict = {
            "enabled": self.enabled,
            "window_seconds": self.window_s,
            "drift_quantile": self.drift_quantile,
            "bands": {
                "degraded_factor": self.degraded_factor,
                "regressed_factor": self.regressed_factor,
                "min_band_s": self.min_band_s,
            },
            "series": {},
            "tenants": {},
        }
        if not self.enabled:
            return body
        worst = NORMAL
        for (lane, phase), series in sorted(self._series.items()):
            body["series"][f"{lane}/{phase}"] = series.snapshot()
            if PERF_STATES.index(series.state) > PERF_STATES.index(worst):
                worst = series.state
        for tenant, series in sorted(self._tenants.items()):
            body["tenants"][tenant] = series.snapshot()
        body["status"] = worst
        body["auto_profile"] = {
            "enabled": self.auto_profile,
            "armed_lanes": sorted(
                {lane: arm.reason for lane, arm in self._arms.items()}.items()
            ),
            "captured": self.profiles_captured,
            "opt_out_tenants": sorted(self._opt_out),
        }
        if self.store is not None:
            body["profile_store"] = {
                "entries": self.store.entry_count(),
                "bytes": self.store.total_bytes(),
                "max_bytes": self.store.max_bytes,
                "max_entries": self.store.max_entries,
                "evictions": self.store.evictions,
            }
        return body
