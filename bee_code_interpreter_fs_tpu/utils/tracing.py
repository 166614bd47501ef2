"""First-party request-scoped distributed tracing (dependency-free).

The control plane is a multi-stage async pipeline (API entry → admission →
slot grant → delta upload → execute → download); after the scheduler (PR 2)
and the content-addressed transfer (PR 3) a single request crosses six
subsystems with only aggregate metrics to explain where its latency went.
This module is the layer that connects them into causal, exportable traces —
the same approach as ``utils/retrying.py``: exactly what the request path
needs, no third-party deps (opentelemetry is not in this environment).

Design:

- **W3C-style ids** — 32-hex trace id, 16-hex span id, propagated via the
  ``traceparent`` header format (``00-<trace>-<span>-<flags>``); the gRPC
  surface carries the same value as ``x-traceparent`` metadata and the
  orchestrator forwards it to sandbox executors on every HTTP call.
- **ContextVar current span** — child spans parent themselves off the task's
  current span automatically, so instrumentation points never thread a span
  argument through six call layers. Events (retry decisions, breaker
  rejections, scheduler enqueue/grant/shed) attach to whatever span is
  current via :func:`add_event`.
- **Head-based sampling** — the decision is made once, when the trace
  starts: an incoming ``traceparent`` is respected (flag 01 records, 00
  propagates ids but records nothing), otherwise ``sample_ratio`` decides.
  Unsampled and disabled paths go through no-op spans whose methods do no
  allocation or locking.
- **Pluggable exporters** — a bounded in-memory ring (the ``GET /traces``
  debug surface) and an append-only JSONL file. Every finished span also
  lands in the module-level :data:`GLOBAL_RING` flight recorder (bounded),
  which CI dumps as a workflow artifact when a chaos leg fails.

Determinism for tests: the sampling ``rng`` and the ``clock``/``walltime``
pair are injectable.
"""

from __future__ import annotations

import json
import logging
import os
import random
import re
import threading
import time
from collections import deque
from collections.abc import Iterable
from contextvars import ContextVar

TRACE_ID_RE = re.compile(r"^[0-9a-f]{32}$")
_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)

current_span_var: ContextVar["Span | NullSpan | None"] = ContextVar(
    "current_span", default=None
)


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def format_traceparent(trace_id: str, span_id: str, sampled: bool) -> str:
    return f"00-{trace_id}-{span_id}-{'01' if sampled else '00'}"


def parse_traceparent(header: str | None) -> tuple[str, str, bool] | None:
    """``(trace_id, parent_span_id, sampled)`` from a W3C traceparent, or
    None for anything malformed (malformed context starts a fresh trace —
    the spec's restart rule — rather than erroring a user request)."""
    if not header:
        return None
    match = _TRACEPARENT_RE.match(header.strip().lower())
    if match is None:
        return None
    version, trace_id, span_id, flags = match.groups()
    if version == "ff" or trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id, bool(int(flags, 16) & 1)


class NullSpan:
    """Non-recording span: carries context ids for propagation (an unsampled
    trace still forwards its ``traceparent`` with flag 00, per W3C), records
    nothing, costs nothing. The id-less singleton :data:`NOOP` is what a
    disabled tracer hands out — its ``traceparent()`` is None, so nothing
    propagates at all."""

    __slots__ = ("trace_id", "span_id", "_install", "_tokens")
    recording = False

    def __init__(
        self, trace_id: str = "", span_id: str = "", *, install: bool = True
    ) -> None:
        self.trace_id = trace_id
        self.span_id = span_id
        # Install as current only when there is context to propagate (an
        # unsampled ROOT still forwards ids downstream). Children of a null
        # span never install (install=False): their parent is already the
        # current span in every task that inherits the context, and a shared
        # instance re-entered from concurrently gathered tasks would pop
        # another task's ContextVar token (LIFO across contexts → ValueError).
        # The id-less NOOP singleton skips even the contextvar write — the
        # true zero-cost path.
        self._install = install and bool(trace_id)
        self._tokens: list = []

    def __enter__(self) -> "NullSpan":
        if self._install:
            self._tokens.append(current_span_var.set(self))
        return self

    def __exit__(self, *exc) -> bool:
        if self._install and self._tokens:
            current_span_var.reset(self._tokens.pop())
        return False

    def set_attribute(self, key: str, value) -> None:
        pass

    def add_event(self, name: str, **attributes) -> None:
        pass

    def traceparent(self) -> str | None:
        if not self.trace_id:
            return None
        return format_traceparent(self.trace_id, self.span_id, False)


NOOP = NullSpan()


class Span:
    """One recorded unit of work. Context-manager protocol installs it as
    the task's current span; exiting (or :meth:`end`) stamps the duration
    and exports it. Exceptions mark ``status="error"`` and still export —
    a failed stage is exactly what a trace is for."""

    __slots__ = (
        "tracer",
        "name",
        "trace_id",
        "span_id",
        "parent_id",
        "start_unix",
        "_start_mono",
        "duration_s",
        "attributes",
        "events",
        "status",
        "_token",
        "_ended",
    )
    recording = True

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: str,
        span_id: str,
        parent_id: str | None,
        attributes: dict | None = None,
    ) -> None:
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start_unix = tracer.walltime()
        self._start_mono = tracer.clock()
        self.duration_s = 0.0
        self.attributes = dict(attributes) if attributes else {}
        self.events: list[dict] = []
        self.status = "ok"
        self._token = None
        self._ended = False

    def set_attribute(self, key: str, value) -> None:
        self.attributes[key] = value

    def add_event(self, name: str, **attributes) -> None:
        event = {"name": name, "ts": self.tracer.walltime()}
        if attributes:
            event["attributes"] = attributes
        self.events.append(event)

    def traceparent(self) -> str:
        """Context to hand the next hop (this span becomes its parent)."""
        return format_traceparent(self.trace_id, self.span_id, True)

    def __enter__(self) -> "Span":
        self._token = current_span_var.set(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._token is not None:
            current_span_var.reset(self._token)
            self._token = None
        if exc_type is not None:
            self.status = "error"
            self.attributes.setdefault(
                "error", f"{exc_type.__name__}: {exc}"[:200]
            )
        self.end()
        return False

    def end(self) -> None:
        if self._ended:
            return
        self._ended = True
        self.duration_s = max(0.0, self.tracer.clock() - self._start_mono)
        self.tracer._export(self.to_dict())

    def to_dict(self) -> dict:
        data = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_unix": round(self.start_unix, 6),
            "duration_s": round(self.duration_s, 6),
            "status": self.status,
        }
        if self.attributes:
            data["attributes"] = self.attributes
        if self.events:
            data["events"] = self.events
        return data


class TraceRing:
    """Bounded in-memory store of finished spans (newest win), thread-safe:
    spans finish on the event loop but ``/metrics``-style debug reads may
    come from anywhere. The bound is the whole memory story — a busy service
    simply remembers its most recent ~capacity spans."""

    def __init__(self, capacity: int = 4096) -> None:
        self._spans: deque[dict] = deque(maxlen=max(1, capacity))
        self._lock = threading.Lock()

    def add(self, span: dict) -> None:
        with self._lock:
            self._spans.append(span)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def trace(self, trace_id: str) -> list[dict]:
        """Every retained span of one trace, in start order; of spans that
        start together the longer first (a parent before its first child)."""
        with self._lock:
            spans = [s for s in self._spans if s.get("trace_id") == trace_id]
        return sorted(
            spans,
            key=lambda s: (s.get("start_unix", 0.0), -s.get("duration_s", 0.0)),
        )

    def recent(self, limit: int = 20, offset: int = 0) -> list[dict]:
        """Newest distinct traces (summary rows for the debug endpoint);
        `offset` pages past the newest rows so the whole ring stays
        reachable through bounded responses."""
        with self._lock:
            spans = list(self._spans)
        grouped: dict[str, list[dict]] = {}
        for span in spans:
            grouped.setdefault(span.get("trace_id", ""), []).append(span)
        summaries = []
        for trace_id, members in grouped.items():
            entry = {
                "trace_id": trace_id,
                "spans": len(members),
                "start_unix": min(s.get("start_unix", 0.0) for s in members),
                "root": None,
                "errors": sum(1 for s in members if s.get("status") == "error"),
            }
            # The root is the span whose parent is outside this trace — a
            # trace joined from an upstream traceparent has a root with a
            # non-null (remote) parent id.
            ids = {s.get("span_id") for s in members}
            roots = [s for s in members if s.get("parent_id") not in ids]
            if roots:
                root = min(roots, key=lambda s: s.get("start_unix", 0.0))
                entry["root"] = root.get("name")
                entry["duration_s"] = root.get("duration_s")
            summaries.append(entry)
        summaries.sort(key=lambda e: e["start_unix"], reverse=True)
        offset = max(0, offset)
        return summaries[offset : offset + max(0, limit)]

    def export_jsonl(self, trace_id: str | None = None) -> str:
        """The retained spans (optionally one trace) as JSONL, one span per
        line — the offline-analysis/CI-artifact format."""
        with self._lock:
            spans = list(self._spans)
        if trace_id is not None:
            spans = [s for s in spans if s.get("trace_id") == trace_id]
        return "".join(json.dumps(s, sort_keys=True) + "\n" for s in spans)


# Module-level flight recorder: every tracer's finished spans also land here
# (bounded), so post-hoc debugging — e.g. CI exporting traces after a failed
# chaos leg — needs no handle to whichever Tracer instance did the work.
GLOBAL_RING = TraceRing(capacity=4096)


class JsonlExporter:
    """Append-only JSONL file exporter (one span per line). Write failures
    disable the exporter with one warning instead of failing requests —
    tracing must never take down the traced path."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._lock = threading.Lock()
        self._broken = False

    def add(self, span: dict) -> None:
        if self._broken:
            return
        line = json.dumps(span, sort_keys=True) + "\n"
        try:
            with self._lock, open(self.path, "a", encoding="utf-8") as f:
                f.write(line)
        except OSError:
            self._broken = True
            logging.getLogger(__name__).warning(
                "trace JSONL exporter disabled: cannot write %s", self.path
            )


class Tracer:
    """Span factory + sampling policy + exporter fan-out for one service.

    ``enabled=False`` (``APP_TRACING_ENABLED=0``) turns the whole subsystem
    into no-ops: every factory method returns :data:`NOOP` and nothing is
    ever allocated or exported."""

    # Bounds on the tentative buffer: concurrent tail-candidate traces
    # beyond the cap fall back to plain unsampled (ids propagate, nothing
    # records), and one trace retains at most this many spans — the whole
    # memory story for tail sampling.
    TAIL_MAX_TRACES = 64
    TAIL_MAX_SPANS = 512

    def __init__(
        self,
        *,
        enabled: bool = True,
        sample_ratio: float = 1.0,
        ring: TraceRing | None = None,
        jsonl_path: str = "",
        metrics=None,
        rng: random.Random | None = None,
        clock=time.perf_counter,
        walltime=time.time,
        tail_enabled: bool = True,
        tail_slow_seconds: float = 5.0,
    ) -> None:
        self.enabled = enabled
        self.sample_ratio = min(1.0, max(0.0, sample_ratio))
        self.ring = ring if ring is not None else TraceRing()
        self.jsonl = JsonlExporter(jsonl_path) if jsonl_path else None
        self.metrics = metrics
        self._rng = rng or random.Random(os.urandom(8))
        self.clock = clock
        self.walltime = walltime
        # Tail-based sampling: traces the head coin flip REJECTED are still
        # recorded tentatively; when the root finishes they are kept anyway
        # if they turned out to matter (error status anywhere, a
        # limit.violation event, or a slow root) and dropped otherwise.
        # This is the flight recorder that keeps a batched dispatch's one
        # bad request reconstructible at 1% head sampling.
        self.tail_enabled = tail_enabled
        self.tail_slow_seconds = max(0.0, tail_slow_seconds)
        # trace_id -> {"root": span_id, "spans": [dict, ...]}
        self._tentative: dict[str, dict] = {}
        # Additional span sinks (the OTLP exporter registers here): each gets
        # every FINAL span via .add(span_dict). Sinks must be non-blocking
        # and never raise — they sit on the span-finish path.
        self.extra_exporters: list = []

    def add_exporter(self, exporter) -> None:
        """Register an extra span sink (`.add(span: dict)` contract, same as
        TraceRing/JsonlExporter). Used by the OTLP exporter so finished
        spans finally leave the process."""
        self.extra_exporters.append(exporter)

    @classmethod
    def from_config(cls, config, metrics=None) -> "Tracer":
        return cls(
            enabled=config.tracing_enabled,
            sample_ratio=config.tracing_sample_ratio,
            ring=TraceRing(config.tracing_ring_capacity),
            jsonl_path=config.tracing_jsonl_path,
            metrics=metrics,
            tail_enabled=config.tracing_tail_enabled,
            tail_slow_seconds=config.tracing_tail_slow_seconds,
        )

    # -------------------------------------------------------------- factories

    def start_trace(
        self,
        name: str,
        *,
        traceparent: str | None = None,
        attributes: dict | None = None,
    ) -> Span | NullSpan:
        """Root span for one request. An incoming ``traceparent`` joins its
        trace (its sampled flag is respected — head-based sampling decides
        once, at the edge that started the trace); absent or malformed
        context starts a fresh trace sampled at ``sample_ratio``."""
        if not self.enabled:
            return NOOP
        parsed = parse_traceparent(traceparent)
        if parsed is not None:
            trace_id, parent_id, sampled = parsed
        else:
            trace_id, parent_id = new_trace_id(), None
            sampled = (
                self.sample_ratio >= 1.0
                or self._rng.random() < self.sample_ratio
            )
        if not sampled:
            if (
                self.tail_enabled
                and parsed is None
                and len(self._tentative) < self.TAIL_MAX_TRACES
            ):
                # Head sampling said no, but record TENTATIVELY anyway:
                # the root's finish decides keep-vs-drop (tail sampling).
                # Only for traces STARTED here — an upstream flag-00
                # decision is respected per W3C.
                span = Span(
                    self, name, trace_id, new_span_id(), parent_id, attributes
                )
                self._tentative[trace_id] = {
                    "root": span.span_id,
                    "spans": [],
                }
                return span
            # Propagate ids (flag 00) downstream, record nothing. Children
            # of a NullSpan are the NullSpan itself — same ids onward.
            return NullSpan(trace_id, parent_id or new_span_id())
        return Span(self, name, trace_id, new_span_id(), parent_id, attributes)

    def span(
        self, name: str, *, attributes: dict | None = None
    ) -> Span | NullSpan:
        """Child of the task's current span. With no current span (direct
        library use, tracing disabled upstream) or a non-recording one,
        returns the cheapest possible no-op."""
        if not self.enabled:
            return NOOP
        parent = current_span_var.get()
        if parent is None:
            return NOOP
        if not parent.recording:
            # A fresh non-installing null child per call: concurrently
            # gathered tasks must never share a context-manager instance
            # (see NullSpan.__init__), and the parent's ids still propagate.
            return NullSpan(parent.trace_id, parent.span_id, install=False)
        return Span(
            self, name, parent.trace_id, new_span_id(), parent.span_id,
            attributes,
        )

    def record_span(
        self,
        name: str,
        *,
        trace_id: str,
        parent_id: str | None,
        start_unix: float,
        duration_s: float,
        attributes: dict | None = None,
        events: Iterable[dict] = (),
        status: str = "ok",
    ) -> str | None:
        """Export an already-timed span directly — how remotely measured
        work (the sandbox executor's stages) and work timed around a call
        stack (the edges of a request) is grafted into a trace as child
        spans after the fact. Returns the new span's id, for its children."""
        if not self.enabled:
            return None
        span = {
            "name": name,
            "trace_id": trace_id,
            "span_id": new_span_id(),
            "parent_id": parent_id,
            "start_unix": round(start_unix, 6),
            "duration_s": round(max(0.0, duration_s), 6),
            "status": status,
        }
        if attributes:
            span["attributes"] = dict(attributes)
        events = list(events)
        if events:
            span["events"] = events
        self._export(span)
        return span["span_id"]

    # --------------------------------------------------------------- plumbing

    def _export(self, span: dict) -> None:
        pending = self._tentative.get(span.get("trace_id", ""))
        if pending is not None:
            if span["span_id"] != pending["root"]:
                if len(pending["spans"]) < self.TAIL_MAX_SPANS:
                    pending["spans"].append(span)
                return  # buffered; the root's finish decides
            del self._tentative[span["trace_id"]]
            if not self._tail_keep(span, pending["spans"]):
                return  # ordinary trace, head sampling's call stands
            # The root exports OUTSIDE the span-buffer cap: a kept trace
            # without its root has no duration and no tree anchor.
            for buffered in [*pending["spans"], span]:
                buffered.setdefault("attributes", {})["sampled"] = "tail"
                self._export_final(buffered)
            return
        self._export_final(span)

    @staticmethod
    def _span_interesting(span: dict) -> bool:
        if span.get("status") == "error":
            return True
        return any(
            event.get("name") == "limit.violation"
            for event in span.get("events", ())
        )

    def _tail_keep(self, root: dict, spans: list[dict]) -> bool:
        """Does an unsampled-by-the-head trace earn retention? Errors and
        typed limit violations always do; so does a slow root (the
        slow-p99 flight-recorder case). The root is checked explicitly —
        it is no longer part of the buffered span list."""
        if root["duration_s"] >= self.tail_slow_seconds > 0:
            return True
        if self._span_interesting(root):
            return True
        return any(self._span_interesting(s) for s in spans)

    def _export_final(self, span: dict) -> None:
        self.ring.add(span)
        if self.ring is not GLOBAL_RING:
            GLOBAL_RING.add(span)
        if self.jsonl is not None:
            self.jsonl.add(span)
        for exporter in self.extra_exporters:
            exporter.add(span)
        histogram = getattr(self.metrics, "span_seconds", None)
        if histogram is not None:
            histogram.observe(span["duration_s"], span=span["name"])


def current_span() -> Span | NullSpan | None:
    return current_span_var.get()


def trace_headers() -> dict | None:
    """Headers that carry the current span's context over a wire hop to a
    sandbox (its executor stamps its own stage timings into a `trace` block
    of the reply then). None when there is nothing to propagate."""
    span = current_span()
    traceparent = span.traceparent() if span is not None else None
    return {"traceparent": traceparent} if traceparent else None


def current_trace_id() -> str | None:
    """The active trace id, or None (no trace / unsampled-without-ids)."""
    span = current_span_var.get()
    if span is None or not span.trace_id:
        return None
    return span.trace_id


def add_event(name: str, **attributes) -> None:
    """Attach an event to the current span, if one is recording. The hook
    decision points (retry engine, circuit breaker, scheduler) call this so
    they stay decoupled from span lifetimes — no current span, no cost."""
    span = current_span_var.get()
    if span is not None and span.recording:
        span.add_event(name, **attributes)
