"""Sandbox backend abstraction.

The reference hard-wired its orchestrator to Kubernetes
(services/kubernetes_code_executor.py); here the pool logic is backend-
agnostic so the same orchestrator runs against a local subprocess backend
(tests, dev, single-host TPU) or the Kubernetes backend (production,
TPU-slice pods). This is also what makes the e2e logic testable without a
cluster — the gap called out in SURVEY.md §4.
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass, field
from typing import Protocol, runtime_checkable

import httpx

from ...utils import tracing

logger = logging.getLogger(__name__)


class SandboxSpawnError(RuntimeError):
    pass


class ResetClient:
    """The HTTP client a backend POSTs /reset with, kept with its keep-alive
    connections for the backend's life: built on first use (inside the
    running loop, so never at import or in a constructor) and closed by the
    backend's `close()`, after which the backend has no sandbox left to
    reset. Building one is synchronous work on the event loop (its TLS
    context: 26 ms on the chip's host, PERF.md), once per turnover before.

    A plain transport, never the executor's `_http_client()`: that one may
    carry a fault-injecting transport whose seeded draws /reset must not
    consume. No connection cap: a slice's hosts are POSTed to at once, and
    the live hosts bound the connections. A pooled connection the sandbox
    has closed is dropped by httpcore before reuse, not handed a request."""

    def __init__(self) -> None:
        self._client: httpx.AsyncClient | None = None

    def get(self) -> httpx.AsyncClient:
        if self._client is None:
            self._client = httpx.AsyncClient(
                limits=httpx.Limits(
                    max_connections=None,
                    max_keepalive_connections=64,
                    keepalive_expiry=30.0,
                ),
            )
        return self._client

    async def aclose(self) -> None:
        if self._client is not None:
            await self._client.aclose()


async def reset_sandbox_over_http(
    sandbox: "Sandbox", kept: ResetClient, *, timeout: float
) -> "Sandbox | None":
    """Shared generation-turnover fan-out: POST /reset to every host of the
    sandbox over the backend's kept client; all must answer 200 + ok within
    `timeout` seconds each. Returns the sandbox with its generation bumped,
    or None (caller must dispose). Backend-specific prechecks (process
    liveness, pod registry) stay in the backends."""
    # The turnover's trace context, where it has one: the executor then
    # stamps its own stages (runner_reset, wipe) into the reply, and they are
    # left on `meta["reset_trace"]`, one block per host, for the caller to
    # graft under its `sandbox.reset` span.
    headers = tracing.trace_headers()
    started = time.perf_counter()
    try:
        client = kept.get()
        # Obtaining the client has a span of its own in the caller's trace
        # (`sandbox.reset_client`): a backend's first turnover builds it.
        sandbox.meta["reset_client_s"] = time.perf_counter() - started
        resps = await asyncio.gather(
            *(
                client.post(f"{url}/reset", headers=headers, timeout=timeout)
                for url in sandbox.host_urls
            ),
            return_exceptions=True,
        )
    except Exception:  # noqa: BLE001 — reuse is best-effort
        return None
    blocks = []
    for resp in resps:
        if isinstance(resp, BaseException) or resp.status_code != 200:
            return None
        try:
            body = resp.json()
        except ValueError:
            return None
        if not body.get("ok"):
            return None
        blocks.append(body.get("trace"))
    sandbox.meta["reset_trace"] = blocks
    sandbox.meta["generation"] = sandbox.meta.get("generation", 0) + 1
    logger.info(
        "recycled sandbox %s (generation %d)",
        sandbox.id,
        sandbox.meta["generation"],
    )
    return sandbox


def num_hosts_for(chip_count: int, chips_per_host: int) -> int:
    """Hosts needed for a slice of `chip_count` chips (0 chips = 1 CPU host).

    Shared by every backend so the same chip_count always produces the same
    group shape locally and on Kubernetes. Sub-host counts (e.g. 1 chip of a
    4-chip host) are fine — one pod requests exactly that many chips. Above
    one host, the count must tile exactly: chip_count=6 on 4-chip hosts
    would silently reserve 8 chips while everything downstream (pool lane,
    metrics, user-visible device count) said 6.
    """
    per_host = max(1, chips_per_host)
    if chip_count <= 0:
        return 1
    if chip_count > per_host and chip_count % per_host != 0:
        raise ValueError(
            f"chip_count={chip_count} does not tile onto {per_host}-chip "
            f"hosts; use a multiple of {per_host}"
        )
    return -(-chip_count // per_host)


@dataclass
class Sandbox:
    """A live single-use execution sandbox reachable over HTTP.

    `chip_count` is the number of TPU chips attached (0 = CPU-only); the pool
    keeps one lane per chip_count so an Execute asking for a v5e-4 slice never
    steals a single-chip sandbox and vice versa.

    A multi-host slice (chip_count > chips-per-host) is ONE sandbox with one
    executor per host: `host_urls` lists every host's executor server, `url`
    is host 0 (the jax.distributed coordinator). The hosts share a JAX mesh
    over ICI but have separate workspaces; the orchestrator fans file
    transfers and /execute out to all of them (SURVEY.md §7.6).
    """

    id: str
    url: str  # base URL of the in-sandbox executor server (host 0)
    chip_count: int = 0
    meta: dict = field(default_factory=dict)
    host_urls: list[str] = field(default_factory=list)

    def __post_init__(self) -> None:
        if not self.host_urls:
            self.host_urls = [self.url]

    @property
    def num_hosts(self) -> int:
        return len(self.host_urls)


@runtime_checkable
class SandboxBackend(Protocol):
    async def spawn(self, chip_count: int = 0) -> Sandbox:
        """Create a sandbox and wait until its executor server is ready."""
        ...

    def pool_capacity(self, chip_count: int) -> int | None:
        """Max warm sandboxes a pool lane should hold on this backend, or
        None for unbounded. A warm TPU sandbox owns its chips for its whole
        pool residency, so the cap reflects physical chip availability —
        the pool must never demand more chips than exist (VERDICT r1 #1/#5)."""
        ...

    async def delete(self, sandbox: Sandbox) -> None:
        """Tear the sandbox down (idempotent, must not raise)."""
        ...

    @property
    def compile_cache_dir_scope(self) -> str:
        """Who can write a sandbox's JAX compilation-cache dir — the trust
        statement the fleet compile-cache harvest gate is built on:

        - ``"private"``  — each sandbox has its own dir (local per-sandbox
          mode, kubernetes emptyDir): only that sandbox's own runs write
          it, so per-sandbox taint vouches for its contents.
        - ``"shared"``   — one dir shared by ALL of this control plane's
          sandboxes (local shared-dir mode): any tenant run anywhere
          taints it for the control plane's lifetime.
        - ``"external"`` — writable by parties outside this control plane
          (kubernetes PVC/hostPath volume sources): nothing can vouch for
          it, harvest is structurally impossible.

        CodeExecutor reads this with a fail-closed ``"external"`` default,
        so a backend that does not declare a scope is never harvested."""
        ...

    async def reset(self, sandbox: Sandbox) -> Sandbox | None:
        """Scrub the sandbox for a new generation, keeping its warm device
        process (TPU lease) alive: wiped workspace, reaped stray processes,
        restored runner state. Returns the recycled Sandbox, or None if it
        cannot be safely reused (caller must delete() it instead). Backends
        without generation turnover just return None — every request then
        pays a full spawn, the reference's behavior."""
        return None

    async def close(self) -> None:
        """Release backend resources (delete all live sandboxes)."""
        ...
