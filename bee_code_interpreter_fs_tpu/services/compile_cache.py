"""Fleet-wide persistent XLA compilation cache: the control plane's store
and the seed/harvest protocol against sandbox executors.

On the TPU rig of rounds 2 to 5 the dominant cost of real array workloads
was JAX/XLA first-compile and accelerator page-in, not execution.
Per-sandbox ``JAX_COMPILATION_CACHE_DIR`` plumbing has existed since the
seed, but it was host-local at best and pod-local-and-dying on Kubernetes:
a million users running the same N popular kernels recompiled them once per
sandbox. This module applies the PR 3 content-addressed machinery to jit
artifacts so the fleet compiles each kernel exactly once:

- **Store** — JAX names every persistent-cache entry by a deterministic
  filename derived from its own cache key (``jit_<name>-<hash>-cache``), so
  the filename IS a stable fleet-wide identity. ``CompileCacheStore`` keeps
  a bounded hot set of those entries: bytes live in a content-addressed
  ``Storage`` (deduped by SHA-256 — identical executables from different
  sandboxes store once), an index maps entry name -> (sha, size, last_hit)
  and persists as JSON so the hot set survives control-plane restarts.
- **Seed at spawn** — every freshly spawned sandbox gets the hot set pushed
  into its cache dir before serving (GET /compile-cache-manifest to learn
  what the host already holds, conditional PUT for the rest — unchanged
  entries never cross the wire twice).
- **Harvest at turnover/teardown, TRUSTED PROVENANCE ONLY** — after a
  sandbox serves (generation turnover or disposal), entries it compiled
  that the store has never seen are pulled back (hash-negotiated: the
  manifest's sha is checked against the store before any bytes move).
  Admission is gated on provenance: a sandbox is harvestable only while
  every piece of code it has EVER run was control-plane-authored (the
  pre-warm kernel set). The moment tenant code executes on a sandbox its
  sync state is tainted for the sandbox's lifetime and harvest never
  touches it again — user code can write arbitrary bytes into
  ``JAX_COMPILATION_CACHE_DIR``, and a harvested artifact is a serialized
  XLA executable that every seeded sandbox would deserialize and run
  (cross-tenant code execution), while even a benignly compiled artifact
  can embed tenant data through constant folding (cross-tenant data
  leak). Tenant-compiled artifacts therefore never enter the fleet store,
  full stop; they still serve that one sandbox locally through its
  preserved cache dir. As a second line of defense the store is
  first-write-wins: a harvest manifest presenting different bytes under
  an entry name the store already maps is rejected, never admitted as a
  replacement.
- **Bounded hot set** — LRU by last hit with byte+entry caps, so seeding
  stays O(hot set), not O(history). Recency moves only on evidence of a
  real (re)compile: harvest admission, or a trusted sandbox presenting an
  entry the control plane did NOT seed into it (seeded entries reappear
  in every harvest manifest, so their re-observation proves nothing).
  The hot set self-heals across control-plane restarts: pre-warm runs on
  every start, so an evicted-but-still-prewarmed kernel is recompiled and
  re-admitted with fresh recency (one trusted recompile), while a kernel
  dropped from ``PREWARM_SOURCES`` is never refreshed again and ages to
  the LRU end.

A host that 404s the manifest route is remembered as legacy (old executor
binary) and is never probed again; the kill switch
(``APP_COMPILE_CACHE_ENABLED=0``) restores the exact pre-cache behavior (no
compile-cache HTTP at all).

Grounded in PAPERS.md ("Compiler-First State Space Duality and Portable
O(1) Autoregressive Caching", "Automatic Full Compilation ... to Cloud
TPUs"): compile-once/run-anywhere is the whole game on TPU.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import time
from collections.abc import Callable
from dataclasses import dataclass, field
from pathlib import Path

import httpx

from ..utils.validation import SHA256_HEX_RE
from .storage import Storage, StorageObjectNotFound

logger = logging.getLogger(__name__)

# Entry names are JAX cache-key filenames (plus the -atime sidecars some
# jax versions keep). Anything path-traversal-ish is rejected outright —
# the name becomes a URL segment and a file path on both ends.
_BAD_NAME_PARTS = ("..", "\x00")

# Wire timeouts. Sync runs on spawn and TURNOVER paths: turnover of a dead
# or wedged sandbox must not park its lane's refill behind the shared
# client's 30s default — the manifest probe fails fast, which short-circuits
# the whole host. Entry bodies get longer (they stream real bytes).
MANIFEST_TIMEOUT = 5.0
ENTRY_TIMEOUT = 15.0


def valid_entry_name(rel: str) -> bool:
    if not rel or len(rel) > 512 or rel.startswith("/"):
        return False
    if rel.endswith("-atime"):
        # jax's per-host LRU sidecars (rewritten on every cache read):
        # local bookkeeping with no fleet meaning. The executor filters
        # them out of its manifest too — this guards against older ones.
        return False
    return not any(bad in rel for bad in _BAD_NAME_PARTS)


@dataclass
class SeedStats:
    """One sandbox's seeding outcome (summed across its hosts)."""

    pushed_files: int = 0
    pushed_bytes: int = 0
    skipped_files: int = 0  # host already held identical content
    skipped_bytes: int = 0


@dataclass
class HarvestStats:
    """One sandbox's harvest outcome (summed across its hosts)."""

    new_files: int = 0
    new_bytes: int = 0
    known_files: int = 0  # manifest entries the store already had
    discarded: int = 0  # bytes arrived but hash mismatched the manifest
    conflicts: int = 0  # entry name already mapped to DIFFERENT bytes


@dataclass
class _Entry:
    sha: str
    size: int
    last_hit: float
    hits: int = 0


class CompileCacheStore:
    """The fleet's hot set of compiled XLA executables.

    Synchronous on purpose: every operation is index bookkeeping (byte
    movement happens through the async ``Storage``); callers hold no lock
    because the control plane is one asyncio thread (the scale-out ROADMAP
    item moves this behind the same shared-store interface as the
    scheduler state).
    """

    INDEX_NAME = "index.json"

    def __init__(
        self,
        store_path: str | os.PathLike,
        *,
        max_bytes: int = 1 << 30,
        max_entries: int = 4096,
        enabled: bool = True,
        clock=time.time,
    ) -> None:
        self.enabled = enabled
        self.max_bytes = max(0, int(max_bytes))
        self.max_entries = max(0, int(max_entries))
        self._clock = clock
        self.path = Path(store_path)
        self._entries: dict[str, _Entry] = {}
        # True whenever the entry map has mutated since the last successful
        # save — new admissions, dedup mappings AND evictions (eviction
        # deletes storage objects, so an unsaved index would reference bytes
        # the store no longer holds after a restart).
        self._dirty = False
        if not enabled:
            # Kill switch: no directories created, no state, every surface
            # answers empty — exact pre-cache behavior.
            self.storage = None
            return
        self.path.mkdir(parents=True, exist_ok=True)
        # Objects live in their own Storage (NOT the workspace-file store):
        # eviction deletes objects, and sharing a store would let a cache
        # eviction delete a workspace file's bytes out from under it.
        self.storage = Storage(self.path / "objects")
        self._load_index()

    @classmethod
    def from_config(cls, config) -> "CompileCacheStore":
        path = config.compile_cache_store_path or os.path.join(
            config.file_storage_path, ".compile-cache"
        )
        return cls(
            path,
            max_bytes=config.compile_cache_max_bytes,
            max_entries=config.compile_cache_max_entries,
            enabled=config.compile_cache_enabled,
        )

    # ------------------------------------------------------------- index IO

    def _load_index(self) -> None:
        try:
            raw = json.loads((self.path / self.INDEX_NAME).read_text())
        except (OSError, ValueError):
            return
        if not isinstance(raw, dict):
            return
        for rel, entry in raw.items():
            if not (isinstance(rel, str) and valid_entry_name(rel)):
                continue
            if not isinstance(entry, dict):
                continue
            sha = entry.get("sha")
            if not (isinstance(sha, str) and SHA256_HEX_RE.match(sha)):
                continue
            try:
                self._entries[rel] = _Entry(
                    sha=sha,
                    size=max(0, int(entry.get("size", 0))),
                    last_hit=float(entry.get("last_hit", 0.0)),
                    hits=max(0, int(entry.get("hits", 0))),
                )
            except (TypeError, ValueError):
                continue

    def save_index(self) -> None:
        """Atomic index persist (tmp + rename), best-effort: a failed save
        costs warm-start continuity, never correctness."""
        if not self.enabled:
            return
        blob = {
            rel: {
                "sha": e.sha,
                "size": e.size,
                "last_hit": e.last_hit,
                "hits": e.hits,
            }
            for rel, e in self._entries.items()
        }
        tmp = self.path / (self.INDEX_NAME + ".tmp")
        try:
            tmp.write_text(json.dumps(blob))
            os.replace(tmp, self.path / self.INDEX_NAME)
        except OSError:
            logger.warning("compile-cache index save failed", exc_info=True)
        else:
            self._dirty = False

    @property
    def dirty(self) -> bool:
        """Entry map mutated since the last successful save_index()."""
        return self._dirty

    # ------------------------------------------------------------- hot set

    def manifest(self) -> dict[str, str]:
        """The hot set as entry-name -> sha (what seeding pushes)."""
        if not self.enabled:
            return {}
        return {rel: e.sha for rel, e in self._entries.items()}

    def sha_of(self, rel: str) -> str | None:
        entry = self._entries.get(rel)
        return entry.sha if entry is not None else None

    def total_bytes(self) -> int:
        return sum(e.size for e in self._entries.values())

    def entry_count(self) -> int:
        return len(self._entries)

    def touch(self, rel: str) -> None:
        entry = self._entries.get(rel)
        if entry is not None:
            entry.last_hit = self._clock()
            entry.hits += 1
            self._dirty = True

    async def record(self, rel: str, sha: str, size: int) -> list[str]:
        """Admit a harvested entry (bytes already in storage under `sha`)
        and enforce the hot-set bounds. Returns the evicted entry names."""
        if not self.enabled or not valid_entry_name(rel):
            return []
        self._entries[rel] = _Entry(
            sha=sha, size=max(0, int(size)), last_hit=self._clock(), hits=1
        )
        self._dirty = True
        return await self._evict_over_caps()

    async def _evict_over_caps(self) -> list[str]:
        """LRU-by-last-hit eviction down to the byte/entry caps. Storage
        objects are deleted only when no surviving entry references the sha
        (distinct entry names can dedup onto identical bytes)."""
        evicted: list[str] = []
        while self._entries and (
            (self.max_entries and len(self._entries) > self.max_entries)
            or (self.max_bytes and self.total_bytes() > self.max_bytes)
        ):
            rel = min(self._entries, key=lambda r: self._entries[r].last_hit)
            entry = self._entries.pop(rel)
            evicted.append(rel)
            self._dirty = True
            if not any(e.sha == entry.sha for e in self._entries.values()):
                try:
                    await self.storage.delete(entry.sha)
                except OSError:
                    pass
        return evicted

    async def drop_unverified(self, sha: str) -> None:
        """A harvested body hashed to `sha` but the manifest promised
        something else (mid-transfer drop, racing rewrite): the object must
        not linger as an orphan unless another entry legitimately owns it."""
        if self.storage is None:
            return
        if not any(e.sha == sha for e in self._entries.values()):
            try:
                await self.storage.delete(sha)
            except OSError:
                pass


class HostCacheState:
    """What the control plane knows about one sandbox host's compile-cache
    dir. Mirrors transfer.HostManifest's tri-state: ``supports`` is None
    until observed, True after any manifest answer, False once a 404 proves
    the host legacy (an old binary without the endpoints) — after which no
    compile-cache HTTP is ever attempted again for that host."""

    __slots__ = ("present", "supports", "seeded")

    def __init__(self) -> None:
        self.present: dict[str, str] = {}
        self.supports: bool | None = None
        # Entry names whose host copy the store is KNOWN to agree with —
        # seeded into it, confirmed present at seed time, or admitted
        # from it by an earlier harvest. Their reappearance in a harvest
        # manifest is NOT evidence of a recompile (the cache dir outlives
        # /reset), so harvest must not refresh their recency.
        self.seeded: set[str] = set()

    def mark_legacy(self) -> None:
        self.present = {}
        self.seeded = set()
        self.supports = False


class SandboxCacheSync:
    """Per-sandbox compile-cache sync state + the wire protocol.

    Rides in ``Sandbox.meta`` (like SandboxTransfer) so it follows the
    sandbox through pool recycles and session parking. The cache dir is
    deliberately NOT wiped by /reset, so ``present`` stays valid across
    generations.
    """

    def __init__(
        self,
        store: CompileCacheStore,
        *,
        harvest_allowed: Callable[[], bool] | None = None,
    ) -> None:
        self.store = store
        # Control-plane-level trust gate, re-evaluated MID-harvest: on a
        # shared cache dir the writer that revokes trust is a different
        # sandbox, so the revocation can land while this sandbox's harvest
        # is awaiting the network — every admission re-checks it (see
        # _trust_revoked) so bytes written after the revocation can never
        # be admitted. None = only per-sandbox taint gates.
        self._harvest_allowed = harvest_allowed
        self._hosts: dict[str, HostCacheState] = {}
        # Surfaced into the first Result.phases after a seed (the request
        # that popped this freshly seeded sandbox reports what seeding it
        # cost) — see CodeExecutor._run_on_sandbox.
        self.pending_seed_bytes: int | None = None
        # Provenance gate for harvest. False only while every piece of code
        # this sandbox has ever run was control-plane-authored (pre-warm);
        # flips True — permanently, the cache dir outlives /reset — the
        # moment tenant code executes. A tainted sandbox's cache dir is
        # attacker-writable, and harvested entries are serialized XLA
        # executables the fleet would deserialize and run, so harvest
        # refuses it outright (not even a manifest probe).
        self.tainted = False

    def taint(self) -> None:
        self.tainted = True

    def _trust_revoked(self) -> bool:
        """Harvest trust as of RIGHT NOW. Checked at every await boundary
        that can admit bytes, not just at harvest entry: the taint (per
        sandbox or control-plane-wide via `harvest_allowed`) is set before
        the tainting tenant code runs, so any cache-dir write that code
        makes strictly follows the flag — a re-check immediately before
        admission therefore can never admit a post-revocation write, even
        when the revocation landed mid-harvest."""
        if self.tainted:
            return True
        return self._harvest_allowed is not None and not self._harvest_allowed()

    def host(self, base_url: str) -> HostCacheState:
        state = self._hosts.get(base_url)
        if state is None:
            state = HostCacheState()
            self._hosts[base_url] = state
        return state

    # -------------------------------------------------------------- protocol

    async def _fetch_manifest(
        self, client: httpx.AsyncClient, base: str, state: HostCacheState
    ) -> dict[str, str] | None:
        """GET /compile-cache-manifest; None = host unusable this round
        (legacy, disabled, or transient failure)."""
        try:
            resp = await client.get(
                f"{base}/compile-cache-manifest", timeout=MANIFEST_TIMEOUT
            )
        except httpx.HTTPError:
            return None
        if resp.status_code == 404:
            # Old binary (or compile cache disabled server-side): remembered
            # forever, exactly like the workspace-manifest fallback.
            state.mark_legacy()
            return None
        if resp.status_code != 200:
            return None
        try:
            files = resp.json().get("files", {})
        except ValueError:
            return None
        if not isinstance(files, dict):
            return None
        manifest = {
            rel: sha
            for rel, sha in files.items()
            if isinstance(rel, str)
            and valid_entry_name(rel)
            and isinstance(sha, str)
            and SHA256_HEX_RE.match(sha)
        }
        state.supports = True
        state.present = dict(manifest)
        return manifest

    async def seed_host(
        self, client: httpx.AsyncClient, base: str
    ) -> SeedStats:
        """Push the store's hot set into one host's cache dir. Entries the
        host already holds (manifest match or conditional-PUT 304) move no
        bytes. Failures degrade to fewer seeded entries, never to errors —
        a missed seed costs one recompile, not a request."""
        stats = SeedStats()
        if not self.store.enabled:
            return stats
        hot = self.store.manifest()
        if not hot:
            return stats
        state = self.host(base)
        if state.supports is False:
            return stats
        remote = await self._fetch_manifest(client, base, state)
        if remote is None:
            return stats
        for rel, sha in hot.items():
            size = 0
            try:
                size = await self.store.storage.size(sha)
            except (StorageObjectNotFound, ValueError):
                continue  # index ahead of storage (crash window): skip
            if remote.get(rel) == sha:
                state.seeded.add(rel)
                stats.skipped_files += 1
                stats.skipped_bytes += size
                continue
            if await self._put_entry(client, base, rel, sha):
                state.present[rel] = sha
                state.seeded.add(rel)
                stats.pushed_files += 1
                stats.pushed_bytes += size
                # Deliberately NOT a last_hit touch: every fresh sandbox
                # lacks everything, so a per-push refresh would flatten the
                # LRU signal across the whole hot set on every spawn.
                # last_hit moves only on evidence of a real (re)compile —
                # harvest admission, or a trusted run presenting an entry
                # this host was never seeded (state.seeded) — and the hot
                # set self-heals across restarts via the per-start
                # pre-warm (evicted-but-kept kernels re-admit; dropped
                # kernels age to the LRU end).
        return stats

    async def _put_entry(
        self, client: httpx.AsyncClient, base: str, rel: str, sha: str
    ) -> bool:
        async def stream():
            async with self.store.storage.reader(sha) as reader:
                while True:
                    data = await reader.read(1 << 20)
                    if not data:
                        return
                    yield data

        try:
            resp = await client.put(
                f"{base}/compile-cache/{rel}",
                content=stream(),
                headers={"If-None-Match": sha},
                timeout=ENTRY_TIMEOUT,
            )
        except httpx.HTTPError:
            return False
        # 304 = host already held these exact bytes; both count as present.
        return resp.status_code in (200, 304)

    async def harvest_host(
        self, client: httpx.AsyncClient, base: str
    ) -> HarvestStats:
        """Pull entries this host compiled that the store has never seen.
        Hash-negotiated: a manifest entry whose sha the store (or another
        entry) already holds moves no bytes. A body that does not hash to
        its promised sha (connection drop mid-stream surfaces as an httpx
        error; a racing rewrite as a mismatch) is discarded — no partial or
        orphan objects, ever.

        Trust boundary: refuses tainted sandboxes entirely (see ``tainted``)
        and is first-write-wins per entry name — a manifest presenting
        different bytes under a name the store already maps is a conflict,
        never a replacement (a rename-an-attack-under-a-known-identity
        channel, and in the benign case a nondeterministic recompile the
        fleet has no reason to prefer)."""
        stats = HarvestStats()
        if not self.store.enabled or self._trust_revoked():
            return stats
        state = self.host(base)
        if state.supports is False:
            return stats
        manifest = await self._fetch_manifest(client, base, state)
        if manifest is None:
            return stats
        for rel, sha in manifest.items():
            if self._trust_revoked():
                # Revoked while this harvest was awaiting the network (a
                # tenant run started on a sandbox sharing this cache dir):
                # everything not yet admitted stays out.
                logger.info(
                    "compile-cache harvest of %s stopped mid-flight: "
                    "trust revoked",
                    base,
                )
                break
            known_sha = self.store.sha_of(rel)
            if known_sha == sha:
                if rel not in state.seeded:
                    # Present on the host but NOT because we seeded it (or
                    # harvested it earlier): a trusted run genuinely
                    # (re)compiled this entry, so refresh its recency —
                    # once. Known entries reappear in every later harvest
                    # manifest of this host (the cache dir outlives
                    # /reset), so without marking them seeded here each
                    # re-observation would re-touch with no recompile and
                    # flatten the LRU signal to nothing.
                    self.store.touch(rel)
                    state.seeded.add(rel)
                stats.known_files += 1
                continue
            if known_sha is not None:
                self._note_conflict(base, rel, stats)
                continue
            if await self.store.storage.exists(sha):
                # Dedup: bytes already stored (same executable under a
                # different entry name, or a previous harvest) — record the
                # mapping without moving anything.
                size = await self.store.storage.size(sha)
                if await self._admit(base, rel, sha, size, stats, state):
                    stats.known_files += 1
                continue
            got = await self._get_entry(client, base, rel)
            if got is None:
                continue
            actual_sha, size = got
            if actual_sha != sha:
                # The manifest promised different content: never admit it
                # under the promised identity, never leave the stray bytes.
                await self.store.drop_unverified(actual_sha)
                stats.discarded += 1
                continue
            if await self._admit(base, rel, sha, size, stats, state):
                stats.new_files += 1
                stats.new_bytes += size
        return stats

    async def _admit(
        self,
        base: str,
        rel: str,
        sha: str,
        size: int,
        stats: HarvestStats,
        state: HostCacheState,
    ) -> bool:
        """Final admission, re-checking the store IMMEDIATELY before
        record(): harvest_host awaits the network between its first
        conflict check and this point, and two sandboxes' turnover
        harvests can race the same entry name (e.g. a nondeterministic
        recompile of the same kernel on two untainted sandboxes).
        First-write-wins must hold across that window too — without the
        re-check the loser would silently REPLACE the winner's mapping
        and orphan its storage object forever (no surviving entry
        references it, so eviction's refcount check never deletes it).
        No awaits run between the re-check and record()'s entry-map
        mutation, so the decision cannot go stale. Returns True when
        `rel` was recorded; on a lost race the bytes are dropped unless
        another entry owns them, and stats are counted here.

        Whenever the store ends up mapping rel -> sha (recorded here, or
        a lost race to identical bytes), the host is marked seeded for
        `rel`: this host's copy and the store's now agree, so its
        reappearance in later harvest manifests of the same host proves
        no recompile and must not re-touch recency."""
        if self._trust_revoked():
            # Trust revoked between the loop's check and this admission
            # (the entry download awaited the network): the bytes may
            # postdate the revoking tenant run, so they must not enter
            # the store — drop them unless another entry owns them.
            await self.store.drop_unverified(sha)
            return False
        current = self.store.sha_of(rel)
        if current == sha:
            state.seeded.add(rel)
            stats.known_files += 1
            return False
        if current is not None:
            self._note_conflict(base, rel, stats)
            await self.store.drop_unverified(sha)
            return False
        await self.store.record(rel, sha, size)
        state.seeded.add(rel)
        return True

    @staticmethod
    def _note_conflict(base: str, rel: str, stats: HarvestStats) -> None:
        """The single first-write-wins rejection point: both the loop's
        pre-download check and _admit's post-download re-check land here,
        so conflict policy/accounting cannot drift between them."""
        logger.warning(
            "compile-cache harvest conflict: %s offered different bytes "
            "for %s; keeping the store's copy",
            base,
            rel,
        )
        stats.conflicts += 1

    async def _get_entry(
        self, client: httpx.AsyncClient, base: str, rel: str
    ) -> tuple[str, int] | None:
        try:
            async with client.stream(
                "GET", f"{base}/compile-cache/{rel}", timeout=ENTRY_TIMEOUT
            ) as resp:
                if resp.status_code != 200:
                    # Checked BEFORE the writer opens: returning from inside
                    # an open writer context would finalize it and commit a
                    # stray empty object no index entry references.
                    return None
                async with self.store.storage.writer() as writer:
                    async for chunk in resp.aiter_bytes():
                        await writer.write(chunk)
        except httpx.HTTPError:
            # Mid-stream drop: the writer context already unlinked its tmp
            # file — nothing partial reaches the object dir.
            return None
        assert writer.hash is not None
        return writer.hash, writer.size

    async def seed(self, client: httpx.AsyncClient, hosts: list[str]) -> SeedStats:
        total = SeedStats()
        results = await asyncio.gather(
            *(self.seed_host(client, base) for base in hosts),
            return_exceptions=True,
        )
        for result in results:
            if isinstance(result, BaseException):
                logger.warning("compile-cache seed failed: %r", result)
                continue
            total.pushed_files += result.pushed_files
            total.pushed_bytes += result.pushed_bytes
            total.skipped_files += result.skipped_files
            total.skipped_bytes += result.skipped_bytes
        return total

    async def harvest(
        self, client: httpx.AsyncClient, hosts: list[str]
    ) -> HarvestStats:
        total = HarvestStats()
        if not self.store.enabled or self._trust_revoked():
            return total
        # Sequential across a slice group's hosts on purpose: peers of one
        # slice compiled the same kernels, so host 0's harvest makes every
        # peer's entries dedup to known_files instead of racing N identical
        # downloads.
        for base in hosts:
            try:
                result = await self.harvest_host(client, base)
            except Exception:  # noqa: BLE001 — harvest is best-effort
                logger.warning("compile-cache harvest failed", exc_info=True)
                continue
            total.new_files += result.new_files
            total.new_bytes += result.new_bytes
            total.known_files += result.known_files
            total.discarded += result.discarded
            total.conflicts += result.conflicts
        # Persist on ANY entry-map mutation — dedup admissions (new entry
        # name onto already-stored bytes) and evictions mutate state without
        # moving new bytes, and an unsaved index would resurrect deleted
        # objects / lose mappings across a control-plane restart.
        if self.store.dirty:
            self.store.save_index()
        return total


# The pool-fill pre-warm kernel set: the core XLA kernels the `examples/`
# workloads exercise (benchmark-matmul.py's jit matmul, benchmark-numpy.py's
# elementwise/reduction chains), distilled to single-compile snippets so a
# pre-warm costs seconds, not a full benchmark run. Each snippet compiles
# with the sandbox's persistent cache armed, so its executable lands in the
# cache dir and the post-execute harvest admits it to the fleet store.
# These runs are the fleet store's ONLY admission source: they execute as
# trusted (control-plane-authored) code on untainted sandboxes, which is
# what makes their harvest safe to seed into every tenant's sandbox.
PREWARM_SOURCES: list[tuple[str, str]] = [
    (
        "matmul",
        """
import jax, jax.numpy as jnp
f = jax.jit(lambda a, b: a @ b)
x = jnp.ones((256, 256), dtype=jnp.float32)
f(x, x).block_until_ready()
print("prewarm matmul ok")
""",
    ),
    (
        "elementwise",
        """
import jax, jax.numpy as jnp
f = jax.jit(lambda a: jnp.tanh(a) * 2.0 + 1.0)
f(jnp.ones((1024,), dtype=jnp.float32)).block_until_ready()
print("prewarm elementwise ok")
""",
    ),
    (
        "reduction",
        """
import jax, jax.numpy as jnp
f = jax.jit(lambda a: jnp.sum(a, axis=-1))
f(jnp.ones((256, 256), dtype=jnp.float32)).block_until_ready()
print("prewarm reduction ok")
""",
    ),
    (
        # The batched-execution-lanes dispatch shape: shard_map over a
        # 1-axis "jobs" mesh (parallel/mesh.job_mesh's layout), one job's
        # matmul block per device — what a fused multi-chip dispatch
        # compiles. Warm fleet-wide, the first batch of a shape loads from
        # cache instead of eating an XLA compile inside the batching
        # window.
        "batched_dispatch",
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
devs = jax.devices()
mesh = Mesh(np.array(devs), ("jobs",))
f = jax.jit(
    jax.shard_map(
        lambda a, b: a @ b,
        mesh=mesh,
        in_specs=(P("jobs"), P("jobs")),
        out_specs=P("jobs"),
    )
)
n = len(devs)
x = jnp.ones((n * 128, 128), dtype=jnp.float32)
y = jnp.ones((n * 128, 128), dtype=jnp.float32)
f(x, y).block_until_ready()
print("prewarm batched_dispatch ok", n)
""",
    ),
    (
        # A hot small-array shape: a chained 64x64 matmul (the coalesced
        # small-job workload the batching lanes exist for; drawn from a CPU
        # harness's traffic, not from a cell), jitted so the whole chain compiles to
        # ONE cached executable. Fleet coverage scales only with this set
        # (pre-warm is the store's sole admission source), and a cold
        # lane's first burst of small jobs is exactly when an XLA compile
        # inside the batching window hurts most.
        "small_matmul_chain",
        """
import jax, jax.numpy as jnp

@jax.jit
def chain(x, y):
    for _ in range(4):
        x = x @ y
    return x

x = jnp.ones((64, 64), dtype=jnp.float32)
y = jnp.eye(64, dtype=jnp.float32)
chain(x, y).block_until_ready()
print("prewarm small_matmul_chain ok")
""",
    ),
]
