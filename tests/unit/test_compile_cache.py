"""Fleet compile-cache tests: store lifecycle (record/evict/persist/kill
switch), the seed/harvest protocol over an in-memory fake sandbox host
(httpx.MockTransport via the backend's http_transport hook), the legacy
old-binary fallback, the end-to-end control-plane flow (seed at spawn,
harvest at turnover, Result.phases counters), and the seeded-chaos leg
(drops mid-harvest leave no partial objects; kill switch = zero
compile-cache HTTP).
"""

import asyncio
import hashlib
import random
from collections import deque

import httpx
import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.base import Sandbox
from bee_code_interpreter_fs_tpu.services.code_executor import (
    CodeExecutor,
    _trusted_source_var,
)
from bee_code_interpreter_fs_tpu.services.compile_cache import (
    CompileCacheStore,
    HarvestStats,
    SandboxCacheSync,
    valid_entry_name,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage

CHAOS_SEEDS = [7, 23, 1337]


def sha(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


def make_store(tmp_path, **kwargs) -> CompileCacheStore:
    kwargs.setdefault("max_bytes", 1 << 20)
    kwargs.setdefault("max_entries", 64)
    return CompileCacheStore(tmp_path / "cc", **kwargs)


async def admit(store: CompileCacheStore, rel: str, data: bytes) -> str:
    object_id = await store.storage.write(data)
    await store.record(rel, object_id, len(data))
    return object_id


# --------------------------------------------------------------------- store


async def test_store_record_and_manifest(tmp_path):
    store = make_store(tmp_path)
    object_id = await admit(store, "jit_f-abc-cache", b"executable-bytes")
    assert store.manifest() == {"jit_f-abc-cache": object_id}
    assert store.total_bytes() == len(b"executable-bytes")
    assert store.entry_count() == 1


async def test_store_lru_eviction_by_last_hit(tmp_path):
    clock = [0.0]
    store = make_store(tmp_path, max_entries=2, clock=lambda: clock[0])
    await admit(store, "old", b"a" * 10)
    clock[0] = 1.0
    await admit(store, "mid", b"b" * 10)
    clock[0] = 2.0
    store.touch("old")  # refresh: "mid" is now the LRU entry
    clock[0] = 3.0
    await admit(store, "new", b"c" * 10)
    assert set(store.manifest()) == {"old", "new"}
    # The evicted entry's bytes are gone from the object store.
    assert not await store.storage.exists(sha(b"b" * 10))


async def test_store_byte_cap_eviction_keeps_shared_objects(tmp_path):
    clock = [0.0]
    store = make_store(tmp_path, max_bytes=25, clock=lambda: clock[0])
    # Two entries deduping onto identical bytes: evicting one must not
    # delete the other's object.
    await admit(store, "first", b"x" * 10)
    clock[0] = 1.0
    await admit(store, "twin", b"x" * 10)
    clock[0] = 2.0
    await admit(store, "big", b"y" * 10)  # 30 bytes total -> evict "first"
    assert "first" not in store.manifest()
    assert await store.storage.exists(sha(b"x" * 10))


async def test_store_index_persists_across_restart(tmp_path):
    store = make_store(tmp_path)
    object_id = await admit(store, "jit_g-def-cache", b"persisted")
    store.save_index()
    reloaded = make_store(tmp_path)
    assert reloaded.manifest() == {"jit_g-def-cache": object_id}
    assert await reloaded.storage.exists(object_id)


async def test_store_kill_switch_is_inert(tmp_path):
    store = make_store(tmp_path, enabled=False)
    assert store.manifest() == {}
    assert await store.record("x", "0" * 64, 10) == []
    assert store.entry_count() == 0
    # Disabled store creates nothing on disk.
    assert not (tmp_path / "cc").exists()


def test_entry_name_validation():
    assert valid_entry_name("jit_f-abc-cache")
    assert valid_entry_name("nested/ok")
    assert not valid_entry_name("../escape")
    assert not valid_entry_name("/abs")
    assert not valid_entry_name("")
    assert not valid_entry_name("a" * 513)


# ----------------------------------------------------- fake host + protocol


class FakeCacheHost:
    """In-memory executor host speaking the compile-cache protocol (or a
    legacy binary without the routes with ``legacy=True``). ``drop_gets``
    makes entry GETs raise mid-request (the chaos lever). Also answers the
    workspace routes CodeExecutor's request path needs."""

    def __init__(self, legacy: bool = False):
        self.legacy = legacy
        self.cache: dict[str, bytes] = {}
        self.requests: list[str] = []  # "<METHOD> <path>" log, cc routes only
        self.puts: list[str] = []
        self.conditional_hits: list[str] = []
        self.drop_gets = False
        self.drop_decider = None  # callable(rel) -> bool, overrides drop_gets
        self.execute_compile_cache: dict | None = None

    def _log(self, request: httpx.Request) -> None:
        path = request.url.path
        if "compile-cache" in path:
            self.requests.append(f"{request.method} {path}")

    async def handler(self, request: httpx.Request) -> httpx.Response:
        path = request.url.path
        self._log(request)
        if path == "/compile-cache-manifest":
            if self.legacy:
                return httpx.Response(404, json={"error": "no route"})
            return httpx.Response(
                200,
                json={"files": {rel: sha(data) for rel, data in self.cache.items()}},
            )
        if path.startswith("/compile-cache/"):
            rel = path[len("/compile-cache/") :]
            if self.legacy:
                return httpx.Response(404, json={"error": "no route"})
            if request.method == "PUT":
                body = await request.aread()
                cond = request.headers.get("If-None-Match")
                if cond and rel in self.cache and sha(self.cache[rel]) == cond:
                    self.conditional_hits.append(rel)
                    return httpx.Response(304)
                self.cache[rel] = body
                self.puts.append(rel)
                return httpx.Response(
                    200, json={"path": path, "sha256": sha(body), "size": len(body)}
                )
            if request.method == "GET":
                if rel not in self.cache:
                    return httpx.Response(404, json={"error": "not found"})
                dropper = self.drop_decider
                if self.drop_gets or (dropper is not None and dropper(rel)):
                    raise httpx.ReadError("connection dropped mid-entry")
                return httpx.Response(200, content=self.cache[rel])
        if request.method == "POST" and path == "/execute":
            body = {
                "stdout": "ok\n",
                "stderr": "",
                "exit_code": 0,
                "files": [],
                "deleted": [],
                "warm": True,
                "runner_restarted": False,
            }
            if self.execute_compile_cache is not None:
                body["compile_cache"] = self.execute_compile_cache
            return httpx.Response(200, json=body)
        if request.method == "POST" and path == "/reset":
            # Generation turnover never wipes the compile-cache dir.
            return httpx.Response(200, json={"ok": True})
        if request.method == "GET" and path == "/workspace-manifest":
            return httpx.Response(200, json={"files": {}})
        return httpx.Response(404, json={"error": "no route"})

    def transport(self) -> httpx.MockTransport:
        return httpx.MockTransport(self.handler)


def make_sync(tmp_path, host, **store_kwargs):
    store = make_store(tmp_path, **store_kwargs)
    sync = SandboxCacheSync(store)
    client = httpx.AsyncClient(transport=host.transport())
    return store, sync, client


async def test_seed_pushes_only_missing_entries(tmp_path):
    host = FakeCacheHost()
    host.cache["already-there"] = b"present"
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "already-there", b"present")
    await admit(store, "missing", b"new-kernel")
    stats = await sync.seed(client, ["http://host-a"])
    assert host.puts == ["missing"]
    assert host.cache["missing"] == b"new-kernel"
    assert stats.pushed_files == 1
    assert stats.pushed_bytes == len(b"new-kernel")
    assert stats.skipped_files == 1
    await client.aclose()


async def test_seed_second_round_moves_nothing(tmp_path):
    host = FakeCacheHost()
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "kernel", b"bytes")
    await sync.seed(client, ["http://host-a"])
    first_round = list(host.requests)
    stats = await sync.seed(client, ["http://host-a"])
    # Round 2: one manifest GET, zero PUTs — unchanged entries never cross
    # the wire twice.
    assert host.requests[len(first_round) :] == [
        "GET /compile-cache-manifest"
    ]
    assert stats.pushed_files == 0 and stats.skipped_files == 1
    await client.aclose()


async def test_legacy_host_probed_exactly_once(tmp_path):
    host = FakeCacheHost(legacy=True)
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "kernel", b"bytes")
    await sync.seed(client, ["http://host-a"])
    await sync.harvest(client, ["http://host-a"])
    await sync.seed(client, ["http://host-a"])
    # One manifest GET proved the host legacy; nothing afterwards.
    assert host.requests == ["GET /compile-cache-manifest"]
    await client.aclose()


async def test_harvest_pulls_new_entries_and_skips_known(tmp_path):
    host = FakeCacheHost()
    host.cache["known"] = b"old-kernel"
    host.cache["fresh"] = b"new-kernel"
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "known", b"old-kernel")
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.new_files == 1
    assert stats.known_files == 1
    assert store.manifest()["fresh"] == sha(b"new-kernel")
    assert await store.storage.read(sha(b"new-kernel")) == b"new-kernel"
    # Only the fresh entry was downloaded.
    assert "GET /compile-cache/fresh" in host.requests
    assert "GET /compile-cache/known" not in host.requests
    await client.aclose()


async def test_harvest_dedups_identical_bytes_under_new_name(tmp_path):
    host = FakeCacheHost()
    host.cache["same-bytes-new-name"] = b"shared-executable"
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "original-name", b"shared-executable")
    stats = await sync.harvest(client, ["http://host-a"])
    # The bytes were already stored: the mapping records without a GET.
    assert stats.known_files == 2 or (
        stats.known_files == 1 and stats.new_files == 0
    )
    assert "GET /compile-cache/same-bytes-new-name" not in host.requests
    assert store.manifest()["same-bytes-new-name"] == sha(b"shared-executable")
    await client.aclose()


async def test_harvest_drop_leaves_no_partial_objects(tmp_path):
    host = FakeCacheHost()
    host.cache["doomed"] = b"never-arrives"
    host.drop_gets = True
    store, sync, client = make_sync(tmp_path, host)
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.new_files == 0
    assert store.manifest() == {}
    # No partial objects, no tmp leftovers.
    objects = [
        p
        for p in (store.path / "objects").rglob("*")
        if p.is_file()
    ]
    assert objects == []
    await client.aclose()


async def test_tainted_sync_means_zero_harvest_http(tmp_path):
    """A sandbox that ran tenant code gets no harvest traffic at all — not
    even the manifest probe: its cache dir is attacker-writable and nothing
    in it may be admitted."""
    host = FakeCacheHost()
    host.cache["jit_evil-cache"] = b"attacker-controlled"
    store, sync, client = make_sync(tmp_path, host)
    sync.taint()
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.new_files == 0
    assert store.manifest() == {}
    assert host.requests == []
    # Seeding still works: pushing trusted store bytes INTO a tainted
    # sandbox is safe (and is how it gets its warm start).
    await admit(store, "hot", b"fleet-kernel")
    seed_stats = await sync.seed(client, ["http://host-a"])
    assert seed_stats.pushed_files == 1
    await client.aclose()


async def test_harvest_never_overwrites_existing_entry(tmp_path):
    """First-write-wins: a host presenting DIFFERENT bytes under an entry
    name the store already maps is a conflict — the store's copy stays, the
    impostor's bytes never move."""
    host = FakeCacheHost()
    host.cache["jit_popular-cache"] = b"impostor-executable"
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "jit_popular-cache", b"canonical-executable")
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.conflicts == 1
    assert stats.new_files == 0
    assert store.manifest()["jit_popular-cache"] == sha(
        b"canonical-executable"
    )
    # The impostor's bytes were never even downloaded, let alone stored.
    assert "GET /compile-cache/jit_popular-cache" not in host.requests
    assert not await store.storage.exists(sha(b"impostor-executable"))
    await client.aclose()


async def test_harvest_persists_index_on_dedup_admission(tmp_path):
    """record() on the dedup path (new entry name onto already-stored
    bytes) must survive a control-plane restart even though new_files == 0
    for the harvest round."""
    host = FakeCacheHost()
    host.cache["twin-name"] = b"shared-executable"
    store, sync, client = make_sync(tmp_path, host)
    await admit(store, "original-name", b"shared-executable")
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.new_files == 0  # nothing moved — pure dedup mapping
    reloaded = make_store(tmp_path)
    assert reloaded.manifest().get("twin-name") == sha(b"shared-executable")
    await client.aclose()


async def test_harvest_persists_index_after_eviction(tmp_path):
    """Eviction deletes storage objects; the reloaded index must not
    reference them after a restart mid-stream of harvests."""
    host = FakeCacheHost()
    host.cache["jit_big-cache"] = b"n" * 30
    store, sync, client = make_sync(tmp_path, host, max_bytes=40)
    await admit(store, "jit_old-cache", b"o" * 20)
    store.save_index()
    await sync.harvest(client, ["http://host-a"])  # evicts jit_old-cache
    assert "jit_old-cache" not in store.manifest()
    reloaded = make_store(tmp_path, max_bytes=40)
    assert set(reloaded.manifest()) == {"jit_big-cache"}
    for object_id in reloaded.manifest().values():
        assert await reloaded.storage.exists(object_id)
    await client.aclose()


async def test_harvest_reobservation_refreshes_recency(tmp_path):
    """A trusted run presenting an entry this host was NEVER seeded
    (known_sha == sha, rel not in state.seeded) is evidence of a real
    recompile: its last_hit refreshes, and the refresh persists across a
    control-plane restart."""
    host = FakeCacheHost()
    clock = [0.0]
    store, sync, client = make_sync(
        tmp_path, host, max_entries=2, clock=lambda: clock[0]
    )
    await admit(store, "aging", b"aging-kernel")
    clock[0] = 1.0
    await admit(store, "refreshed", b"refreshed-kernel")
    host.cache["refreshed"] = b"refreshed-kernel"
    clock[0] = 2.0
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.known_files == 1
    clock[0] = 3.0
    await admit(store, "newcomer", b"newcomer-kernel")
    # "aging" (last_hit 0.0) evicts, not "refreshed" (touched to 2.0).
    assert set(store.manifest()) == {"refreshed", "newcomer"}
    # The touch was persisted by harvest (dirty-flag save), so a restarted
    # control plane keeps the refreshed recency, not the admission time.
    reloaded = make_store(tmp_path, max_entries=2, clock=lambda: clock[0])
    assert reloaded._entries["refreshed"].last_hit == 2.0
    await client.aclose()


async def test_harvest_never_touches_entries_it_seeded(tmp_path):
    """Seeded entries reappear in every harvest manifest, so their
    re-observation proves nothing: touching them would refresh the whole
    hot set each pre-warm and flatten the LRU signal to nothing. Recency
    stays at admission time for entries the control plane pushed itself."""
    host = FakeCacheHost()
    clock = [0.0]
    store, sync, client = make_sync(tmp_path, host, clock=lambda: clock[0])
    await admit(store, "seeded-kernel", b"seeded-bytes")
    clock[0] = 1.0
    seed_stats = await sync.seed(client, ["http://host-a"])
    assert seed_stats.pushed_files == 1
    clock[0] = 2.0
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.known_files == 1
    assert store._entries["seeded-kernel"].last_hit == 0.0  # admission time
    assert store._entries["seeded-kernel"].hits == 1
    await client.aclose()


async def test_reobservation_touches_recency_only_once(tmp_path):
    """Known-entry re-observation is evidence of ONE recompile, not many:
    the cache dir outlives /reset, so the same entries reappear in every
    later harvest manifest of a long-lived untainted host. Only the first
    observation refreshes recency; repeats — and entries the harvest
    itself admitted — are silent, or mere persistence would re-touch
    indefinitely and flatten the LRU signal."""
    host = FakeCacheHost()
    clock = [0.0]
    store, sync, client = make_sync(tmp_path, host, clock=lambda: clock[0])
    host.cache["jit_organic-cache"] = b"organic-kernel"
    stats = await sync.harvest(client, ["http://host-a"])  # admitted at t=0
    assert stats.new_files == 1
    clock[0] = 5.0
    await sync.harvest(client, ["http://host-a"])  # re-presented: no recompile
    entry = store._entries["jit_organic-cache"]
    assert entry.last_hit == 0.0  # admission time, not 5.0
    assert entry.hits == 1
    # An entry already in the store (another host's harvest) observed on
    # THIS host refreshes once — the first sighting — never again.
    await admit(store, "jit_other-cache", b"other-kernel")  # t=5
    host.cache["jit_other-cache"] = b"other-kernel"
    clock[0] = 7.0
    await sync.harvest(client, ["http://host-a"])  # first sighting: touch
    clock[0] = 9.0
    await sync.harvest(client, ["http://host-a"])  # repeat: silent
    assert store._entries["jit_other-cache"].last_hit == 7.0
    await client.aclose()


async def test_admit_rechecks_store_after_download_race(tmp_path):
    """First-write-wins must hold across harvest's network awaits: two
    sandboxes' turnover harvests can race the same entry name (e.g. a
    nondeterministic recompile on two untainted sandboxes), both passing
    the loop's conflict check before either records. The loser's final
    admission re-check routes to the conflict path and drops its bytes —
    no silent replacement, no orphaned storage object."""
    host = FakeCacheHost()
    store, sync, client = make_sync(tmp_path, host)
    stats = HarvestStats()
    # Simulate the race: a competing harvest admitted different bytes for
    # this entry name while "our" harvest was downloading its copy.
    winner = await admit(store, "jit_raced-cache", b"winner-bytes")
    loser_sha = await store.storage.write(b"loser-bytes")
    admitted = await sync._admit(
        "http://host-b",
        "jit_raced-cache",
        loser_sha,
        11,
        stats,
        sync.host("http://host-b"),
    )
    assert not admitted
    assert stats.conflicts == 1
    assert store.manifest()["jit_raced-cache"] == winner
    # The loser's bytes were dropped, not left as an orphan no entry
    # references (eviction's refcount check would never delete it).
    assert not await store.storage.exists(loser_sha)
    await client.aclose()


async def test_harvest_hash_mismatch_discarded(tmp_path):
    host = FakeCacheHost()
    host.cache["liar"] = b"promised-content"

    real_handler = host.handler

    async def lying_handler(request: httpx.Request) -> httpx.Response:
        if request.method == "GET" and request.url.path.endswith("/liar"):
            host._log(request)
            return httpx.Response(200, content=b"DIFFERENT-content")
        return await real_handler(request)

    store = make_store(tmp_path)
    sync = SandboxCacheSync(store)
    client = httpx.AsyncClient(transport=httpx.MockTransport(lying_handler))
    stats = await sync.harvest(client, ["http://host-a"])
    assert stats.discarded == 1
    assert stats.new_files == 0
    assert store.manifest() == {}
    # Neither identity survived: not the promised sha, not the actual one.
    assert not await store.storage.exists(sha(b"promised-content"))
    assert not await store.storage.exists(sha(b"DIFFERENT-content"))
    await client.aclose()


# ------------------------------------------------- CodeExecutor integration


class CacheBackend(FakeBackend):
    """FakeBackend whose sandbox HTTP lands on one FakeCacheHost."""

    def __init__(self, host: FakeCacheHost, **kwargs):
        super().__init__(**kwargs)
        self.fake_host = host

    def http_transport(self):
        return self.fake_host.transport()


def make_stack(tmp_path, legacy=False, **config_kwargs):
    host = FakeCacheHost(legacy=legacy)
    backend = CacheBackend(host)
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        executor_pod_queue_target_length=1,
        **config_kwargs,
    )
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    return executor, host, backend


async def settle(executor):
    for _ in range(3):
        await asyncio.sleep(0)
    tasks = list(executor._dispose_tasks) + list(executor._fill_tasks)
    if tasks:
        await asyncio.gather(*tasks, return_exceptions=True)


async def test_spawn_seeds_tenant_sandbox_but_never_harvests_it(tmp_path):
    """Tenant code gets the hot set seeded in, but nothing a tenant
    sandbox's cache dir holds ever enters the fleet store: user code can
    write arbitrary bytes there, and a harvested entry is a serialized
    executable every other tenant's seeded sandbox would deserialize and
    run. Taint closes the channel with zero harvest HTTP."""
    executor, host, backend = make_stack(tmp_path)
    try:
        await admit(executor.compile_cache, "hot-kernel", b"hot-bytes")
        host.cache["compiled-here"] = b"organic-kernel"
        result = await executor.execute("print('hi')")
        assert result.exit_code == 0
        # Seed at spawn pushed the hot set into the sandbox...
        assert host.cache["hot-kernel"] == b"hot-bytes"
        # ...and the seeding cost rides the first request's phases.
        assert result.phases["compile_cache_seeded_bytes"] == float(
            len(b"hot-bytes")
        )
        await settle(executor)
        # Turnover did NOT harvest the tenant sandbox — the entry stayed
        # out of the store and no entry bytes moved store-ward.
        assert "compiled-here" not in executor.compile_cache.manifest()
        assert not any(
            r.startswith("GET /compile-cache/") for r in host.requests
        )
    finally:
        await executor.close()


async def test_trusted_prewarm_run_is_harvested(tmp_path):
    """Control-plane-authored code (the pre-warm path) leaves its sandbox
    untainted — turnover harvest admits what it compiled. This is the fleet
    store's only admission source."""
    executor, host, backend = make_stack(tmp_path)
    try:
        host.cache["jit_prewarmed-cache"] = b"trusted-kernel"
        result = await executor._execute_trusted("print('prewarm')")
        assert result.exit_code == 0
        await settle(executor)
        assert executor.compile_cache.manifest()["jit_prewarmed-cache"] == sha(
            b"trusted-kernel"
        )
    finally:
        await executor.close()


async def test_taint_outlives_recycle_into_trusted_run(tmp_path):
    """Once tenant code ran on a sandbox, even a LATER trusted run on the
    recycled sandbox must not re-qualify it: the cache dir survives /reset,
    so whatever the tenant planted is still there."""
    executor, host, backend = make_stack(tmp_path)
    try:
        first = await executor.execute("print('tenant')")
        assert first.exit_code == 0
        await settle(executor)
        host.cache["planted-by-tenant"] = b"attacker-bytes"
        second = await executor._execute_trusted("print('prewarm')")
        assert second.exit_code == 0
        await settle(executor)
        # Same recycled sandbox (reuse on, pool of 1): still tainted.
        assert backend.spawns == 1
        assert "planted-by-tenant" not in executor.compile_cache.manifest()
    finally:
        await executor.close()


async def test_trusted_pop_prefers_untainted_sandbox(tmp_path):
    """Pre-warm runs exist to produce harvestable artifacts, and a tainted
    sandbox is harvest-ineligible for life — so a trusted acquire skips
    tainted pooled sandboxes when an untainted one is available, but still
    takes a tainted one rather than stalling (livelock on a constrained
    lane would be worse; the pre-warm pass detects and retries instead)."""
    executor, host, backend = make_stack(tmp_path)
    try:
        tainted = Sandbox(id="tainted", url="http://fake")
        fresh = Sandbox(id="fresh", url="http://fake")
        executor._cache_sync(tainted).taint()
        # Tenant requests take the leftmost sandbox regardless of taint.
        pool = deque([tainted, fresh])
        assert executor._pop_pool_sandbox(pool) is tainted
        pool = deque([tainted, fresh])
        token = _trusted_source_var.set(True)
        try:
            assert executor._pop_pool_sandbox(pool) is fresh
            assert executor._pop_pool_sandbox(pool) is tainted  # fallback
        finally:
            _trusted_source_var.reset(token)
    finally:
        await executor.close()


async def test_prewarm_retries_ineffective_pass(tmp_path):
    """A pre-warm pass whose kernels all ran yet admitted NOTHING (in
    production: every run landed on tainted recycled sandboxes, or harvest
    HTTP failed) is retried after a backoff — prewarm is the store's only
    admission source, so giving up on the first dud would leave the fleet
    store empty for the deployment's lifetime."""
    executor, host, backend = make_stack(tmp_path)
    executor._PREWARM_BACKOFF_SECONDS = 0.0
    host.cache["jit_prewarmed-cache"] = b"trusted-kernel"
    attempts = []

    def drop_first_pass(rel):
        attempts.append(rel)
        # One harvest per kernel release, three kernels per pass: dropping
        # the first three GETs makes the whole first pass admit nothing.
        return len(attempts) <= 3

    host.drop_decider = drop_first_pass
    try:
        await executor._prewarm_compile_cache()
        await settle(executor)
        assert len(attempts) > 3  # a second pass actually ran
        assert executor.compile_cache.manifest()["jit_prewarmed-cache"] == sha(
            b"trusted-kernel"
        )
    finally:
        await executor.close()


async def test_prewarm_gives_up_bounded_with_only_tainted_sandboxes(tmp_path):
    """Pool of one with reuse on and the sandbox tenant-tainted: every
    pre-warm pass lands on the same harvest-ineligible sandbox. The retry
    loop must terminate (bounded passes) rather than spin forever, leaving
    the store empty and a warning behind."""
    executor, host, backend = make_stack(tmp_path)
    executor._PREWARM_BACKOFF_SECONDS = 0.0
    executor._PREWARM_MAX_PASSES = 2
    try:
        first = await executor.execute("print('tenant')")
        assert first.exit_code == 0
        await settle(executor)
        host.cache["jit_prewarmed-cache"] = b"trusted-kernel"
        await executor._prewarm_compile_cache()
        await settle(executor)
        assert executor.compile_cache.entry_count() == 0
        assert backend.spawns == 1  # every pass recycled the tainted sandbox
    finally:
        await executor.close()


async def test_external_cache_dir_disables_harvest(tmp_path):
    """A backend declaring its cache dir externally writable (k8s with a
    shared PVC/hostPath volume source) makes the dir writable by OTHER
    pods' tenants, so per-sandbox taint can't vouch for an 'untainted'
    sandbox's dir: even a trusted run is never harvested. Seeding still
    works — the store only ever holds trusted bytes."""
    executor, host, backend = make_stack(tmp_path)
    backend.compile_cache_dir_scope = "external"
    try:
        await admit(executor.compile_cache, "hot", b"fleet-kernel")
        host.cache["planted-via-shared-volume"] = b"other-pods-tenant-bytes"
        result = await executor._execute_trusted("print('prewarm')")
        assert result.exit_code == 0
        # Seeding is unaffected: the store only ever holds trusted bytes.
        assert result.phases["compile_cache_seeded_bytes"] > 0
        await settle(executor)
        # Even the TRUSTED run was not harvested: the planted entry never
        # entered the store, and no entry bytes ever moved store-ward
        # (seeding GETs only the manifest, never entries).
        assert "planted-via-shared-volume" not in (
            executor.compile_cache.manifest()
        )
        assert not any(
            r.startswith("GET /compile-cache/") for r in host.requests
        )
    finally:
        await executor.close()


async def test_shared_cache_dir_tenant_run_ends_harvest_fleet_wide(tmp_path):
    """Shared-dir scope (the local backend's default: every sandbox serves
    the SAME host cache dir): per-sandbox taint can't vouch for the dir,
    because tenant code in sandbox A writes entries that sandbox B's
    manifest then presents as its own. The first tenant execute must
    therefore end harvesting control-plane-wide — even a LATER trusted run
    on a genuinely fresh, per-sandbox-untainted sandbox is refused."""
    executor, host, backend = make_stack(tmp_path)
    backend.compile_cache_dir_scope = "shared"
    backend.resettable = False  # every run gets a genuinely fresh sandbox
    try:
        # Trusted-only epoch: harvest admits normally.
        host.cache["jit_epoch-cache"] = b"trusted-kernel"
        first = await executor._execute_trusted("print('prewarm')")
        assert first.exit_code == 0
        await settle(executor)
        assert executor.compile_cache.manifest()["jit_epoch-cache"] == sha(
            b"trusted-kernel"
        )
        # One tenant run anywhere taints the shared dir for life.
        tenant = await executor.execute("print('tenant')")
        assert tenant.exit_code == 0
        await settle(executor)
        # A later trusted run lands on a FRESH sandbox (untainted by the
        # per-sandbox rule) — the shared-dir taint must still refuse it:
        # its manifest lists whatever the tenant planted in the shared dir.
        host.cache["jit_planted-cache"] = b"tenant-planted-bytes"
        later = await executor._execute_trusted("print('prewarm again')")
        assert later.exit_code == 0
        await settle(executor)
        assert backend.spawns >= 3  # the runs really used distinct sandboxes
        assert "jit_planted-cache" not in executor.compile_cache.manifest()
    finally:
        await executor.close()


async def test_shared_taint_landing_mid_harvest_blocks_admission(tmp_path):
    """The shared-dir gate is not a one-shot entry check: the revoking
    tenant run happens on a DIFFERENT sandbox, so it can land while this
    sandbox's harvest is awaiting an entry download. The admission path
    re-checks trust after every network await — bytes fetched across the
    revocation are dropped, never recorded, and leave no orphan object."""
    executor, host, backend = make_stack(tmp_path)
    backend.compile_cache_dir_scope = "shared"
    host.cache["jit_racy-cache"] = b"tenant-racy-bytes"
    sandbox = Sandbox(id="sb-race", url="http://fake")
    sync = executor._cache_sync(sandbox)

    def flip_taint_during_entry_get(rel):
        # Runs inside the entry GET — after the harvest loop's own trust
        # check passed. Models the first tenant execute starting on a
        # sibling sandbox mid-download.
        executor._shared_cache_tainted = True
        return False  # don't drop the request; deliver the bytes

    host.drop_decider = flip_taint_during_entry_get
    try:
        stats = await sync.harvest(executor._http_client(), ["http://fake"])
        assert stats.new_files == 0
        assert "jit_racy-cache" not in executor.compile_cache.manifest()
        # The downloaded bytes were dropped, not left as an orphan object.
        assert not await executor.compile_cache.storage.exists(
            sha(b"tenant-racy-bytes")
        )
    finally:
        await executor.close()


async def test_prewarm_skipped_on_external_cache_dir(tmp_path):
    """With harvest structurally off (externally writable cache dir), a
    pre-warm pass could never admit anything — it must not start at all,
    rather than burn executes and then warn about an empty store."""
    executor, host, backend = make_stack(tmp_path)
    backend.compile_cache_dir_scope = "external"
    try:
        assert executor.start_compile_cache_prewarm() is None
        assert backend.spawns == 0  # no pass ran
    finally:
        await executor.close()


async def test_prewarm_stops_once_shared_dir_tainted(tmp_path):
    """Shared-dir scope with tenant code already run: the control-plane
    -wide taint is permanent, so the pre-warm retry loop must stop
    immediately instead of burning its bounded passes on sandboxes whose
    harvest is refused by construction."""
    executor, host, backend = make_stack(tmp_path)
    backend.compile_cache_dir_scope = "shared"
    executor._PREWARM_BACKOFF_SECONDS = 0.0
    try:
        tenant = await executor.execute("print('tenant')")
        assert tenant.exit_code == 0
        await settle(executor)
        host.cache["jit_prewarmed-cache"] = b"trusted-kernel"
        await executor._prewarm_compile_cache()
        await settle(executor)
        assert executor.compile_cache.entry_count() == 0
        assert backend.spawns == 1  # no pre-warm pass ever executed
    finally:
        await executor.close()


async def test_local_backend_shared_dir_trusted_epoch(tmp_path):
    """Local backend, shared-dir mode: a dir surviving a previous
    control-plane lifetime could hold that lifetime's tenant writes, which
    this lifetime's trusted-only epoch would then harvest as its own — so a
    shared dir that is NOT EMPTY at start is never harvested ("external").
    The dir itself is never wiped: it may be the caller's
    JAX_COMPILATION_CACHE_DIR. Per-sandbox dirs are private regardless."""
    from bee_code_interpreter_fs_tpu.services.backends.local import (
        LocalSandboxBackend,
    )

    def make_local(subdir, stale=True, **overrides):
        cache = tmp_path / subdir / "shared-cache"
        cache.mkdir(parents=True)
        if stale:
            (cache / "jit_stale-cache").write_bytes(b"last-epoch-tenant-bytes")
        config = Config(
            local_sandbox_root=str(tmp_path / subdir / "sb"),
            file_storage_path=str(tmp_path / subdir / "storage"),
            jax_compilation_cache_dir=str(cache),
            **overrides,
        )
        return cache, LocalSandboxBackend(config, warm_import_jax=False)

    cache, backend = make_local("stale")
    assert backend.compile_cache_dir_scope == "external"
    assert (cache / "jit_stale-cache").exists()  # handed in, never wiped

    cache, backend = make_local("empty", stale=False)
    assert backend.compile_cache_dir_scope == "shared"  # trusted epoch

    cache, backend = make_local("private", compile_cache_per_sandbox=True)
    assert backend.compile_cache_dir_scope == "private"
    assert (cache / "jit_stale-cache").exists()


async def test_execute_surfaces_hit_miss_phases(tmp_path):
    executor, host, backend = make_stack(tmp_path)
    try:
        host.execute_compile_cache = {
            "hits": 3,
            "misses": 1,
            "new_entries": 1,
            "new_bytes": 2048,
        }
        result = await executor.execute("print('hi')")
        assert result.phases["compile_cache_hits"] == 3.0
        assert result.phases["compile_cache_misses"] == 1.0
        assert result.phases["compile_cache_new_bytes"] == 2048.0
    finally:
        await executor.close()


async def test_kill_switch_means_zero_compile_cache_http(tmp_path):
    executor, host, backend = make_stack(
        tmp_path, compile_cache_enabled=False
    )
    try:
        result = await executor.execute("print('hi')")
        assert result.exit_code == 0
        await settle(executor)
        assert host.requests == []  # no cc routes touched, ever
        assert "compile_cache_hits" not in result.phases
        assert "compile_cache_seeded_bytes" not in result.phases
    finally:
        await executor.close()


async def test_legacy_executor_fallback_in_full_flow(tmp_path):
    """A fleet on an old binary (no cc endpoints) behaves exactly as before
    the cache existed: one probe per host, requests unharmed."""
    executor, host, backend = make_stack(tmp_path, legacy=True)
    try:
        await admit(executor.compile_cache, "hot-kernel", b"hot-bytes")
        result = await executor.execute("print('hi')")
        assert result.exit_code == 0
        await settle(executor)
        probes = [r for r in host.requests if r == "GET /compile-cache-manifest"]
        assert len(probes) == 1
        assert len(host.requests) == 1
    finally:
        await executor.close()


# ------------------------------------------------------------------- chaos


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
async def test_seeded_chaos_harvest_integrity(tmp_path, seed):
    """Seeded drops mid-harvest: whatever subset survives, every stored
    object verifies against its content hash (no partial or mislabeled
    objects) and the index never references bytes the store lacks."""
    rng = random.Random(seed)
    host = FakeCacheHost()
    for i in range(12):
        host.cache[f"jit_k{i}-cache"] = bytes([i]) * (50 + i)
    host.drop_decider = lambda rel: rng.random() < 0.5
    store = make_store(tmp_path)
    sync = SandboxCacheSync(store)
    client = httpx.AsyncClient(transport=host.transport())
    for _ in range(3):  # several harvest rounds, drops resampled each time
        await sync.harvest(client, ["http://host-a"])
    manifest = store.manifest()
    for rel, object_id in manifest.items():
        data = await store.storage.read(object_id)
        assert sha(data) == object_id, f"corrupt object for {rel}"
        assert data == host.cache[rel]
    # Nothing beyond the verified objects + index lives in the store dir.
    object_files = {
        p.name for p in (store.path / "objects").iterdir() if p.is_file()
    }
    assert object_files == set(manifest.values())
    await client.aclose()


@pytest.mark.parametrize("seed", CHAOS_SEEDS)
async def test_seeded_chaos_disabled_is_pre_cache_exact(tmp_path, seed):
    """Cache disabled under the same chaos plan: byte-for-byte pre-cache
    behavior — zero compile-cache requests regardless of faults."""
    rng = random.Random(seed)
    host = FakeCacheHost()
    host.drop_decider = lambda rel: rng.random() < 0.5
    executor, host2, backend = make_stack(
        tmp_path, compile_cache_enabled=False
    )
    try:
        for _ in range(3):
            result = await executor.execute("print('x')")
            assert result.exit_code == 0
        await settle(executor)
        assert host2.requests == []
    finally:
        await executor.close()
