"""End-to-end fleet compile cache through the real local backend + C++
executor: a kernel compiled by one TRUSTED (pre-warm-style) sandbox run is
harvested into the fleet store at that sandbox's teardown and seeded into a
FRESH sandbox before its user code runs — with the first sandbox already
disposed. Per-sandbox cache dirs + reuse off reproduce the Kubernetes
pod-local reality where the fleet store is the ONLY cross-sandbox channel.

Harvest is provenance-gated: only control-plane-authored runs (driven here
via executor._execute_trusted, the pre-warm mechanism) are harvestable;
tenant executes taint their sandbox and nothing it holds ever enters the
fleet store — covered by its own leg below.

The fast legs use a synthetic cache entry (code writing into
$JAX_COMPILATION_CACHE_DIR stands in for XLA's cache writer — byte-for-byte
the same protocol surface). The slow leg compiles a real jitted kernel and
proves zero recompilation via the runner's jax.monitoring hit counter.
"""

# Optional-dep guard: a missing dependency must degrade this module to a
# SKIP at collection, not an ERROR that interrupts the whole run.
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")

import asyncio  # noqa: E402

from bee_code_interpreter_fs_tpu.config import Config  # noqa: E402
from bee_code_interpreter_fs_tpu.services.backends.local import (  # noqa: E402
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import (  # noqa: E402
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage  # noqa: E402

WRITE_ENTRY = """
import os
d = os.environ["JAX_COMPILATION_CACHE_DIR"]
path = os.path.join(d, "jit_popular_kernel-e2e-cache")
existed = os.path.exists(path)
if not existed:
    open(path, "wb").write(b"compiled-executable-bytes" * 10)
print("hit" if existed else "miss")
"""


def make_stack(tmp_path, *, warm_import_jax=False, **config_overrides):
    defaults = dict(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        # No warm pool: every execute spawns (and disposes) its own
        # sandbox, so seed-at-spawn and harvest-at-teardown interleave
        # deterministically (a pooled replacement would race the harvest).
        executor_pod_queue_target_length=0,
        jax_compilation_cache_dir=str(tmp_path / "unused-shared-cache"),
        compile_cache_per_sandbox=True,  # pod-local reality
        executor_reuse_sandboxes=False,  # every execute = a fresh sandbox
        default_execution_timeout=60.0,
    )
    defaults.update(config_overrides)
    config = Config(**defaults)
    backend = LocalSandboxBackend(config, warm_import_jax=warm_import_jax)
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    return executor, backend


async def _settle(executor):
    for _ in range(200):
        pending = list(executor._dispose_tasks) + list(executor._fill_tasks)
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


@pytest.mark.parametrize("fresh_sandboxes", [1, 2])
async def test_disposed_sandboxs_kernel_reused_by_fresh_sandbox(
    tmp_path, fresh_sandboxes
):
    executor, backend = make_stack(tmp_path)
    try:
        # The compiling run is control-plane-authored (the pre-warm
        # mechanism) — the only provenance harvest admits.
        first = await executor._execute_trusted(WRITE_ENTRY)
        assert first.exit_code == 0, first.stderr
        assert first.stdout.strip() == "miss"  # sandbox 1 had to "compile"
        # The prime run is where the fleet paid its one compile.
        assert first.phases["compile_cache_new_bytes"] > 0
        await _settle(executor)
        # Sandbox 1 is gone (reuse off => disposed) and its kernel was
        # harvested into the fleet store at teardown.
        assert backend._procs == {}
        manifest = executor.compile_cache.manifest()
        assert "jit_popular_kernel-e2e-cache" in manifest

        for _ in range(fresh_sandboxes):
            later = await executor.execute(WRITE_ENTRY)
            assert later.exit_code == 0, later.stderr
            # THE acceptance criterion: every fresh TENANT sandbox found the
            # kernel already in its cache dir — seeded at spawn from the
            # fleet store, zero recompilation: nothing new in its cache.
            assert later.stdout.strip() == "hit"
            assert later.phases["compile_cache_seeded_bytes"] > 0
            assert later.phases.get("compile_cache_new_bytes", 0) == 0
            await _settle(executor)
            assert backend._procs == {}
    finally:
        await executor.close()


async def test_tenant_compiled_entry_never_reaches_other_sandboxes(tmp_path):
    """The cache-poisoning regression: a TENANT run that writes into its
    cache dir is never harvested — the fleet store stays empty and a fresh
    sandbox sees a cold cache (no cross-tenant executable channel)."""
    executor, backend = make_stack(tmp_path)
    try:
        first = await executor.execute(WRITE_ENTRY)
        assert first.exit_code == 0, first.stderr
        assert first.stdout.strip() == "miss"
        await _settle(executor)
        assert backend._procs == {}
        assert executor.compile_cache.manifest() == {}

        second = await executor.execute(WRITE_ENTRY)
        assert second.exit_code == 0, second.stderr
        # The next tenant's sandbox was NOT seeded with the first tenant's
        # planted entry.
        assert second.stdout.strip() == "miss"
        await _settle(executor)
    finally:
        await executor.close()


async def test_kill_switch_restores_pre_cache_behavior(tmp_path):
    executor, backend = make_stack(tmp_path, compile_cache_enabled=False)
    try:
        # Even a trusted run moves nothing with the switch off.
        first = await executor._execute_trusted(WRITE_ENTRY)
        assert first.exit_code == 0, first.stderr
        assert first.stdout.strip() == "miss"
        await _settle(executor)
        assert executor.compile_cache.manifest() == {}

        second = await executor.execute(WRITE_ENTRY)
        assert second.exit_code == 0, second.stderr
        # No fleet cache: the fresh sandbox recompiles, exactly as before.
        assert second.stdout.strip() == "miss"
        # Nothing reports cache traffic: no seeding, no hit.
        assert "compile_cache_seeded_bytes" not in second.phases
        assert second.phases.get("compile_cache_hits", 0) == 0
        await _settle(executor)
    finally:
        await executor.close()


async def test_harvest_and_seed_counters_move(tmp_path):
    executor, backend = make_stack(tmp_path)
    try:
        first = await executor._execute_trusted(WRITE_ENTRY)
        assert first.exit_code == 0
        # The executor reported the new cache entry on the execute itself.
        assert first.phases.get("compile_cache_new_bytes", 0) > 0
        await _settle(executor)
        render = executor.metrics.registry.render()
        assert (
            'code_interpreter_compile_cache_bytes_total{direction="harvest"}'
            in render
        )
        second = await executor.execute("print('warm')")
        await _settle(executor)
        assert (
            'code_interpreter_compile_cache_bytes_total{direction="seed"}'
            in render or second.phases.get("compile_cache_seeded_bytes", 0) > 0
        )
    finally:
        await executor.close()


@pytest.mark.slow
async def test_real_jit_kernel_zero_recompilation(tmp_path):
    """The full story with a real XLA compile: sandbox 1 jits a matmul
    (persistent cache write), dies; its local cache dir is wiped (modeling
    the next pod's empty emptyDir — sandbox 1 AND its cache are gone, the
    fleet store holds the only copy); sandbox 2 is seeded from the store
    and the runner's jax.monitoring listener reports persistent-cache HITS
    with no new cache entries — zero recompilation across disposed
    sandboxes.

    Shared-path mode on purpose: jax hashes the cache-dir PATH into its
    cache key, so fleet-wide hits require the fleet-constant cache path
    production has (every pod mounts the cache at the same mountPath);
    per-sandbox paths would change the keys themselves."""
    pytest.importorskip("jax")
    import shutil

    cache_dir = tmp_path / "pod-cache-path"
    # Warm jax import: the runner's jax.monitoring listener (which reports
    # the per-request hit/miss counts this test asserts on) registers
    # during the warm import.
    executor, backend = make_stack(
        tmp_path,
        warm_import_jax=True,
        compile_cache_per_sandbox=False,
        jax_compilation_cache_dir=str(cache_dir),
    )
    source = (
        "import jax, jax.numpy as jnp\n"
        "f = jax.jit(lambda a, b: a @ b)\n"
        "x = jnp.ones((128, 128), dtype=jnp.float32)\n"
        "f(x, x).block_until_ready()\n"
        "print('ran')\n"
    )
    try:
        # The compile happens on a trusted (pre-warm-style) run — harvest
        # only admits those.
        first = await executor._execute_trusted(source, timeout=300.0)
        assert first.exit_code == 0, first.stderr
        assert first.phases.get("compile_cache_new_bytes", 0) > 0
        await _settle(executor)
        assert backend._procs == {}  # sandbox 1 disposed
        assert executor.compile_cache.entry_count() > 0
        # The "pod" and its local cache are both gone; only the fleet
        # store survives.
        shutil.rmtree(cache_dir)

        second = await executor.execute(source, timeout=300.0)
        assert second.exit_code == 0, second.stderr
        assert second.phases.get("compile_cache_seeded_bytes", 0) > 0
        # Seeded kernels served the whole run: hits, no fresh misses that
        # produced new cache entries.
        assert second.phases.get("compile_cache_hits", 0) > 0
        assert second.phases.get("compile_cache_new_bytes", 1) == 0
        await _settle(executor)
    finally:
        await executor.close()


@pytest.mark.slow
async def test_new_prewarm_kernel_harvests_in_trusted_epoch(tmp_path):
    """The PREWARM_SOURCES growth contract (carried follow-up from PR 6:
    fleet coverage scales only with this set): the newly added
    small_matmul_chain kernel — a chained 64x64 matmul —
    compiles on a trusted (pre-warm) run, harvests into the fleet store in
    the trusted epoch, and a later TENANT run of the same shape hits the
    seeded cache with zero recompilation."""
    pytest.importorskip("jax")
    import shutil

    from bee_code_interpreter_fs_tpu.services.compile_cache import (
        PREWARM_SOURCES,
    )

    sources = dict(PREWARM_SOURCES)
    assert "small_matmul_chain" in sources  # the satellite's new entry
    cache_dir = tmp_path / "pod-cache-path"
    executor, backend = make_stack(
        tmp_path,
        warm_import_jax=True,
        compile_cache_per_sandbox=False,
        jax_compilation_cache_dir=str(cache_dir),
    )
    try:
        trusted = await executor._execute_trusted(
            sources["small_matmul_chain"], timeout=300.0
        )
        assert trusted.exit_code == 0, trusted.stderr
        assert "prewarm small_matmul_chain ok" in trusted.stdout
        # The trusted run COMPILED it (fresh store, fresh dir)...
        assert trusted.phases.get("compile_cache_new_bytes", 0) > 0
        await _settle(executor)
        # ...and teardown harvested it into the fleet store while the
        # epoch was still trusted (no tenant code has run).
        assert backend._procs == {}
        assert executor.compile_cache.entry_count() > 0

        # The sandbox and its local cache are both gone; only the fleet
        # store survives to seed the next spawn.
        shutil.rmtree(cache_dir)
        tenant = await executor.execute(
            sources["small_matmul_chain"], timeout=300.0
        )
        assert tenant.exit_code == 0, tenant.stderr
        assert tenant.phases.get("compile_cache_seeded_bytes", 0) > 0
        assert tenant.phases.get("compile_cache_hits", 0) > 0
        assert tenant.phases.get("compile_cache_new_bytes", 1) == 0
        await _settle(executor)
    finally:
        await executor.close()
