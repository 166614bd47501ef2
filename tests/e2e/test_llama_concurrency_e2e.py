"""BASELINE config 5 scale-down: 16 concurrent Llama-class Executes.

The capstone concurrency story (SURVEY.md §7.6, BASELINE.json config 5:
"Llama-2-7B JAX inference via Execute, 16 concurrent requests") previously
existed only as an unexecuted benchmark script (VERDICT r1 #10). This drives
16 simultaneous Executes of the in-repo Llama model — each through the full
stack: orchestrator → pool → C++ executor server → warm JAX runner — on the
CPU-forced test platform, asserting every request succeeds and the pool
neither leaks sandboxes nor serializes the burst.
"""

# Optional-dep guard: a missing dependency must degrade this module to a
# SKIP at collection, not an ERROR that interrupts the whole run.
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")

import asyncio
import re
import time


from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.storage import Storage

CONCURRENCY = 16

# Tiny Llama-class forward, self-shrunk for CI: the same model family and
# code path as BASELINE.json's llama inference configuration, smaller shapes.
LLAMA_SNIPPET = """
import jax, jax.numpy as jnp
from bee_code_interpreter_fs_tpu.models.llama import LlamaConfig, init_params, forward

cfg = LlamaConfig.tiny(n_layers=2, dim=128, n_heads=4, n_kv_heads=4,
                       hidden_dim=352, vocab_size=512, max_seq_len=64)
params = init_params(jax.random.PRNGKey(0), cfg)
tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab_size)
fwd = jax.jit(lambda p, t: forward(p, t, cfg))
out = fwd(params, tokens)
out.block_until_ready()
print("llama_ok shape=%s" % (tuple(out.shape),))
"""


@pytest.fixture
async def llama_executor(tmp_path):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=4,
        default_execution_timeout=240.0,
        jax_compilation_cache_dir=str(tmp_path / "jax-cache"),
    )
    backend = LocalSandboxBackend(config, warm_import_jax=True)
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    yield executor, backend
    await executor.close()


async def test_16_concurrent_llama_executes(llama_executor):
    executor, backend = llama_executor
    await executor.fill_pool()
    t0 = time.perf_counter()
    results = await asyncio.gather(
        *(
            executor.execute(LLAMA_SNIPPET, timeout=240.0)
            for _ in range(CONCURRENCY)
        )
    )
    wall = time.perf_counter() - t0

    failures = [r for r in results if r.exit_code != 0]
    assert not failures, f"{len(failures)} failed; first stderr: " + (
        failures[0].stderr[-800:] if failures else ""
    )
    for r in results:
        assert re.search(r"llama_ok shape=\(1, 64, 512\)", r.stdout), r.stdout

    # The burst must actually run concurrently. Full serialization would put
    # wall at ~the sum of the exec phases; require clear overlap. (Bounding
    # against min-exec × N broke once reuse landed: a recycled warm sandbox
    # makes the fastest exec far faster than the burst's cold average, so
    # the old bound tightened for the wrong reason.)
    serialized_total = sum(r.phases["exec"] for r in results)
    assert wall < 0.75 * serialized_total, (
        f"wall {wall:.1f}s vs serialized total {serialized_total:.1f}s — "
        "the burst did not overlap"
    )

    # Pool hygiene: disposals drain; nothing leaks past close() (checked by
    # the fixture teardown), and live processes stay bounded by the LANE
    # TARGET — dynamic since the autoscaler (the burst legitimately raises
    # it to retain warm supply), so runaway means exceeding even that.
    await asyncio.gather(*executor._dispose_tasks, return_exceptions=True)
    await asyncio.gather(*executor._fill_tasks, return_exceptions=True)
    assert len(backend._procs) <= executor._lane_target(0)
