"""The serving stack through the PRODUCT surface: a continuous-batching
engine built and driven INSIDE a sandbox via Execute — orchestrator →
pool → C++ executor server → warm JAX runner → ServingEngine — with the
outputs token-checked against the fused decoder in the same process.

This is the correctness backbone of BASELINE.json's config 5 (a resident
engine served from a session); here the full feature surface rides
one Execute: prefix caching, per-request sampling with a seed, logprobs,
and a QLoRA adapter served beside base traffic.
"""

# Optional-dep guard: a missing dependency must degrade this module to a
# SKIP at collection, not an ERROR that interrupts the whole run.
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")


from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.storage import Storage

SERVING_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from bee_code_interpreter_fs_tpu.models import (
    LlamaConfig, ServingEngine, greedy_generate, init_params, init_lora,
    lora_wrap, quantize_params,
)

cfg = LlamaConfig.tiny(n_layers=2, dim=64, n_heads=4, n_kv_heads=2,
                       hidden_dim=128, vocab_size=97, max_seq_len=96,
                       dtype="float32")
base = quantize_params(init_params(jax.random.PRNGKey(0), cfg))
lora = jax.tree.map(lambda x: x + 0.02 * jnp.ones_like(x),
                    init_lora(jax.random.PRNGKey(1), cfg, rank=4))

eng = ServingEngine(base, cfg, n_slots=2, max_len=96, steps_per_sync=3,
                    adapters={"t": lora})
pid = eng.register_prefix([9, 4, 27])
r_pre = eng.submit([3, 5], 7, prefix_id=pid, logprobs=True)
r_ada = eng.submit([3, 5], 7, adapter="t")
r_smp = eng.submit([8], 6, temperature=1.1, seed=5)
res = eng.run()

ref_pre = np.asarray(greedy_generate(
    base, jnp.asarray([[9, 4, 27, 3, 5]], jnp.int32), cfg,
    max_new_tokens=7))[0, 5:]
assert np.array_equal(res[r_pre], ref_pre), (res[r_pre], ref_pre)
lps = eng.take_logprobs(r_pre)
assert lps is not None and lps.shape == (7,) and np.isfinite(lps).all()

ref_ada = np.asarray(greedy_generate(
    lora_wrap(base, lora), jnp.asarray([[3, 5]], jnp.int32), cfg,
    max_new_tokens=7))[0, 2:]
assert np.array_equal(res[r_ada], ref_ada), (res[r_ada], ref_ada)
assert len(res[r_smp]) == 6

print("serving_ok prefix+qlora+sampled")
"""


@pytest.fixture
async def stack(tmp_path):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=1,
        default_execution_timeout=240.0,
        jax_compilation_cache_dir=str(tmp_path / "jax-cache"),
    )
    backend = LocalSandboxBackend(config, warm_import_jax=True)
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    yield executor
    await executor.close()


async def test_serving_engine_inside_sandbox(stack):
    executor = stack
    await executor.fill_pool()
    result = await executor.execute(SERVING_SNIPPET, timeout=240.0)
    assert result.exit_code == 0, result.stderr[-1200:]
    assert "serving_ok prefix+qlora+sampled" in result.stdout

SPEC_SNIPPET = """
import jax, jax.numpy as jnp, numpy as np
from bee_code_interpreter_fs_tpu.models import (
    LlamaConfig, SpeculativeServingEngine, greedy_generate, init_params,
)

cfg = LlamaConfig.tiny(n_layers=2, dim=64, n_heads=4, n_kv_heads=2,
                       hidden_dim=128, vocab_size=97, max_seq_len=64,
                       dtype="float32")
dcfg = LlamaConfig.tiny(n_layers=1, dim=32, n_heads=2, n_kv_heads=2,
                        hidden_dim=64, vocab_size=97, max_seq_len=64,
                        dtype="float32")
target = init_params(jax.random.PRNGKey(0), cfg)
draft = init_params(jax.random.PRNGKey(3), dcfg)

eng = SpeculativeServingEngine(target, cfg, draft_params=draft,
                               draft_cfg=dcfg, gamma=3, n_slots=2,
                               max_len=64, steps_per_sync=2)
r1 = eng.submit([3, 17, 55, 9], 8)
r2 = eng.submit([8], 6)
res = eng.run()
ref = np.asarray(greedy_generate(
    target, jnp.asarray([[3, 17, 55, 9]], jnp.int32), cfg,
    max_new_tokens=8))[0, 4:]
assert np.array_equal(res[r1], ref), (res[r1], ref)
assert len(res[r2]) == 6
print("spec_serving_ok draft+verify")
"""


async def test_speculative_engine_inside_sandbox(stack):
    executor = stack
    await executor.fill_pool()
    result = await executor.execute(SPEC_SNIPPET, timeout=240.0)
    assert result.exit_code == 0, result.stderr[-1200:]
    assert "spec_serving_ok draft+verify" in result.stdout
