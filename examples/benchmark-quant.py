"""Weight-only quantized decode benchmark: fused greedy decode tok/s for
bf16 vs int8 vs group-wise packed int4, same model / prompt / batch.

Autoregressive decode at small batch is weight-HBM-bound: every step
streams every matmul weight from HBM for a sliver of MXU work, so halving
the bytes per weight (models/quant.py: int8 + per-output-channel f32
scales, dequantize fused into the matmul operand path) should translate
directly into step rate. This measures that claim on the actual chip —
whole generations fused into one jitted program via greedy_generate, so
per-step host dispatch never touches the clock.

The reference has no quantized serving at all; this is a TPU-native
addition (SURVEY.md lists no counterpart).
"""

import time

import jax
import jax.numpy as jnp

from bee_code_interpreter_fs_tpu.models import (
    LlamaConfig,
    greedy_generate,
    init_params,
    quantize4_params,
    quantize_params,
    quantized_nbytes,
)

ON_TPU = jax.devices()[0].platform == "tpu"
if ON_TPU:
    # ~0.94B params: the bf16 (1.9 GB), int8 (1.0 GB), and int4 (~0.55 GB)
    # trees coexist in HBM so all three legs run in one process against
    # identical weights — size cfg with the SUM in mind.
    cfg = LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=16, n_heads=16, n_kv_heads=16,
        hidden_dim=5504, max_seq_len=512,
    )
    NEW_TOKENS, BATCH = 128, 1
else:
    cfg = LlamaConfig.tiny(dtype="float32")
    NEW_TOKENS, BATCH = 8, 1

key = jax.random.PRNGKey(0)
params = init_params(key, cfg)
qparams = quantize_params(params)
prompt = jax.random.randint(
    jax.random.PRNGKey(1), (BATCH, 16), 0, cfg.vocab_size
)


def timed_best(fn, iters=3):
    jax.block_until_ready(fn())  # compile off the clock
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


t_bf16 = timed_best(
    lambda: greedy_generate(params, prompt, cfg, max_new_tokens=NEW_TOKENS)
)
t_int8 = timed_best(
    lambda: greedy_generate(qparams, prompt, cfg, max_new_tokens=NEW_TOKENS)
)

# bf16/int8 results go out BEFORE the int4 leg starts: a partial run
# (int4 OOM / timeout under the caller's deadline) must still carry the
# measurements already made.
bf16_bytes = quantized_nbytes(params)
int8_bytes = quantized_nbytes(qparams)
print(f"backend: {jax.devices()[0].platform}", flush=True)
print(
    f"model: dim={cfg.dim} layers={cfg.n_layers} "
    f"weights bf16={bf16_bytes / 1e9:.2f}GB int8={int8_bytes / 1e9:.2f}GB"
)
print(f"batch={BATCH} new_tokens={NEW_TOKENS} (fused greedy decode)")
print(f"BF16_DECODE_TOKS={BATCH * NEW_TOKENS / t_bf16:.1f}")
print(f"INT8_DECODE_TOKS={BATCH * NEW_TOKENS / t_int8:.1f}")
print(f"INT8_DECODE_SPEEDUP={t_bf16 / t_int8:.2f}", flush=True)

q4params = quantize4_params(params)
t_int4 = timed_best(
    lambda: greedy_generate(q4params, prompt, cfg, max_new_tokens=NEW_TOKENS)
)
print(f"int4_weights_gb={quantized_nbytes(q4params) / 1e9:.2f}")
print(f"INT4_DECODE_TOKS={BATCH * NEW_TOKENS / t_int4:.1f}")
print(f"INT4_DECODE_SPEEDUP={t_bf16 / t_int4:.2f}")
