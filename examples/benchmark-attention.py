"""Long-context fused attention benchmark: the Pallas flash kernel as an
ordinary Execute payload. Causal attention at t=16384 — a sequence length
whose dense score matrix (t² floats per head) would be gigabytes — runs in
one kernel with K/V tiles streaming through VMEM. Steady state over chained
iterations (each consumes the previous output as queries) with one final
sync.

A TPU payload: the kernel lowers through Mosaic, never interpreted, and on
any other backend the script exits non-zero."""

import os
import time
from functools import partial

import jax
import jax.numpy as jnp

from bee_code_interpreter_fs_tpu.ops.flash_attention import flash_attention

DEVICE = jax.devices()[0]
if DEVICE.platform != "tpu":
    raise SystemExit(
        f"benchmark-attention.py is a TPU payload; jax attached {DEVICE.platform}"
    )
B, T, H, D = 1, 16384, 4, 128
# Tile-sweep knobs (powers of two; see flash_attention's clamp rule).
BLOCK_Q = int(os.environ.get("BENCH_BLOCK_Q", "512"))
BLOCK_K = int(os.environ.get("BENCH_BLOCK_K", "1024"))
T = int(os.environ.get("BENCH_SEQ_LEN", str(T)))
# One host sync ends the whole chain, so its cost is amortized over ITERS.
ITERS = 32

key = jax.random.PRNGKey(0)
q, k, v = (
    jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    for kk in jax.random.split(key, 3)
)


@jax.jit
def chain(q, k, v):
    def body(_, q):
        return flash_attention(
            q, k, v, block_q=BLOCK_Q, block_k=BLOCK_K
        ).astype(q.dtype)

    out = jax.lax.fori_loop(0, ITERS, body, q)
    return out[0, 0, 0, 0].astype(jnp.float32)


float(chain(q, k, v))  # compile + first run off the clock
best = float("inf")
for _ in range(2):
    t0 = time.perf_counter()
    float(chain(q, k, v))
    best = min(best, time.perf_counter() - t0)

# Causal attention flops: QK^T + PV, each 2*b*h*(t^2/2)*d.
flops = ITERS * 4 * B * H * (T * T / 2) * D
# Report the EFFECTIVE tile sizes (after the kernel's clamp-to-t +
# power-of-two rounding), not the requested ones — sweep data points must
# be labeled with the configuration that actually ran.
from bee_code_interpreter_fs_tpu.ops.flash_attention import effective_blocks

eff_q, eff_k = effective_blocks(T, BLOCK_Q, BLOCK_K)
print(
    f"backend: {DEVICE.platform} t={T} iters={ITERS} "
    f"blocks={eff_q}x{eff_k}"
)
print(f"ATTN_TFLOPS={flops / best / 1e12:.2f}")
