"""Compute-bound benchmark: chained bf16 matmuls on the MXU.

BASELINE.json describes a matmul config and SURVEY.md §6 orders both shapes
measured; the sum-of-squares headline is HBM-bandwidth-bound, so this is the
number that shows whether Execute-submitted user code can reach the systolic
array's peak. Pure JAX user code (no numpy shim needed): a lax.fori_loop
chain of DIM×DIM @ DIM×DIM bf16 matmuls — each iteration consumes the
previous product, so XLA cannot collapse the chain — with one host sync at
the end. Reports achieved TFLOPS and, on the device kind the peak belongs
to, model-flops-utilization against it.

A TPU payload: on any other backend it exits non-zero instead of printing the
same markers from a shrunken CPU run.
"""

import time
from functools import partial

import jax
import jax.numpy as jnp

DEVICE = jax.devices()[0]
if DEVICE.platform != "tpu":
    raise SystemExit(
        f"benchmark-matmul.py is a TPU payload; jax attached {DEVICE.platform}"
    )
DIM = 8192
# One host sync ends the whole chain, so its cost is amortized over ITERS.
ITERS = 256
# Published bf16 peak per chip, keyed by device_kind (Google Cloud
# documentation, "TPU v5e"). A kind that is not here gets no MFU line.
BF16_PEAK_TFLOPS = {"TPU v5 lite": 197.0, "TPU v5e": 197.0}


@partial(jax.jit, static_argnums=(1,))
def matmul_chain(a, iters):
    def body(_, b):
        # Rescale each product so bf16 stays in range across the chain:
        # per-iteration std grows by ~sqrt(DIM)*scale, so scale must sit at
        # or below 1/sqrt(DIM) ≈ 0.011 — 0.0100 decays gently (~1e-6 after
        # 256 iters, nowhere near bf16's underflow), where the old 0.0156
        # grew ~1.4x/iter and overflowed to inf/NaN past ~250 iterations.
        return (a @ b) * jnp.bfloat16(0.0100)

    b = jax.lax.fori_loop(0, iters, body, a)
    return b[0, 0].astype(jnp.float32)


key = jax.random.PRNGKey(0)
a = jax.random.normal(key, (DIM, DIM), dtype=jnp.bfloat16)
probe = float(matmul_chain(a, ITERS))  # compile + first run off the clock
assert probe == probe, "matmul chain produced NaN — rescale is wrong"

best = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    float(matmul_chain(a, ITERS))
    best = min(best, time.perf_counter() - t0)

tflops = ITERS * 2 * DIM**3 / best / 1e12
print(f"backend: {DEVICE.platform} kind={DEVICE.device_kind} dim={DIM} iters={ITERS}")
print(f"elapsed_s={best:.4f}")
print(f"TFLOPS={tflops:.2f}")
if DEVICE.device_kind not in BF16_PEAK_TFLOPS:
    raise SystemExit(f"no published bf16 peak for device kind {DEVICE.device_kind!r}")
print(f"MFU_vs_v5e_peak_pct={tflops / BF16_PEAK_TFLOPS[DEVICE.device_kind] * 100:.1f}")
