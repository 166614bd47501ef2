"""Headline array benchmark (parity: reference examples/benchmark-numpy.py —
sum of squares over 1e8 random doubles, self-reported wall clock).

Submitted through Execute, the sandbox's numpy dispatch shim routes the array
work onto the TPU. Two numbers are reported:

- GFLOPS (the BASELINE.json headline): steady-state throughput over ITERS
  data-DEPENDENT passes with one host sync at the end — each pass consumes
  the previous pass's array, so XLA cannot CSE the chain into one kernel,
  and the per-sync host round-trip is amortized the way any pipelined
  workload amortizes it.
- GFLOPS_single_shot: one pass, one sync — the reference script's exact
  shape. A large gap between the two measures dispatch and sync latency,
  not the chip.
"""

import time

import numpy as np

N = 100_000_000

t0 = time.perf_counter()
a = np.random.rand(N)
# float() forces device sync, so the timings below exclude materialization.
_ = float(a[0])
t1 = time.perf_counter()

# Host numpy has no dispatch latency to amortize (steady == single shot);
# keep the CPU-baseline run short.
ITERS = 32 if type(a).__name__ == "TpuArray" else 4

# Reference-parity single shot: one full pass, one host sync.
s = float((a * a).sum())
t2 = time.perf_counter()

# Steady state: ITERS chained passes, one host sync. b feeds back into the
# next pass so every pass really runs (no CSE); acc folds every result into
# the final scalar so nothing is dead code.
acc = 0.0
b = a
for _ in range(ITERS):
    acc = acc + (b * b).sum()
    b = b + 1e-9
acc = float(acc)
t3 = time.perf_counter()

flops = 2 * N  # one multiply + one add per element per pass
print(f"backend: {type(a).__name__}")
print(f"sum(x*x) over {N:_} doubles = {s:.6f}")
print(
    f"alloc_s={t1 - t0:.4f} single_shot_s={t2 - t1:.4f} "
    f"steady_s={t3 - t2:.4f} (x{ITERS})"
)
print(f"GFLOPS_single_shot={flops / (t2 - t1) / 1e9:.3f}")
print(f"GFLOPS={flops * ITERS / (t3 - t2) / 1e9:.3f}")
