# The tests' own array payload: one sum of cubes over R x C float32 that the
# turn holds, data closed-form as sumsq's (element i is (i mod M) / M), rows
# summed first.
import numpy as np

R, C, M = P["R"], P["C"], P["M"]
LOWP = P.get("LOWP", 0)  # the control: the array, its products and sums held in bfloat16
N = R * C
a = np.arange(N, dtype=np.int32)
a %= M
a = a.astype(np.float32)
a /= float(M)
a = a.reshape(R, C)
if LOWP:
    import ml_dtypes

    a = a.astype(ml_dtypes.bfloat16)
s = float((a * a * a).sum(axis=1).sum())
print(f"sum(x*x*x) over N={N} float32 M={M} = {s:.9e}")
