# upstream examples/benchmark-numpy.py: ONE sum of squares over an array that
# the user holds, `(a * a).sum()`. What differs from upstream is listed, each
# with what forced it, in configs/toolcalls-1chip.json: the data is
# closed-form where upstream draws it (stock numpy and the shim must hold the
# same array: element i is (i mod M) / M, M an odd prime drawn from the seed);
# float32, which is what the chip computes in; N raised until the array is a
# quarter of the chip's memory; rows summed first (numpy's flat float32 sum
# over 1.2e9 elements is itself off by up to 3e-4); no wall clock printed,
# since stdout is compared. `a` stays bound, as upstream's does, so the shim
# writes the array out on the device and does not fuse it away.
import numpy as np

R, C, M = P["R"], P["C"], P["M"]
LOWP = P.get("LOWP", 0)  # the control: the array, its products and sums held in bfloat16
N = R * C
a = np.arange(N, dtype=np.int32)
a %= M  # M is a python int: the shim compiles a constant divisor, one program per M
a = a.astype(np.float32)
a /= float(M)
a = a.reshape(R, C)
if LOWP:
    import ml_dtypes

    a = a.astype(ml_dtypes.bfloat16)
s = float((a * a).sum(axis=1).sum())
print(f"sum(x*x) over N={N} float32 M={M} = {s:.9e}")
