# NPBench (github.com/spcl/npbench), npbench/benchmarks/deep_learning/softmax:
# `kernel()` of softmax_numpy.py, the source's lines kept as they are;
# `initialize()` of softmax.py reads the turn's input FILES where the source
# draws (`rng.random((N, H, SM, SM), dtype=np.float32)`). What differs is
# listed, each with what forced it, in configs/npbench-files-1chip.json: the
# data come from files (the harness makes them from the seed as bytes; the
# upper 24 bits of each 32-bit word are a float32 in [0, 1), the construction
# numpy's own float32 `random` uses, exact under stock numpy and on the chip),
# in SHARDS files of N / SHARDS each, N raised, and what is printed: the source
# prints nothing and NPBench times the call; here four single elements of the
# input and of the output at stated places and the sum of each, rows first, go
# to stdout, since stdout is compared.
#
# The least an execution moves on the device, whatever implements it: the words
# as they were read (4 bytes an element), x written once (it is printed, so it
# exists: 4) and the result written once (4); the row's maximum, the
# exponentials and their sum fit in one pass over a row of SM held on chip:
# 12 * N * H * SM * SM bytes (`floor` in softmax.json). 8 of them if nothing
# had to hold x.
import numpy as np

N, H, SM, SHARDS = P["N"], P["H"], P["SM"], P["SHARDS"]
LOWP = P.get("LOWP", 0)  # the control: x held in bfloat16


def from_file(path):
    raw = np.fromfile(path, dtype=np.uint32)
    return (raw >> 8).astype(np.float32) * np.float32(2.0 ** -24)


def initialize(N, H, SM):
    # source: rng = np.random.default_rng(42); x = rng.random((N, H, SM, SM), dtype=np.float32)
    x = np.concatenate([from_file(f"x_{i:02d}.bin") for i in range(SHARDS)])
    return x.reshape(N, H, SM, SM)


# Numerically-stable version of softmax
def softmax(x):
    tmp_max = np.max(x, axis=-1, keepdims=True)
    tmp_out = np.exp(x - tmp_max)
    tmp_sum = np.sum(tmp_out, axis=-1, keepdims=True)
    return tmp_out / tmp_sum


x = initialize(N, H, SM)
if LOWP:
    import ml_dtypes

    x = x.astype(ml_dtypes.bfloat16)
out = softmax(x)

at = ([0, N // 3, N // 2, N - 1], [0, H // 3, H // 2, H - 1], [1, SM // 3, SM // 2, SM - 2], [1, SM // 2, SM // 3, SM - 2])
print(f"softmax N={N} H={H} SM={SM} shards={SHARDS} float32")
# (the result first: the program that computes it then makes x on its way, once)
for name, array in (("out", out), ("x", x)):
    picked = np.asarray(array[at]).astype(np.float64)
    for n, h, i, j, value in zip(*at, picked):
        print(f"{name}[{n}, {h}, {i}, {j}] = {value:.9e}")
    print(f"sum({name}), rows first = {float(array.sum(axis=-1).sum()):.9e}")
