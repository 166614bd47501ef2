# NPBench (github.com/spcl/npbench), npbench/benchmarks/polybench/floyd_warshall:
# `initialize()` of floyd_warshall.py and `kernel()` of floyd_warshall_numpy.py,
# the source's lines kept as they are (each is quoted where it was changed).
# What differs is listed, each with what forced it, in
# configs/npbench-linalg-1chip.json: N raised, the steps cut to the first K
# vertices, `initialize()`'s double python loop written as one `np.where`, the
# constant it sets drawn from the seed (the source's 999, or 997), and what is
# printed: the source prints nothing and NPBench times the call; here four
# single elements of the output `path` at stated places (off the diagonal,
# none in the first or the last row; the second held the drawn constant after
# `initialize()`, and the K steps lowered it) and its sum go to stdout, since
# stdout is compared. The sum is taken in two steps, every row in int32 on the
# device (a row is under 2**31) and the N row sums in int64 on the host, so
# that N numbers cross to the host and not the matrix: numpy promotes an
# integer sum's accumulator to int64, which the chip does not have.
#
# The least a step-by-step execution moves, whatever implements it: step k
# needs the whole of step k - 1's result (its row k and its column k), so a
# step reads `path` and writes it, 8 N^2 bytes in int32: K * 8 * N * N
# (`floor` in floyd_warshall.json).
import numpy as np

N, K, INF = P["N"], P["K"], P["INF"]
LOWP = P.get("LOWP", 0)  # the control: `path` and its row sums held in bfloat16 (999 is 1000 there)
datatype = np.int32  # as the source


def initialize(N, datatype=datatype):
    path = np.fromfunction(lambda i, j: i * j % 7 + 1, (N, N), dtype=datatype)
    # source: for i in range(N):
    #             for j in range(N):
    #                 if (i + j) % 13 == 0 or (i + j) % 7 == 0 or (i + j) % 11 == 0:
    #                     path[i, j] = 999
    s = np.fromfunction(lambda i, j: i + j, (N, N), dtype=datatype)
    path = np.where((s % 13 == 0) | (s % 7 == 0) | (s % 11 == 0), datatype(INF), path)
    return path


def kernel(path):
    for k in range(K):  # source: for k in range(path.shape[0]):
        path[:] = np.minimum(path[:], np.add.outer(path[:, k], path[k, :]))


path = initialize(N)
if LOWP:
    import ml_dtypes

    path = path.astype(ml_dtypes.bfloat16)
kernel(path)

held = N // 2 + -(N // 3 + N // 2) % 7  # (N // 3 + held) % 7 == 0: `initialize()` set the constant there
rows, cols = [1, N // 3, N // 2, N - 2], [N - 2, held, N // 3, 1]
print(f"floyd_warshall N={N} K={K} INF={INF} int32")
picked = np.asarray(path[rows, cols]).astype(np.int64)
for i, j, value in zip(rows, cols, picked):
    print(f"path[{i}, {j}] = {value}")
row_sums = np.asarray(path.sum(axis=1, dtype=path.dtype))
print(f"sum(path), rows first = {int(row_sums.astype(np.int64).sum())}")
