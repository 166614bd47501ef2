# NPBench (github.com/spcl/npbench), npbench/benchmarks/polybench/jacobi_2d:
# `initialize()` of jacobi_2d.py and `kernel()` of jacobi_2d_numpy.py, the
# source's lines kept as they are (each is quoted where it was changed). What
# differs is listed, each with what forced it, in configs/npbench-1chip.json:
# float32 (`datatype`), the grid raised and the steps cut so that a turn's
# work stays the source's, the column offset of A drawn from the seed (the
# source's 2, or 4: it changes no amount of work), and what is printed: the
# source prints nothing and NPBench times the call; here four single elements
# of each output array at stated places and its sum, rows first, go to stdout,
# since stdout is compared.
#
# The least a step-by-step execution moves, whatever implements it: a half-step
# reads one grid and writes the other, 8 N^2 bytes in float32, and a time step
# is two of them: (TSTEPS - 1) * 16 * N * N (`floor` in jacobi_2d.json).
import numpy as np

N, TSTEPS, C = P["N"], P["TSTEPS"], P["C"]
LOWP = P.get("LOWP", 0)  # the control: both grids held in bfloat16
datatype = np.float32  # source: datatype=np.float64


def initialize(N, datatype=datatype):
    # source: A = np.fromfunction(lambda i, j: i * (j + 2) / N, (N, N), dtype=datatype)
    A = np.fromfunction(lambda i, j: i * (j + C) / N, (N, N), dtype=datatype)
    B = np.fromfunction(lambda i, j: i * (j + 3) / N, (N, N), dtype=datatype)
    return A, B


def kernel(TSTEPS, A, B):
    for t in range(1, TSTEPS):
        B[1:-1, 1:-1] = 0.2 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] +
                               A[2:, 1:-1] + A[:-2, 1:-1])
        A[1:-1, 1:-1] = 0.2 * (B[1:-1, 1:-1] + B[1:-1, :-2] + B[1:-1, 2:] +
                               B[2:, 1:-1] + B[:-2, 1:-1])


A, B = initialize(N)
if LOWP:
    import ml_dtypes

    A, B = A.astype(ml_dtypes.bfloat16), B.astype(ml_dtypes.bfloat16)
kernel(TSTEPS, A, B)

# A first: its last half-step reads B's, so one program computes both.
rows, cols = [1, N // 3, N // 2, N - 2], [1, N - 2, N // 3, N // 2]
print(f"jacobi_2d N={N} TSTEPS={TSTEPS} C={C} float32")
for name, grid in (("A", A), ("B", B)):
    picked = np.asarray(grid[rows, cols]).astype(np.float64)
    for i, j, value in zip(rows, cols, picked):
        print(f"{name}[{i}, {j}] = {value:.9e}")
    print(f"sum({name}), rows first = {float(grid.sum(axis=1).sum()):.9e}")
