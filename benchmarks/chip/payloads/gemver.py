# NPBench (github.com/spcl/npbench), npbench/benchmarks/polybench/gemver:
# `initialize()` of gemver.py and `kernel()` of gemver_numpy.py, the source's
# lines kept as they are (each is quoted where it was changed). What differs is
# listed, each with what forced it, in configs/npbench-1chip.json: float32
# (`datatype`), N raised until A is a quarter of the chip's memory, alpha drawn
# from the seed (the source's 1.5, or 1.25: it changes no amount of work), and
# what is printed: the source prints nothing and NPBench times the call; here
# four single elements of each output array at stated places and its sum (A's
# rows first) go to stdout, since stdout is compared.
#
# The least an execution moves, whatever implements it: A is written once (its
# data is closed-form, the outer products are of vectors) and read once more for
# the second product, which needs the whole of the first's result; the first
# product can be summed in the pass that writes A. 8 N^2 bytes in float32
# (`floor` in gemver.json). Three separate passes over A would be 12 N^2.
import numpy as np

N, ALPHA = P["N"], P["ALPHA"]
LOWP = P.get("LOWP", 0)  # the control: the matrix and the vectors held in bfloat16
datatype = np.float32  # source: datatype=np.float64


def initialize(N, datatype=datatype):
    alpha = datatype(ALPHA)  # source: alpha = datatype(1.5)
    beta = datatype(1.2)
    fn = datatype(N)
    A = np.fromfunction(lambda i, j: (i * j % N) / N, (N, N), dtype=datatype)
    u1 = np.fromfunction(lambda i: i, (N, ), dtype=datatype)
    u2 = np.fromfunction(lambda i: ((i + 1) / fn) / 2.0, (N, ), dtype=datatype)
    v1 = np.fromfunction(lambda i: ((i + 1) / fn) / 4.0, (N, ), dtype=datatype)
    v2 = np.fromfunction(lambda i: ((i + 1) / fn) / 6.0, (N, ), dtype=datatype)
    w = np.zeros((N, ), dtype=datatype)
    x = np.zeros((N, ), dtype=datatype)
    y = np.fromfunction(lambda i: ((i + 1) / fn) / 8.0, (N, ), dtype=datatype)
    z = np.fromfunction(lambda i: ((i + 1) / fn) / 9.0, (N, ), dtype=datatype)
    return alpha, beta, A, u1, v1, u2, v2, w, x, y, z


def kernel(alpha, beta, A, u1, v1, u2, v2, w, x, y, z):
    A += np.outer(u1, v1) + np.outer(u2, v2)
    x += beta * y @ A + z
    w += alpha * A @ x


alpha, beta, A, u1, v1, u2, v2, w, x, y, z = initialize(N)
if LOWP:
    import ml_dtypes

    alpha, beta = ml_dtypes.bfloat16(alpha), ml_dtypes.bfloat16(beta)
    A, u1, v1, u2, v2, w, x, y, z = (
        a.astype(ml_dtypes.bfloat16) for a in (A, u1, v1, u2, v2, w, x, y, z))
kernel(alpha, beta, A, u1, v1, u2, v2, w, x, y, z)

at = [1, N // 3, N // 2, N - 2]
print(f"gemver N={N} alpha={ALPHA} float32")
for name, vector in (("w", w), ("x", x)):
    picked = np.asarray(vector[at]).astype(np.float64)
    for i, value in zip(at, picked):
        print(f"{name}[{i}] = {value:.9e}")
    print(f"sum({name}) = {float(vector.sum()):.9e}")
picked = np.asarray(A[at, at[::-1]]).astype(np.float64)
for i, j, value in zip(at, at[::-1], picked):
    print(f"A[{i}, {j}] = {value:.9e}")
print(f"sum(A), rows first = {float(A.sum(axis=1).sum()):.9e}")
