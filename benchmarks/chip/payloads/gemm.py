# NPBench (github.com/spcl/npbench), npbench/benchmarks/polybench/gemm:
# `initialize()` of gemm.py and `kernel()` of gemm_numpy.py, the source's
# lines kept as they are (each is quoted where it was changed). What differs
# is listed, each with what forced it, in configs/npbench-linalg-1chip.json:
# float32 (`datatype`), NI, NJ and NK raised in PolyBench's ratios until the
# three matrices are a quarter of the chip's memory, and what is printed: the
# source prints nothing and NPBench times the call; here four single elements
# of the output C at stated places (off the diagonal, none in the first or the
# last row) and its sum, rows first, go to stdout, since stdout is compared.
#
# The least ANY execution does, whatever implements it: the product, a
# multiplication and an addition for each of NI * NJ * NK triples:
# 2 * NI * NJ * NK floating-point operations (`floor` in gemm.json). The two
# scalings and the addition are NI * (NK + 2 * NJ) more, a ten-thousandth.
import numpy as np

NI, NJ, NK = P["NI"], P["NJ"], P["NK"]
LOWP = P.get("LOWP", 0)  # the control: the three matrices and the scalars held in bfloat16
datatype = np.float32  # source: datatype=np.float64


def initialize(NI, NJ, NK, datatype=datatype):
    alpha = datatype(1.5)
    beta = datatype(1.2)
    C = np.fromfunction(lambda i, j: ((i * j + 1) % NI) / NI, (NI, NJ), dtype=datatype)
    A = np.fromfunction(lambda i, k: (i * (k + 1) % NK) / NK, (NI, NK), dtype=datatype)
    B = np.fromfunction(lambda k, j: (k * (j + 2) % NJ) / NJ, (NK, NJ), dtype=datatype)
    return alpha, beta, C, A, B


def kernel(alpha, beta, C, A, B):
    C[:] = alpha * A @ B + beta * C


alpha, beta, C, A, B = initialize(NI, NJ, NK)
if LOWP:
    import ml_dtypes

    alpha, beta = ml_dtypes.bfloat16(alpha), ml_dtypes.bfloat16(beta)
    C, A, B = (a.astype(ml_dtypes.bfloat16) for a in (C, A, B))
kernel(alpha, beta, C, A, B)

rows, cols = [1, NI // 3, NI // 2, NI - 2], [NJ - 2, NJ // 2, NJ // 3, 1]
print(f"gemm NI={NI} NJ={NJ} NK={NK} float32")
picked = np.asarray(C[rows, cols]).astype(np.float64)
for i, j, value in zip(rows, cols, picked):
    print(f"C[{i}, {j}] = {value:.9e}")
print(f"sum(C), rows first = {float(C.sum(axis=1).sum()):.9e}")
