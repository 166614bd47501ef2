# NPBench (github.com/spcl/npbench), npbench/benchmarks/polybench/k3mm
# (PolyBench's 3mm): `initialize()` of k3mm.py and `kernel()` of
# k3mm_numpy.py, the source's lines kept as they are (each is quoted where it
# was changed). What differs is listed, each with what forced it, in
# configs/npbench-linalg-1chip.json: float32 (`datatype`), the five sizes
# raised in PolyBench's ratios, and what is printed: the source prints nothing
# and NPBench times the call; here four single elements of the output (the
# product the kernel returns, NI x NL) at stated places (off the diagonal,
# none in the first or the last row) and its sum, rows first, go to stdout,
# since stdout is compared.
#
# The least ANY execution does, whatever implements it: a chain of four
# matrices can be multiplied in five orders, and left to right, as the source
# writes it, is the cheapest at these sizes (5.28e12 operations; the other
# four 5.40e12 to 6.56e12): 2 * NI * (NK * NJ + NJ * NM + NM * NL) (`floor`
# in k3mm.json).
import numpy as np

NI, NJ, NK, NL, NM = P["NI"], P["NJ"], P["NK"], P["NL"], P["NM"]
LOWP = P.get("LOWP", 0)  # the control: the four matrices held in bfloat16
datatype = np.float32  # source: datatype=np.float64


def initialize(NI, NJ, NK, NL, NM, datatype=datatype):
    A = np.fromfunction(lambda i, j: ((i * j + 1) % NI) / (5 * NI), (NI, NK), dtype=datatype)
    B = np.fromfunction(lambda i, j: ((i * (j + 1) + 2) % NJ) / (5 * NJ), (NK, NJ), dtype=datatype)
    C = np.fromfunction(lambda i, j: (i * (j + 3) % NL) / (5 * NL), (NJ, NM), dtype=datatype)
    D = np.fromfunction(lambda i, j: ((i * (j + 2) + 2) % NK) / (5 * NK), (NM, NL), dtype=datatype)
    return A, B, C, D


def kernel(A, B, C, D):
    return A @ B @ C @ D


A, B, C, D = initialize(NI, NJ, NK, NL, NM)
if LOWP:
    import ml_dtypes

    A, B, C, D = (a.astype(ml_dtypes.bfloat16) for a in (A, B, C, D))
G = kernel(A, B, C, D)

rows, cols = [1, NI // 3, NI // 2, NI - 2], [NL - 2, NL // 2, NL // 3, 1]
print(f"k3mm NI={NI} NJ={NJ} NK={NK} NL={NL} NM={NM} float32")
picked = np.asarray(G[rows, cols]).astype(np.float64)
for i, j, value in zip(rows, cols, picked):
    print(f"G[{i}, {j}] = {value:.9e}")
print(f"sum(G), rows first = {float(G.sum(axis=1).sum()):.9e}")
