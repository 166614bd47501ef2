# NPBench (github.com/spcl/npbench), npbench/benchmarks/polybench/fdtd_2d:
# `initialize()` of fdtd_2d.py and `kernel()` of fdtd_2d_numpy.py, the source's
# lines kept as they are (each is quoted where it was changed). What differs is
# listed, each with what forced it, in configs/npbench-1chip.json: float32
# (`datatype`), the grid raised at the source's 10:13 and the steps cut so that
# a turn's work stays the source's, the column offset of ex drawn from the seed
# (the source's 1, or 4: it changes no amount of work), and what is printed:
# the source prints nothing and NPBench times the call; here four single
# elements of each output field at stated places and its sum, rows first, go to
# stdout, since stdout is compared.
#
# The least a step-by-step execution moves, whatever implements it: a step
# reads and writes each of the three fields once (the two updates that read hz
# may share one pass over it), 24 NX NY bytes in float32: TMAX * 24 * NX * NY
# (`floor` in fdtd_2d.json).
import numpy as np

TMAX, NX, NY, C = P["TMAX"], P["NX"], P["NY"], P["C"]
LOWP = P.get("LOWP", 0)  # the control: the fields held in bfloat16
datatype = np.float32  # source: datatype=np.float64


def initialize(TMAX, NX, NY, datatype=datatype):
    # source: ex = np.fromfunction(lambda i, j: (i * (j + 1)) / NX, (NX, NY), dtype=datatype)
    ex = np.fromfunction(lambda i, j: (i * (j + C)) / NX, (NX, NY), dtype=datatype)
    ey = np.fromfunction(lambda i, j: (i * (j + 2)) / NY, (NX, NY), dtype=datatype)
    hz = np.fromfunction(lambda i, j: (i * (j + 3)) / NX, (NX, NY), dtype=datatype)
    _fict_ = np.fromfunction(lambda i: i, (TMAX, ), dtype=datatype)
    return ex, ey, hz, _fict_


def kernel(TMAX, ex, ey, hz, _fict_):
    for t in range(TMAX):
        ey[0, :] = _fict_[t]
        ey[1:, :] -= 0.5 * (hz[1:, :] - hz[:-1, :])
        ex[:, 1:] -= 0.5 * (hz[:, 1:] - hz[:, :-1])
        hz[:-1, :-1] -= 0.7 * (ex[:-1, 1:] - ex[:-1, :-1] + ey[1:, :-1] -
                               ey[:-1, :-1])


ex, ey, hz, _fict_ = initialize(TMAX, NX, NY)
if LOWP:
    import ml_dtypes

    ex, ey, hz, _fict_ = (f.astype(ml_dtypes.bfloat16) for f in (ex, ey, hz, _fict_))
kernel(TMAX, ex, ey, hz, _fict_)

# hz first: its last update reads ex's and ey's, so one program computes all three.
# Places inside the grid: along row 0 and column 0 the fields are differences of
# near-equal numbers, which no float32 run holds to more than a few digits.
rows, cols = [NX // 5, NX // 3, NX // 2, NX - 2], [NY // 7, NY - 2, NY // 3, NY // 2]
print(f"fdtd_2d NX={NX} NY={NY} TMAX={TMAX} C={C} float32")
for name, field in (("hz", hz), ("ex", ex), ("ey", ey)):
    picked = np.asarray(field[rows, cols]).astype(np.float64)
    for i, j, value in zip(rows, cols, picked):
        print(f"{name}[{i}, {j}] = {value:.9e}")
    print(f"sum({name}), rows first = {float(field.sum(axis=1).sum()):.9e}")
