# NPBench (github.com/spcl/npbench), npbench/benchmarks/pythran/arc_distance:
# `kernel()` of arc_distance_numpy.py, the source's lines kept as they are;
# `initialize()` of arc_distance.py reads the turn's four input FILES where the
# source draws (`rng.random((N, ))`, four times). What differs is listed, each
# with what forced it, in configs/npbench-files-1chip.json: the data come from
# files (the harness makes them from the seed as bytes; the upper 24 bits of
# each 32-bit word are a float32 in [0, 1), exact under stock numpy and on the
# chip), float32 (the source's vectors are float64), N raised, and what is
# printed: the source prints nothing and NPBench times the call; here four
# single elements of each input and of the result at stated places and the sum
# of each go to stdout, since stdout is compared.
#
# The least an execution moves on the device, whatever implements it: four
# vectors read as they were read from the files and the result written, all
# element by element in one pass: 20 * N bytes (`floor` in arc_distance.json).
import numpy as np

N = P["N"]
LOWP = P.get("LOWP", 0)  # the control: the four vectors held in bfloat16


def from_file(path):
    raw = np.fromfile(path, dtype=np.uint32)
    return (raw >> 8).astype(np.float32) * np.float32(2.0 ** -24)


def initialize(N):
    # source: rng = default_rng(42); t0, p0, t1, p1 = rng.random((N, )), rng.random((N, )), rng.random((N, )), rng.random((N, ))
    t0, p0, t1, p1 = from_file("theta_1.bin"), from_file("phi_1.bin"), from_file("theta_2.bin"), from_file("phi_2.bin")
    return t0, p0, t1, p1


def arc_distance(theta_1, phi_1, theta_2, phi_2):
    """
    Calculates the pairwise arc distance between all points in vector a and b.
    """
    temp = np.sin((theta_2 - theta_1) / 2)**2 + np.cos(theta_1) * np.cos(theta_2) * np.sin((phi_2 - phi_1) / 2)**2
    distance_matrix = 2 * (np.arctan2(np.sqrt(temp), np.sqrt(1 - temp)))
    return distance_matrix


t0, p0, t1, p1 = initialize(N)
if LOWP:
    import ml_dtypes

    t0, p0, t1, p1 = (a.astype(ml_dtypes.bfloat16) for a in (t0, p0, t1, p1))
distance = arc_distance(t0, p0, t1, p1)

at = [1, N // 3, N // 2, N - 2]
print(f"arc_distance N={N} float32")
for name, vector in (("theta_1", t0), ("phi_1", p0), ("theta_2", t1), ("phi_2", p1), ("distance", distance)):
    picked = np.asarray(vector[at]).astype(np.float64)
    for i, value in zip(at, picked):
        print(f"{name}[{i}] = {value:.9e}")
    print(f"sum({name}) = {float(vector.sum()):.9e}")
