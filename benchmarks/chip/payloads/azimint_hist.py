# NPBench (github.com/spcl/npbench), npbench/benchmarks/azimint_hist:
# `kernel()` of azimint_hist_numpy.py, the source's lines kept as they are;
# `initialize()` of azimint_hist.py reads the turn's two input FILES where the
# source draws (`rng.random((N, ))`, twice). What differs is listed, each with
# what forced it, in configs/npbench-files-1chip.json: the data come from files
# (the harness makes them from the seed as bytes; the upper 24 bits of each
# 32-bit word are a float32 in [0, 1), exact under stock numpy and on the
# chip), float32 (the source's vectors are float64), N raised, and what is
# printed: the source prints nothing and NPBench times the call; here four
# single elements of each input at stated places and its sum, the two
# histograms' counts at four bins and their totals, and the result at those
# bins and its sum go to stdout, since stdout is compared.
#
# The least an execution moves on the device, whatever implements it: the two
# vectors read once as they were read from the files (one pass can fill both
# histograms); the npt bins stay on chip: 8 * N bytes (`floor` in
# azimint_hist.json).
import numpy as np

N, NPT = P["N"], P["NPT"]
K = P["K"]  # drawn from the seed: the first place printed of each input (1 or 2); it changes no amount of work
LOWP = P.get("LOWP", 0)  # the control: both vectors held in bfloat16, so the bins themselves move


def from_file(path):
    raw = np.fromfile(path, dtype=np.uint32)
    return (raw >> 8).astype(np.float32) * np.float32(2.0 ** -24)


def initialize(N):
    # source: rng = default_rng(42); data, radius = rng.random((N, )), rng.random((N, ))
    data, radius = from_file("data.bin"), from_file("radius.bin")
    return data, radius


def azimint_hist(data, radius, npt):
    histu = np.histogram(radius, npt)[0]
    histw = np.histogram(radius, npt, weights=data)[0]
    return histw / histu


def both_histograms(data, radius, npt):
    """What the kernel computes on its way, for the prints: the same two calls."""
    return np.histogram(radius, npt)[0], np.histogram(radius, npt, weights=data)[0]


data, radius = initialize(N)
if LOWP:
    import ml_dtypes

    data, radius = data.astype(ml_dtypes.bfloat16), radius.astype(ml_dtypes.bfloat16)
result = azimint_hist(data, radius, NPT)

print(f"azimint_hist N={N} npt={NPT} K={K} float32")
at = [K, N // 3, N // 2, N - 2]
for name, vector in (("data", data), ("radius", radius)):
    picked = np.asarray(vector[at]).astype(np.float64)
    for i, value in zip(at, picked):
        print(f"{name}[{i}] = {value:.9e}")
    print(f"sum({name}) = {float(vector.sum()):.9e}")
bins = [1, NPT // 3, NPT // 2, NPT - 2]
histu, histw = both_histograms(data, radius, NPT)
for name, counts in (("histu", histu), ("histw", histw), ("result", result)):
    picked = np.asarray(counts[bins]).astype(np.float64)
    for b, value in zip(bins, picked):
        print(f"{name}[{b}] = {value:.9e}")
    print(f"sum({name}) = {float(counts.sum()):.9e}")
