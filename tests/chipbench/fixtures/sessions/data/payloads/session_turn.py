# A test's session turn (tests/chipbench/fixtures): turn 1 finds the uploaded
# files and makes the state; every turn loads `state.npy`, reads one workspace
# file, builds an R x C float32 array with the state mixed in, runs K
# sum-of-squares passes, saves the new state and rewrites the file it read.
import numpy as np

R, C, K, T = P["R"], P["C"], P["K"], P["T"]
LOWP = P.get("LOWP", 0)  # the control: products and sums held in bfloat16
N = R * C
M = P["M"]  # a python int: the shim compiles `% M` with a constant divisor
turn = np.array([T], dtype=np.int32)

if T == 1:
    state = np.arange(P["STATE_N"], dtype=np.int32)
    state %= M
else:
    state = np.array(np.load("state.npy"))

name = f"ws_{(T * 5 + M) % P['FILES']:02d}.bin"
data = open(name, "rb").read()
tag = sum(data[:4096]) % 251  # what this turn takes from the file it read

# The state's first C values, as a row that every row of the array gets.
w = (state[:C] % 16).astype(np.float32)
w /= np.array([64.0], dtype=np.float32)

a = np.arange(N, dtype=np.int32)
a %= M
a = a.astype(np.float32)
a /= float(M)
a = a.reshape(R, C) + w
if LOWP:
    import ml_dtypes

    a = a.astype(ml_dtypes.bfloat16)
c = np.array([1.0], dtype=a.dtype)
d = np.array([1e-4 * tag], dtype=a.dtype)
acc = 0.0
for k in range(K):
    # The array is written once and read once per pass: a * c + d is used
    # once, so nothing of its size has to be kept. Row by row: numpy's flat
    # float32 sum over 1.2e9 elements is itself off by up to 3e-4, pairwise
    # sums of rows of C are not.
    s = float(np.square(a * c + d).sum(axis=1).sum())
    acc += s
    c = np.array([(N / 3.0 / s) ** 0.5], dtype=a.dtype)
    d = np.array([1e-3 * (k + 1) + 1e-4 * tag], dtype=a.dtype)

state = (state * 3 + turn) % 65521
np.save("state.npy", np.asarray(state))
with open(name, "wb") as f:
    f.write(bytes((x + T) % 256 for x in data[:4096]) + data[4096:])
print(f"turn {T} read {name} tag {tag} state {int(np.asarray(state[:64]).sum())}")
print(f"session_turn R={R} C={C} K={K} M={M} last={s:.9e} acc={acc:.9e}")
