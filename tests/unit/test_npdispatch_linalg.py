"""What the deployment `npbench-linalg-1chip` asks of the numpy shim (ISSUE
37), on the CPU at small sizes over the threshold: NPBench's gemm, k3mm and
floyd_warshall against stock numpy; numpy's ufunc methods and attributes on
the shim's ufuncs (`np.add.outer`, `np.minimum.reduce`, `np.maximum.accumulate`,
`np.add.at`, `np.add.reduceat`, `np.add.nin`), on host arrays under the
threshold and on device arrays, the integer policy kept; and the three
counters a served turn is stamped with: contractions, their operations, and
ufunc methods that ran on the device. Nothing here times anything."""

import json
from pathlib import Path

import jax.numpy as jnp
import numpy as real_np
import pytest

from bee_code_interpreter_fs_tpu.ops import npdispatch
from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy
from bee_code_interpreter_fs_tpu.ops.npdispatch.shim import TpuArray

THRESHOLD = 1000
PAYLOADS = Path(__file__).resolve().parents[2] / "benchmarks" / "chip" / "payloads"


@pytest.fixture
def np_shim():
    npdispatch.install(threshold=THRESHOLD)
    import numpy as np

    lazy.counters.reset()
    yield np
    npdispatch.uninstall()


# -- the three kernels ---------------------------------------------------------

# NPBench's `initialize()` and `kernel()` of gemm, k3mm and floyd_warshall, this
# file's own copies (the benchmark's payloads are the benchmark's;
# `tests/chipbench` rehearses those). `P` holds the sizes and `LOWP` for the
# control in bfloat16; floyd_warshall's `initialize()` is vectorised and its
# loop runs over the first K vertices, as the deployment's.
SOURCES = {
    "gemm": """
import numpy as np
NI, NJ, NK = P["NI"], P["NJ"], P["NK"]
def initialize(NI, NJ, NK, datatype=np.float32):
    alpha = datatype(1.5)
    beta = datatype(1.2)
    C = np.fromfunction(lambda i, j: ((i * j + 1) % NI) / NI, (NI, NJ), dtype=datatype)
    A = np.fromfunction(lambda i, k: (i * (k + 1) % NK) / NK, (NI, NK), dtype=datatype)
    B = np.fromfunction(lambda k, j: (k * (j + 2) % NJ) / NJ, (NK, NJ), dtype=datatype)
    return alpha, beta, C, A, B
def kernel(alpha, beta, C, A, B):
    C[:] = alpha * A @ B + beta * C
alpha, beta, C, A, B = initialize(NI, NJ, NK)
if P.get("LOWP"):
    import ml_dtypes
    alpha, beta = ml_dtypes.bfloat16(alpha), ml_dtypes.bfloat16(beta)
    C, A, B = (a.astype(ml_dtypes.bfloat16) for a in (C, A, B))
kernel(alpha, beta, C, A, B)
""",
    "k3mm": """
import numpy as np
NI, NJ, NK, NL, NM = P["NI"], P["NJ"], P["NK"], P["NL"], P["NM"]
def initialize(NI, NJ, NK, NL, NM, datatype=np.float32):
    A = np.fromfunction(lambda i, j: ((i * j + 1) % NI) / (5 * NI), (NI, NK), dtype=datatype)
    B = np.fromfunction(lambda i, j: ((i * (j + 1) + 2) % NJ) / (5 * NJ), (NK, NJ), dtype=datatype)
    C = np.fromfunction(lambda i, j: (i * (j + 3) % NL) / (5 * NL), (NJ, NM), dtype=datatype)
    D = np.fromfunction(lambda i, j: ((i * (j + 2) + 2) % NK) / (5 * NK), (NM, NL), dtype=datatype)
    return A, B, C, D
def kernel(A, B, C, D):
    return A @ B @ C @ D
A, B, C, D = initialize(NI, NJ, NK, NL, NM)
if P.get("LOWP"):
    import ml_dtypes
    A, B, C, D = (a.astype(ml_dtypes.bfloat16) for a in (A, B, C, D))
G = kernel(A, B, C, D)
""",
    "floyd_warshall": """
import numpy as np
N, K, INF = P["N"], P["K"], P["INF"]
def initialize(N, datatype=np.int32):
    path = np.fromfunction(lambda i, j: i * j % 7 + 1, (N, N), dtype=datatype)
    s = np.fromfunction(lambda i, j: i + j, (N, N), dtype=datatype)
    return np.where((s % 13 == 0) | (s % 7 == 0) | (s % 11 == 0), datatype(INF), path)
def kernel(path):
    for k in range(K):
        path[:] = np.minimum(path[:], np.add.outer(path[:, k], path[k, :]))
path = initialize(N)
if P.get("LOWP"):
    import ml_dtypes
    path = path.astype(ml_dtypes.bfloat16)
kernel(path)
row_sums = path.sum(axis=1, dtype=path.dtype)
""",
}
# The sizes in the deployment's ratios; the arrays NPBench checks (and the
# row sums a floyd_warshall turn prints); the dots and ufunc methods of a turn.
KERNELS = {
    "gemm": ({"NI": 160, "NJ": 184, "NK": 208}, ("C",), 1, 0),
    "k3mm": ({"NI": 160, "NJ": 180, "NK": 200, "NL": 220, "NM": 240}, ("G",), 3, 0),
    "floyd_warshall": ({"N": 300, "K": 16, "INF": 997}, ("path", "row_sums"), 0, 16),
}
# float32 against float32, other order of the same arithmetic: the widest
# difference of an output array over its widest element (measured 3e-7 at
# these sizes); floyd_warshall is integers and has to be equal. The limits of
# the benchmark's payloads (`rel_limit`), which its control has to pass three
# times over.
LIMITS = {"gemm": 2e-5, "k3mm": 2e-5, "floyd_warshall": 0.0}


def run_kernel(name: str, params: dict, outputs) -> dict:
    """The kernel under whatever `import numpy` gives now; its output arrays by
    name, as host float64 (exact for int32 and bfloat16)."""
    scope = {"__name__": "__main__", "P": params}
    exec(compile(SOURCES[name], f"{name}.py", "exec"), scope)
    return {k: real_np.asarray(scope[k]).astype(real_np.float64) for k in outputs}


def widest_gap(got, want) -> float:
    return float(real_np.abs(got - want).max() / real_np.abs(want).max())


def floor_of(name: str, params: dict) -> dict:
    """The floor the benchmark's payload states, at these sizes."""
    spec = json.loads((PAYLOADS / f"{name}.json").read_text())
    return {bound: eval(expr, {"__builtins__": {}}, dict(params)) for bound, expr in spec["floor"].items()}


@pytest.fixture(scope="module")
def stock():
    """Each kernel under stock numpy, once."""
    return {name: run_kernel(name, params, outputs) for name, (params, outputs, _, _) in KERNELS.items()}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_under_the_shim_equals_stock_numpy(name, stock, np_shim):
    params, outputs, dots, methods = KERNELS[name]
    lazy._exec_cache.clear()
    got = run_kernel(name, params, outputs)
    for key in outputs:
        assert got[key].shape == stock[name][key].shape
        assert widest_gap(got[key], stock[name][key]) <= LIMITS[name], key
    taken = lazy.counters.take()
    assert (taken["dots"], taken["ufunc_methods"], taken["fallbacks"]) == (dots, methods, 0)
    assert taken["dot_flops"] == floor_of(name, params).get("flops", 0), "the counter reads the payload's floor"
    assert taken["h2d_bytes"] == 0, "every matrix is made on the device"
    # the same source again: the same programs, python scalars (k) in their keys and all
    run_kernel(name, params, outputs)
    again = lazy.counters.take()
    assert again["exec_cache_misses"] == 0 and again["programs"] == taken["programs"]
    assert (again["dots"], again["dot_flops"], again["ufunc_methods"]) == (dots, taken["dot_flops"], methods)


def test_floyd_warshalls_steps_are_one_program_in_int32(np_shim):
    """The sixteen steps, `path` itself and its row sums stay int32 on the
    device: the picks and the row sums are a program each, nothing else."""
    params, outputs, _, _ = KERNELS["floyd_warshall"]
    scope = {"__name__": "__main__", "P": params}
    exec(compile(SOURCES["floyd_warshall"], "floyd_warshall.py", "exec"), scope)
    path, row_sums = scope["path"], scope["row_sums"]
    assert isinstance(path, TpuArray) and path.dtype == real_np.int32 and row_sums.dtype == real_np.int32
    real_np.asarray(path[[1, 5], [7, 2]])
    first = lazy.counters.take()
    assert first["programs"] == 1 and first["ufunc_methods"] == 16 and first["nodes"] > 6 * 16
    real_np.asarray(row_sums)
    assert lazy.counters.take()["programs"] == 1


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_control_in_bfloat16_is_over_three_times_the_limit(name, stock, np_shim):
    params, outputs, _, _ = KERNELS[name]
    got = run_kernel(name, dict(params, LOWP=1), outputs)
    spec = json.loads((PAYLOADS / f"{name}.json").read_text())
    gap = max(widest_gap(got[key], stock[name][key]) for key in outputs)
    assert gap > 3 * spec["rel_limit"] and gap > 1e-3


# -- the contractions' operations ----------------------------------------------

M, K, N = 40, 50, 60
CONTRACTIONS = {
    "matmul": (lambda np, a, b, v: np.matmul(a, b), 1, 2 * M * K * N),
    "operator": (lambda np, a, b, v: a @ b, 1, 2 * M * K * N),
    "dot": (lambda np, a, b, v: np.dot(a, b), 1, 2 * M * K * N),
    "method": (lambda np, a, b, v: a.dot(b), 1, 2 * M * K * N),
    "matrix_vector": (lambda np, a, b, v: v @ b, 1, 2 * K * N),
    "inner": (lambda np, a, b, v: np.inner(a, a), 1, 2 * M * M * K),
    "tensordot": (lambda np, a, b, v: np.tensordot(a, b, axes=([1], [0])), 1, 2 * M * K * N),
    "einsum": (lambda np, a, b, v: np.einsum("ik,kj->ij", a, b), 1, 2 * M * K * N),
    "chain": (lambda np, a, b, v: (a @ b) @ b.T @ v, 3, 2 * (M * K * N + M * N * K + M * K)),
    "elementwise": (lambda np, a, b, v: a * 2.0 + 1.0, 0, 0),
}


@pytest.mark.parametrize("case", sorted(CONTRACTIONS))
def test_dots_and_their_operations_are_counted_per_execution(np_shim, case):
    call, dots, flops = CONTRACTIONS[case]
    np = np_shim
    a = np.fromfunction(lambda i, j: (i + j) % 5 / 5, (M, K), dtype=np.float32)
    b = np.fromfunction(lambda i, j: (i * j) % 3 / 3, (K, N), dtype=np.float32)
    v = np.fromfunction(lambda i: i % 4 / 4, (K,), dtype=np.float32)
    got = call(np, a, b, v)
    assert lazy.counters.take()["dots"] == 0, "a node that no program has executed is not counted"
    host = [real_np.asarray(x) for x in (a, b, v)]
    taken = lazy.counters.take()  # (a, b and v were computed: no dot among them)
    assert (taken["dots"], taken["dot_flops"]) == (0, 0)
    want = call(real_np, *host)
    assert real_np.allclose(real_np.asarray(got), want, rtol=1e-5)
    taken = lazy.counters.take()
    assert (taken["dots"], taken["dot_flops"]) == (dots, flops)
    real_np.asarray(got)
    assert lazy.counters.take()["dots"] == 0, "a node keeps its value: nothing runs twice"


# -- `%` of floats ---------------------------------------------------------------

# `initialize()`'s index arithmetic at the deployment's sizes: products of up
# to 2**29, whose float32 rounding the remainder has to carry as numpy's does.
INDEX = real_np.arange(23400, dtype=real_np.float32)
REMAINDERS = {
    "gemm.B.column": (INDEX * real_np.float32(20700.0), real_np.float32(20700.0)),
    "gemm.C.row": (INDEX * real_np.float32(17999.0) + real_np.float32(1.0), real_np.float32(18000.0)),
    "k3mm.D.row": (INDEX * real_np.float32(11998.0) + real_np.float32(2.0), real_np.float32(10000.0)),
    "negative.x": (-(INDEX * real_np.float32(20698.0)), real_np.float32(20700.0)),
    "negative.y": (INDEX * real_np.float32(10352.0), real_np.float32(-20700.0)),
    "negative.both": (-(INDEX * real_np.float32(10352.0)), real_np.float32(-20700.0)),
    "small": (real_np.arange(-2000, 2000, dtype=real_np.float32) / 8, real_np.float32(7.5)),
    "array.divisor": (INDEX * real_np.float32(17999.0), 1000 + INDEX % 977),
    "special": (real_np.array([4, -4, 5, -5, 5, real_np.inf, 3, -0.0, 0.0, 7.5, -7.5, real_np.nan, 1e30] * 100, real_np.float32),
                real_np.array([-2, 2, real_np.inf, real_np.inf, -real_np.inf, 3, 0, 3, -3, -2, 2, 1, 3] * 100, real_np.float32)),
}


def same_floats(got, want) -> bool:
    """Bit for bit, -0.0 apart from 0.0; a NaN equal to a NaN of either sign."""
    got, want = real_np.asarray(got), real_np.asarray(want)
    nans = real_np.isnan(got) & real_np.isnan(want)
    signs = (real_np.signbit(got) == real_np.signbit(want)) | nans
    return got.dtype == want.dtype and bool(((got == want) | nans).all()) and bool(signs.all())


@pytest.mark.parametrize("spelling", ["operator", "mod", "remainder"])
@pytest.mark.parametrize("case", sorted(REMAINDERS))
def test_a_float_remainder_on_the_device_is_numpys_bit_for_bit(np_shim, case, spelling):
    x, y = REMAINDERS[case]
    with real_np.errstate(all="ignore"):
        want = real_np.remainder(x, y)
    dx = np_shim.asarray(x) * 1  # a device array
    dy = np_shim.asarray(y) * 1 if isinstance(y, real_np.ndarray) else y
    assert isinstance(dx, TpuArray)
    got = {"operator": lambda: dx % dy, "mod": lambda: np_shim.mod(dx, dy), "remainder": lambda: np_shim.remainder(dx, dy)}[spelling]()
    assert isinstance(got, TpuArray) and same_floats(got, want)
    assert lazy.counters.take()["fallbacks"] == 0


@pytest.mark.parametrize("off_by", [-1, 0, 1])
def test_the_remainder_is_exact_from_a_quotient_that_is_off(off_by):
    """What the chip's division gives (PERF.md, PR 37): a quotient off the
    truncated one, where numpy's fmod is exact. (A correctly rounded division
    is itself one over wherever `x / y` rounds up to a whole number: two off
    in all here, which is what `_fmod_of_magnitudes` promises.)"""
    from bee_code_interpreter_fs_tpu.ops.npdispatch import shim

    for name in ("gemm.B.column", "gemm.C.row", "k3mm.D.row"):
        x, y = REMAINDERS[name]
        q = jnp.maximum(jnp.trunc(jnp.asarray(x) / y) + off_by, 0)
        assert same_floats(shim._fmod_of_magnitudes(jnp.asarray(x), jnp.asarray(y), q), real_np.fmod(x, y)), name


def test_integers_and_narrow_floats_keep_jnps_remainder(np_shim):
    whole = operand(np_shim, BIG, "int32") - 6
    assert same_floats(whole % 5, (operand(real_np, BIG, "int32") - 6) % 5)
    half = operand(np_shim, BIG, "float16")
    assert (half % 5).dtype == real_np.float16


# -- a ufunc's methods and attributes ------------------------------------------

SMALL, BIG = 8, THRESHOLD * 2


def operand(np, n: int, dtype: str, k: int = 3):
    """Whole numbers under 13, exact in every dtype here; a TpuArray at BIG."""
    return (np.arange(n, dtype="float32") * k % 13).astype(dtype)


# (the call under whatever `np` is; on device operands: a ufunc method that
# runs there, else a fallback)
METHODS = {
    "add.outer": (lambda np, a, b: np.add.outer(a[:50], b[:40]), True),
    "multiply.outer": (lambda np, a, b: np.multiply.outer(a[:50], b[:40]), True),
    "minimum.outer": (lambda np, a, b: np.minimum.outer(a[:50], b[:40]), True),
    "add.reduce": (lambda np, a, b: np.add.reduce(a.astype("float32")), True),
    "add.reduce.axis": (lambda np, a, b: np.add.reduce(np.add.outer(a[:50], b[:40]).astype("float32"), axis=1, keepdims=True), True),
    "minimum.reduce": (lambda np, a, b: np.minimum.reduce(a), True),
    "maximum.reduce.initial": (lambda np, a, b: np.maximum.reduce(a, initial=5), True),
    "maximum.accumulate": (lambda np, a, b: np.maximum.accumulate(a), True),
    "add.accumulate": (lambda np, a, b: np.add.accumulate(a.astype("float32")), True),
    "logical_or.reduce": (lambda np, a, b: np.logical_or.reduce(a > 11), True),
    "subtract.outer": (lambda np, a, b: np.subtract.outer(a[:50], b[:40]), True),
    "add.reduceat": (lambda np, a, b: np.add.reduceat(a, [0, 4, 6]), False),
    "divide.outer": (lambda np, a, b: np.divide.outer(a[:50].astype("float32"), 1 + b[:40].astype("float32")), False),
    "hypot.reduce": (lambda np, a, b: np.hypot.reduce(a.astype("float32")), False),
    "add.reduce.out": (lambda np, a, b: np.add.reduce(a[:8].astype("float32").reshape(2, 4), axis=0, out=real_np.zeros(4, "float32")), False),
}


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("size", [SMALL, BIG])
@pytest.mark.parametrize("case", sorted(METHODS))
def test_a_ufuncs_method_returns_what_numpys_returns(np_shim, case, size, dtype):
    call, on_device = METHODS[case]
    want = call(real_np, operand(real_np, size, dtype), operand(real_np, size, dtype, 5))
    a, b = operand(np_shim, size, dtype), operand(np_shim, size, dtype, 5)
    assert isinstance(a, TpuArray) == (size == BIG)
    real_np.asarray(a), real_np.asarray(b)
    lazy.counters.take()
    got = call(np_shim, a, b)
    host = real_np.asarray(got)
    assert host.shape == want.shape and real_np.array_equal(host, want)
    assert host.dtype == want.dtype or (want.dtype == real_np.float64 and host.dtype == real_np.float32)
    taken = lazy.counters.take()
    if size == SMALL:
        assert not isinstance(got, TpuArray) and (taken["ufunc_methods"], taken["fallbacks"], taken["programs"]) == (0, 0, 0)
    elif on_device:
        assert isinstance(got, TpuArray) and taken["ufunc_methods"] >= 1 and taken["fallbacks"] == 0
    else:
        assert taken["ufunc_methods"] == 0 and taken["fallbacks"] >= 1, "numpy on host copies: correct, and counted"


# numpy promotes these accumulators to the platform integer (`np.add.reduce` of
# int32 is `np.sum` of it): exact on the host, as `sum` / `cumsum`; an explicit
# narrow dtype, and every reduction that promotes nothing, on the device.
INT_REDUCTIONS = {
    "add.reduce": (lambda np, a: np.add.reduce(a), "int64", False),
    "multiply.reduce": (lambda np, a: np.multiply.reduce(a[:12] + 1), "int64", False),
    "add.accumulate": (lambda np, a: np.add.accumulate(a), "int64", False),
    "multiply.accumulate": (lambda np, a: np.multiply.accumulate(a[:12] + 1), "int64", False),
    "add.reduce.int64": (lambda np, a: np.add.reduce(a, dtype=np.int64), "int64", False),
    "add.reduce.int32": (lambda np, a: np.add.reduce(a, dtype=np.int32), "int32", True),
    "minimum.reduce": (lambda np, a: np.minimum.reduce(a), "int32", True),
    "maximum.accumulate": (lambda np, a: np.maximum.accumulate(a), "int32", True),
    "add.outer": (lambda np, a: np.add.outer(a[:30], a[:30]), "int32", True),
}


@pytest.mark.parametrize("case", sorted(INT_REDUCTIONS))
def test_an_int32_reduction_promotes_as_sum_does(np_shim, case):
    call, dtype, on_device = INT_REDUCTIONS[case]
    want = call(real_np, operand(real_np, BIG, "int32") + 2**20)
    a = operand(np_shim, BIG, "int32") + 2**20  # BIG of them pass 2**31: an int32 accumulator would wrap
    got = call(np_shim, a)
    assert isinstance(got, TpuArray) == on_device
    assert real_np.asarray(got).dtype == want.dtype == real_np.dtype(dtype)
    assert real_np.array_equal(real_np.asarray(got), want)


ATTRIBUTES = ["nin", "nout", "nargs", "ntypes", "types", "identity", "signature", "__name__"]


@pytest.mark.parametrize("ufunc", ["add", "minimum", "logical_and", "sqrt", "arctan2", "matmul"])
@pytest.mark.parametrize("attribute", ATTRIBUTES)
def test_a_ufuncs_attributes_are_numpys(np_shim, ufunc, attribute):
    assert getattr(getattr(np_shim, ufunc), attribute) == getattr(getattr(real_np, ufunc), attribute)


def test_a_shim_ufunc_is_callable_and_no_instance_of_numpys_type(np_shim):
    """Stated in `_UfuncDispatcher`'s docstring: numpy's ufunc type cannot be
    subclassed; what is promised is the methods and the attributes."""
    assert callable(np_shim.add) and not isinstance(np_shim.add, real_np.ufunc)
    assert np_shim.add.resolve_dtypes((real_np.dtype("i4"), real_np.dtype("f4"), None))[2] == real_np.float64
    with pytest.raises(AttributeError):
        np_shim.add.no_such_attribute
    with pytest.raises(AttributeError):
        np_shim.sum.outer  # no ufunc, under numpy neither
    with pytest.raises(ValueError):
        np_shim.add.reduce(real_np.ones(3), axis=2)  # numpy's own error, on a host array
    with pytest.raises(RuntimeError):
        np_shim.matmul.reduce(operand(np_shim, BIG, "float32"))  # and on host copies of a device array


# `at`: in place, repeated indices accumulate. (the call; whether a TpuArray
# target's update runs on the device)
AT = {
    "add.list": (lambda np, a: np.add.at(a, [0, 0, 2], 1.0), True),
    "add.int64_array": (lambda np, a: np.add.at(a, real_np.array([1, 1, 1, 7]), real_np.array([1.0, 2.0, 3.0, 4.0], "float32")), True),
    "add.slice": (lambda np, a: np.add.at(a, slice(2, 6), 2.0), True),
    "maximum.list": (lambda np, a: np.maximum.at(a, [3, 3, 4], real_np.array([20.0, 30.0, 1.0], "float32")), True),
    "multiply.list": (lambda np, a: np.multiply.at(a, [5, 5], 3.0), True),
    "subtract.tuple": (lambda np, a: np.subtract.at(a, ([7, 7],), real_np.array([1.0, 0.5], "float32")), True),
    "minimum.device_values": (lambda np, a: np.minimum.at(a, [2, 2], np.asarray([4.0, 5.0], dtype="float32") * 1.0), True),
    "negative.unary": (lambda np, a: np.negative.at(a, [0, 0, 1]), False),  # (no ufunc the shim dispatches)
    "sqrt.unary": (lambda np, a: np.sqrt.at(a, [3, 4]), False),
    "divide.list": (lambda np, a: np.divide.at(a, [6, 6], 2.0), False),
}


@pytest.mark.parametrize("size", [SMALL, BIG])
@pytest.mark.parametrize("case", sorted(AT))
def test_at_updates_in_place_and_repeated_indices_accumulate(np_shim, case, size):
    call, on_device = AT[case]
    want = operand(real_np, size, "float32")
    assert call(real_np, want) is None
    target = operand(np_shim, size, "float32")
    real_np.asarray(target)
    lazy.counters.take()
    alias = target
    assert call(np_shim, target) is None
    assert alias is target and isinstance(target, TpuArray) == (size == BIG)
    assert real_np.array_equal(real_np.asarray(target), want)
    taken = lazy.counters.take()
    if size == SMALL:
        assert (taken["ufunc_methods"], taken["fallbacks"]) == (0, 0)
    else:
        assert (taken["ufunc_methods"], taken["fallbacks"]) == ((1, 0) if on_device else (0, 1))


def test_at_on_two_dimensions_and_on_a_host_target_with_device_values(np_shim):
    np = np_shim
    want = real_np.zeros((50, 40), "float32")
    real_np.add.at(want, ([1, 1, 2], [3, 3, 4]), 1.5)
    grid = np.zeros((50, 40), dtype="float32")
    np.add.at(grid, ([1, 1, 2], [3, 3, 4]), 1.5)
    assert isinstance(grid, TpuArray) and real_np.array_equal(real_np.asarray(grid), want)
    # an ndarray target stays the caller's array, updated by numpy itself
    host, values = real_np.zeros(BIG, "float32"), operand(np, BIG, "float32")
    lazy.counters.take()
    np.add.at(host, real_np.arange(BIG) % 4, values)
    assert type(host) is real_np.ndarray and host[:4].sum() == real_np.asarray(values).sum()
    assert lazy.counters.take()["fallbacks"] == 1, "host copies of a device array: counted"
    # a value of another kind than the target's is cast numpy's way, by numpy
    whole, want = operand(np, BIG, "int32"), operand(real_np, BIG, "int32")
    np.add.at(whole, [0, 1, 1], 1.5)
    real_np.add.at(want, [0, 1, 1], 1.5)
    assert real_np.array_equal(real_np.asarray(whole), want) and lazy.counters.take()["fallbacks"] == 1


def test_a_method_that_jnp_runs_eagerly_is_counted_where_it_ran(np_shim):
    """`where=` is an array among the keyword arguments: no node of the graph
    takes it, so the method runs eagerly, on the device, and is counted then."""
    np = np_shim
    a = operand(np, BIG, "float32")
    mask = a > 5
    real_np.asarray(a), real_np.asarray(mask)
    lazy.counters.take()
    got = np.add.reduce(a, where=mask)
    taken = lazy.counters.take()
    assert isinstance(got, TpuArray) and (taken["ufunc_methods"], taken["fallbacks"]) == (1, 0)
    host = real_np.asarray(a)
    assert float(got) == real_np.add.reduce(host, where=host > 5)


def test_the_methods_nodes_are_the_jnp_ufuncs_own(np_shim):
    np = np_shim
    a = operand(np, BIG, "float32")
    node = np.add.outer(a[:30], a[:40])._node
    assert node.op_name == "add.outer" and node.fn == jnp.add.outer and tuple(node.aval.shape) == (30, 40)
