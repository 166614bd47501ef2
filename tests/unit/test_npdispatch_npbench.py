"""What the deployment `npbench-1chip` asks of the numpy shim (ISSUE 31), on
the CPU at small sizes: NPBench's jacobi_2d, fdtd_2d and gemver under the shim
against stock numpy, every output array; `np.fromfunction` / `np.indices`
built on the device; dead leaves donated, held ones never; and the counters
of what the shim did. Nothing here times anything."""

import functools
import gc

import jax
import ml_dtypes
import numpy as real_np
import pytest

from bee_code_interpreter_fs_tpu.ops import npdispatch
from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy, stencil
from bee_code_interpreter_fs_tpu.ops.npdispatch.shim import TpuArray

THRESHOLD = 1000
N = THRESHOLD * 4


@pytest.fixture
def np_shim():
    npdispatch.install(threshold=THRESHOLD)
    import numpy as np

    lazy.counters.reset()
    yield np
    npdispatch.uninstall()


# -- the three kernels ---------------------------------------------------------

# NPBench's `initialize()` and `kernel()` of jacobi_2d, fdtd_2d and gemver in
# float32, this file's own copies (the benchmark's payloads are the
# benchmark's; `tests/chipbench` rehearses those). `P` holds the sizes, one
# data constant `C` / `ALPHA`, and `LOWP` for the control in bfloat16. gemver's
# `kernel()` returns nothing, as the source's: its caller reads x and w.
SOURCES = {
    "jacobi_2d": """
import numpy as np
def initialize(N, C):
    A = np.fromfunction(lambda i, j: i * (j + C) / N, (N, N), dtype=np.float32)
    B = np.fromfunction(lambda i, j: i * (j + 3) / N, (N, N), dtype=np.float32)
    return A, B
def kernel(TSTEPS, A, B):
    for t in range(1, TSTEPS):
        B[1:-1, 1:-1] = 0.2 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] +
                               A[2:, 1:-1] + A[:-2, 1:-1])
        A[1:-1, 1:-1] = 0.2 * (B[1:-1, 1:-1] + B[1:-1, :-2] + B[1:-1, 2:] +
                               B[2:, 1:-1] + B[:-2, 1:-1])
A, B = initialize(P["N"], P["C"])
if P.get("LOWP"):
    import ml_dtypes
    A, B = A.astype(ml_dtypes.bfloat16), B.astype(ml_dtypes.bfloat16)
kernel(P["TSTEPS"], A, B)
""",
    "fdtd_2d": """
import numpy as np
def initialize(TMAX, NX, NY, C):
    ex = np.fromfunction(lambda i, j: (i * (j + C)) / NX, (NX, NY), dtype=np.float32)
    ey = np.fromfunction(lambda i, j: (i * (j + 2)) / NY, (NX, NY), dtype=np.float32)
    hz = np.fromfunction(lambda i, j: (i * (j + 3)) / NX, (NX, NY), dtype=np.float32)
    _fict_ = np.fromfunction(lambda i: i, (TMAX, ), dtype=np.float32)
    return ex, ey, hz, _fict_
def kernel(TMAX, ex, ey, hz, _fict_):
    for t in range(TMAX):
        ey[0, :] = _fict_[t]
        ey[1:, :] -= 0.5 * (hz[1:, :] - hz[:-1, :])
        ex[:, 1:] -= 0.5 * (hz[:, 1:] - hz[:, :-1])
        hz[:-1, :-1] -= 0.7 * (ex[:-1, 1:] - ex[:-1, :-1] + ey[1:, :-1] -
                               ey[:-1, :-1])
ex, ey, hz, _fict_ = initialize(P["TMAX"], P["NX"], P["NY"], P["C"])
if P.get("LOWP"):
    import ml_dtypes
    ex, ey, hz, _fict_ = (f.astype(ml_dtypes.bfloat16) for f in (ex, ey, hz, _fict_))
kernel(P["TMAX"], ex, ey, hz, _fict_)
""",
    "gemver": """
import numpy as np
def initialize(N, ALPHA):
    alpha, beta, fn = np.float32(ALPHA), np.float32(1.2), np.float32(N)
    A = np.fromfunction(lambda i, j: (i * j % N) / N, (N, N), dtype=np.float32)
    u1 = np.fromfunction(lambda i: i, (N, ), dtype=np.float32)
    u2 = np.fromfunction(lambda i: ((i + 1) / fn) / 2.0, (N, ), dtype=np.float32)
    v1 = np.fromfunction(lambda i: ((i + 1) / fn) / 4.0, (N, ), dtype=np.float32)
    v2 = np.fromfunction(lambda i: ((i + 1) / fn) / 6.0, (N, ), dtype=np.float32)
    w = np.zeros((N, ), dtype=np.float32)
    x = np.zeros((N, ), dtype=np.float32)
    y = np.fromfunction(lambda i: ((i + 1) / fn) / 8.0, (N, ), dtype=np.float32)
    z = np.fromfunction(lambda i: ((i + 1) / fn) / 9.0, (N, ), dtype=np.float32)
    return alpha, beta, A, u1, v1, u2, v2, w, x, y, z
def kernel(alpha, beta, A, u1, v1, u2, v2, w, x, y, z):
    A += np.outer(u1, v1) + np.outer(u2, v2)
    x += beta * y @ A + z
    w += alpha * A @ x
alpha, beta, A, u1, v1, u2, v2, w, x, y, z = initialize(P["N"], P["ALPHA"])
if P.get("LOWP"):
    import ml_dtypes
    alpha, beta = ml_dtypes.bfloat16(alpha), ml_dtypes.bfloat16(beta)
    A, u1, v1, u2, v2, w, x, y, z = (
        a.astype(ml_dtypes.bfloat16) for a in (A, u1, v1, u2, v2, w, x, y, z))
kernel(alpha, beta, A, u1, v1, u2, v2, w, x, y, z)
""",
}
# A few hundred points a side; the arrays NPBench checks.
KERNELS = {
    "jacobi_2d": ({"N": 200, "TSTEPS": 14, "C": 4}, ("A", "B")),
    "fdtd_2d": ({"TMAX": 12, "NX": 200, "NY": 260, "C": 4}, ("ex", "ey", "hz")),
    "gemver": ({"N": 300, "ALPHA": 1.25}, ("A", "x", "w")),
}
# float32 against float32, other order of the same arithmetic: the widest
# difference of an output array over its widest element. Measured 4e-7 at these
# sizes; bfloat16 storage reads 2e-3 and more.
FLOAT32_LIMIT = 1e-5
BFLOAT16_FLOOR = 1e-3


def run_kernel(name: str, params: dict, outputs) -> dict:
    """The kernel under whatever `import numpy` gives now; its output arrays by
    name, as host float64."""
    scope = {"__name__": "__main__", "P": params}
    exec(compile(SOURCES[name], f"{name}.py", "exec"), scope)
    return {k: real_np.asarray(scope[k]).astype(real_np.float64) for k in outputs}


def widest_gap(got, want) -> float:
    return float(real_np.abs(got - want).max() / real_np.abs(want).max())


@pytest.fixture(scope="module")
def stock():
    """Each kernel under stock numpy, once."""
    return {name: run_kernel(name, params, outputs) for name, (params, outputs) in KERNELS.items()}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_under_the_shim_equals_stock_numpy(name, stock, np_shim):
    params, outputs = KERNELS[name]
    got = run_kernel(name, params, outputs)
    for key in outputs:
        assert got[key].shape == stock[name][key].shape
        assert widest_gap(got[key], stock[name][key]) <= FLOAT32_LIMIT, key
    taken = lazy.counters.take()
    assert taken["programs"] >= 1 and taken["nodes"] >= 10
    # Creation is on the device: nothing but vectors under the threshold crosses
    # (gemver's u1, v1, u2, v2, y, z; x as zeros and as the first product; w).
    assert taken["h2d_bytes"] <= 9 * 4 * max(v for v in params.values() if isinstance(v, int))
    assert taken["fallbacks"] == 0


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_control_in_bfloat16_is_outside_the_limit(name, stock, np_shim):
    params, outputs = KERNELS[name]
    got = run_kernel(name, dict(params, LOWP=1), outputs)
    assert all(widest_gap(got[key], stock[name][key]) > BFLOAT16_FLOOR for key in outputs)


@pytest.mark.parametrize("name", ["jacobi_2d", "fdtd_2d"])
def test_a_time_loop_is_flushed_once_and_runs_nothing_twice(name, np_shim):
    """The loop crosses the node cap once; every node of the graph is
    executed once (the arrays still pending at the flush come back as outputs
    and are not computed again), and the grids are donated."""
    params, outputs = KERNELS[name]
    lazy.counters.reset()
    lazy._exec_cache.clear()
    run_kernel(name, params, outputs)
    first = lazy.counters.take()
    run_kernel(name, params, outputs)
    again = lazy.counters.take()
    assert first["flushes"] == again["flushes"] == 1
    steps = 2 * 11 * (params["TSTEPS"] - 1) if name == "jacobi_2d" else 26 * params["TMAX"]
    assert steps < first["nodes"] < steps + 60  # creation besides
    assert 2 <= first["exec_cache_misses"] <= first["programs"] and again["exec_cache_misses"] == 0
    assert again["programs"] == first["programs"] and again["nodes"] == first["nodes"]
    assert first["donated_bytes"] >= len(outputs) * 4 * 200 * 200


# -- a window store over the array's full shape -----------------------------------

SIDE = 384  # the benchmark's `rehearse` grid: over the shim's own threshold too


def ints(np, shape, k=3, dtype="float32"):
    """Small whole numbers, so that every case below is exact in its dtype
    and equality with stock numpy is equality of bits."""
    return np.fromfunction(lambda *at: sum((k + d) * i for d, i in enumerate(at)) % 7, shape, dtype=dtype)


def _jacobi(np, shim):
    A, B = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    for _ in range(2):
        B[1:-1, 1:-1] = 0.25 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] + A[2:, 1:-1] + A[:-2, 1:-1])
        A[1:-1, 1:-1] = 0.25 * (B[1:-1, 1:-1] + B[1:-1, :-2] + B[1:-1, 2:] + B[2:, 1:-1] + B[:-2, 1:-1])
    return A, B


def _fdtd(np, shim):
    ex, ey, hz = (ints(np, (340, 442), k) for k in (3, 4, 5))
    fict = np.fromfunction(lambda i: i, (2,), dtype="float32")
    for t in range(2):
        ey[0, :] = fict[t]
        ey[1:, :] -= 0.5 * (hz[1:, :] - hz[:-1, :])
        ex[:, 1:] -= 0.5 * (hz[:, 1:] - hz[:, :-1])
        hz[:-1, :-1] -= 0.75 * (ex[:-1, 1:] - ex[:-1, :-1] + ey[1:, :-1] - ey[:-1, :-1])
    return ex, ey, hz


def _two_arrays(np, shim):
    a, b, c = (ints(np, (SIDE, SIDE), k) for k in (3, 4, 5))
    c[1:-1, 1:-1] = a[1:-1, 1:-1] * 0.5 + b[2:, :-2]
    return (c,)


def _rank1(np, shim):
    a = ints(np, (SIDE * SIDE,))
    a[1:-1] = 0.5 * (a[:-2] + a[2:])  # reads the values it overwrites: the old ones
    return (a,)


def _rank3(np, shim):
    a, b = ints(np, (40, 48, 80)), ints(np, (40, 48, 80), 4)
    b[1:-1, 1:-1, 1:-1] = 0.25 * (a[2:, 1:-1, 1:-1] + a[:-2, 1:-1, 1:-1] + a[1:-1, 2:, 1:-1] + a[1:-1, 1:-1, :-2])
    return (b,)


def _bfloat16(np, shim):
    import ml_dtypes

    A, B = (ints(np, (SIDE, SIDE), k).astype(ml_dtypes.bfloat16) for k in (3, 4))
    B[1:-1, 1:-1] = 0.25 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] + A[2:, 1:-1] + A[:-2, 1:-1])
    return (B,)


def _every_kind_of_operand(np, shim):
    """Unary and comparison operators, a power, a numpy scalar, a 0-d array
    (computed as ever: `a[2, 3]` is no window), a store into an integer grid,
    and a window that is the whole array."""
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    b[:-2, 2:] = -abs(a[1:-1, 1:-1] - 3.0) ** 2 * np.float32(0.5) + a[2, 3] * a[2:, :-2]
    k, m = ints(np, (SIDE, SIDE), 5, "int32"), ints(np, (SIDE, SIDE), 6, "int32")
    m[1:, 1:] = (k[1:, 1:] << 2) % 5 + ~k[:-1, :-1] // 3
    m[:, :] = m[:, :] - k[:, :]
    return b, m


def _scalar(np, shim):
    a = ints(np, (SIDE, SIDE))
    a[1:-1, 1:-1] = 3.0
    return (a,)


def _row_broadcast(np, shim):
    a, row = ints(np, (SIDE, SIDE)), ints(np, (SIDE * SIDE,), 4)
    a[1:-1, 1:-1] = row[1:SIDE - 1]
    a[0, :] = row[5]
    return (a,)


def _strided(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    a[1:-1:2, 1:-1] = 0.5 * b[1:-1:2, 1:-1]
    a[::-1, :] = 0.5 * b[::-1, :]
    return (a,)


def _reduced_rank(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    a[1:-1] = 0.5 * b[1:-1]
    a[3, 1:] = b[4, 1:] + b[5, :-1]
    a[..., 1:] = 0.5 * b[..., 1:]
    return (a,)


def _under_half(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    a[:SIDE // 2, :-1] = 0.5 * (b[:SIDE // 2, :-1] + b[:SIDE // 2, 1:])
    return (a,)


def _another_shape(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    wider, patch = ints(np, (SIDE, SIDE + 2), 5), ints(np, (SIDE - 2, SIDE - 2), 6)
    a[1:-1, 1:-1] = b[1:-1, 1:-1] + wider[1:-1, 1:SIDE - 1]  # a window of an array of another shape
    b[1:-1, 1:-1] = b[1:-1, 1:-1] + patch  # a whole array of the window's shape
    b[1:-1, 1:-1] = b[1:-1, 1:-1] * a[0, 1:-1]  # a row, which broadcasts
    return a, b


def _no_operator(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    a[1:-1, 1:-1] = np.where(b[1:-1, 1:-1] > 2, b[2:, 1:-1], 0.0)
    b[1:-1, 1:-1] = a[1:-1, 1:-1] - np.sum(a[1:-1, 1:-1], axis=0)
    b[1:-1, 1:-1] = np.cumsum(b[1:-1, 1:-1], axis=1) % 7 + a[1:-1, 1:-1]
    a[:, :] = (a[:, :] % 3) @ (b[:, :] % 3)  # an operator, and no element-wise one
    return a, b


def _a_view_still_held(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    view = b[1:-1, 1:-1] + b[2:, 2:]
    a[1:-1, 1:-1] = 0.5 * view
    b[1:-1, 1:-1] = 0.5 * (a[1:-1, 1:-1] + a[2:, 2:])  # nobody holds these windows
    return a, view * 1.0, b


def _a_second_name(np, shim):
    a, b = ints(np, (SIDE, SIDE)), ints(np, (SIDE, SIDE), 4)
    assert float(a[0, 1]) == 4.0  # a is computed, and a buffer of its own
    # What a second wrapper of the same buffer is to the shim (np.asarray of a
    # TpuArray: numpy itself would hand back the one array; the shim's contract
    # is a copy), a copy is to numpy.
    other = np.asarray(a) if shim else a.copy()
    a[1:-1, 1:-1] = 0.5 * (b[1:-1, 1:-1] + a[2:, 1:-1])
    return a, other


# name: (the statements, the window stores a program runs at the full shape)
WINDOW_STORES = {
    "jacobi": (_jacobi, 4), "fdtd": (_fdtd, 6), "two arrays": (_two_arrays, 1), "rank 1": (_rank1, 1),
    "rank 3": (_rank3, 1), "bfloat16": (_bfloat16, 1), "every kind of operand": (_every_kind_of_operand, 3),
    "a scalar": (_scalar, 0), "a row broadcast": (_row_broadcast, 0), "a strided window": (_strided, 0),
    "a reduced-rank index": (_reduced_rank, 0), "under half the array": (_under_half, 0),
    "an operand of another shape": (_another_shape, 0), "no element-wise operator": (_no_operator, 0),
    "a view still held": (_a_view_still_held, 1), "a second name": (_a_second_name, 1),
}


@pytest.mark.parametrize("name", sorted(WINDOW_STORES))
def test_a_window_store_equals_stock_numpys(name, np_shim):
    """`a[1:-1, 1:-1] = f(b[...])` over the shapes `lazy._full_shape_plan`
    tells apart: to the bit what stock numpy gives, whether it runs as one
    select over the array's full shape or as the slices and the scatter it was
    before; the counter says which."""
    statements, aligned = WINDOW_STORES[name]
    want = [real_np.asarray(x) for x in statements(real_np, False)]
    lazy._exec_cache.clear()
    lazy.counters.reset()
    got = statements(np_shim, True)
    assert all(isinstance(x, TpuArray) for x in got)
    for g, w in zip(got, want, strict=True):
        g = real_np.asarray(g)
        assert g.dtype == w.dtype and real_np.array_equal(g, w)
    taken = lazy.counters.take()
    assert taken["aligned_stores"] == aligned and taken["fallbacks"] == 0
    if name == "a second name":
        assert taken["donated_bytes"] == 4 * SIDE * SIDE, "b's buffer, dead after the store; never a's"
    # the same statements again: the same programs, and counted per execution
    for g in statements(np_shim, True):
        real_np.asarray(g)
    again = lazy.counters.take()
    assert again["aligned_stores"] == aligned and again["exec_cache_misses"] == 0


def test_the_elementwise_operators_are_the_operator_tables_less_the_contraction():
    """Collected where `TpuArray`'s operators are defined, so that a new
    operator is one; `isinstance(fn, jnp.ufunc)` would miss three of them."""
    import jax.numpy as jnp

    from bee_code_interpreter_fs_tpu.ops.npdispatch.shim import _BINOPS, _UNOPS

    ops = lazy.ELEMENTWISE_OPS
    assert {id(fn) for fn in ops} == {id(fn) for fn in (*_BINOPS.values(), *_UNOPS.values())} - {id(jnp.matmul)}
    assert len(_BINOPS) == 19 and len(_UNOPS) == 4 and len({id(fn) for fn in ops}) == 22
    for fn in (jnp.true_divide, jnp.power, jnp.abs, jnp.subtract, jnp.invert):
        assert any(fn is op for op in ops)


def test_a_full_shape_store_leaves_no_scatter_and_no_window_in_the_program(np_shim):
    """What the runner is traced to, as jax sees it: pads and a select, where
    today's lowering has five slices and a scatter."""
    from bee_code_interpreter_fs_tpu.ops.npdispatch.lazy import _full_shape_plan, _make_runner

    A, B = ints(np_shim, (SIDE, SIDE)), ints(np_shim, (SIDE, SIDE), 4)
    B[1:-1, 1:-1] = 0.25 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] + A[2:, 1:-1] + A[:-2, 1:-1])
    lin = lazy._Linear([B._node])
    out = [len(lin.spec) - 1]
    shifts, stores, kernels = _full_shape_plan(lin, out)
    assert kernels == {}  # no TPU named: the select, as on any CPU
    assert sorted(shifts.values()) == [(-1, 0), (0, -1), (0, 0), (0, 1), (1, 0)]
    assert list(stores.values()) == [((1, 1), (SIDE - 2, SIDE - 2))]
    new = str(jax.make_jaxpr(_make_runner(lin.spec, out, shifts, stores, {}))(*lin.leaves))
    old = str(jax.make_jaxpr(_make_runner(lin.spec, out, {}, {}, {}))(*lin.leaves))
    assert "scatter" in old and "slice" in old and f"{SIDE - 2},{SIDE - 2}" in old
    assert "scatter" not in new and "slice" not in new and f"{SIDE - 2},{SIDE - 2}" not in new
    assert new.count(" pad[") == 4 and "select_n" in new


# -- a window store as one kernel ----------------------------------------------------

ROWS, LANES = 64, 384  # whole registers, four row blocks of 16 rows, and a row of more than its two edges


def _as_on_a_tpu(patch):
    """The plan is told its leaves live on a TPU and the kernel is built for
    the interpreter: a CPU has neither the device nor Mosaic."""
    patch.setattr(lazy, "_platform", lambda leaves: "tpu")
    patch.setattr(lazy, "window_store", functools.partial(stencil.window_store, interpret=True))


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    _as_on_a_tpu(monkeypatch)


def grids(np, n, shape=(ROWS, LANES), dtype="float32"):
    return [ints(np, shape, 3 + k, dtype) for k in range(n)]


def _k_jacobi_half_step(np):
    A, B = grids(np, 2)
    B[1:-1, 1:-1] = 0.25 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] + A[2:, 1:-1] + A[:-2, 1:-1])
    return A, B


def _k_jacobi_two_chunks(np):
    """A row of 2304 lanes is two chunks of 1152, with a read across their seam."""
    A, B = grids(np, 2, (32, 2304))
    B[1:-1, 1:-1] = 0.25 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] + A[2:, 1:-1] + A[:-2, 1:-1])
    return A, B


def _k_fdtd_ey(np):
    ey, hz = grids(np, 2)
    fict = np.fromfunction(lambda i: i + 2, (3,), dtype="float32")
    ey[0, :] = fict[1]  # no window store: a row in place, as ever
    ey[1:, :] -= 0.5 * (hz[1:, :] - hz[:-1, :])
    return ey, hz


def _k_fdtd_ex(np):
    ex, hz = grids(np, 2)
    ex[:, 1:] -= 0.5 * (hz[:, 1:] - hz[:, :-1])
    return ex, hz


def _k_fdtd_hz(np):
    ex, ey, hz = grids(np, 3)
    hz[:-1, :-1] -= 0.75 * (ex[:-1, 1:] - ex[:-1, :-1] + ey[1:, :-1] - ey[:-1, :-1])
    return ex, ey, hz


def _k_origins(np):
    """Windows whose origins are (1, 1), (1, 0), (0, 1) and (0, 0), each read
    across a corner, and two as far as the halo reaches."""
    a, b, c, d, e, f, g = grids(np, 7)
    b[1:, 1:] = a[1:, 1:] - 0.5 * a[:-1, :-1]
    c[1:, :-1] = a[1:, :-1] - 0.5 * a[:-1, 1:]
    d[:-1, 1:] = a[:-1, 1:] - 0.5 * a[1:, :-1]
    e[:-1, :-1] = a[:-1, :-1] - 0.5 * a[1:, 1:]
    f[8:-8, :] = a[:-16, :] + a[16:, :]
    g[:, 128:] = a[:, 128:] - a[:, :-128]
    return b, c, d, e, f, g


def _k_scalars(np):
    """A python scalar, a numpy scalar and two 0-d arrays (one of integers) among the operands."""
    a, b = grids(np, 2)
    k, m = grids(np, 2, dtype="int32")
    b[1:-1, 1:-1] = a[2, 3] * a[1:-1, 1:-1] + np.float32(0.5) * a[2:, 1:-1] - 2.0
    m[1:, 1:] = k[:-1, 1:] * k[3, 4] + (k[1:, :-1] << 1)
    return b, m


def _k_two_steps_donated(np):
    A, B = grids(np, 2)
    assert float(A[0, 1]) == 4.0 and float(B[0, 1]) == 5.0  # both computed: leaves of the next program
    for _ in range(2):
        B[1:-1, 1:-1] = 0.25 * (A[1:-1, 1:-1] + A[1:-1, :-2] + A[1:-1, 2:] + A[2:, 1:-1] + A[:-2, 1:-1])
        A[1:-1, 1:-1] = 0.25 * (B[1:-1, 1:-1] + B[1:-1, :-2] + B[1:-1, 2:] + B[2:, 1:-1] + B[:-2, 1:-1])
    return A, B


# name: (the statements, the window stores among them)
KERNEL_STORES = {
    "jacobi half-step": (_k_jacobi_half_step, 1), "jacobi over two chunks of lanes": (_k_jacobi_two_chunks, 1),
    "fdtd ey": (_k_fdtd_ey, 1), "fdtd ex": (_k_fdtd_ex, 1),
    "fdtd hz": (_k_fdtd_hz, 1), "four origins and the halo's reach": (_k_origins, 6),
    "scalars and 0-d arrays": (_k_scalars, 2), "two steps, both grids donated": (_k_two_steps_donated, 4),
}


def _run_statements(np, statements):
    lazy._exec_cache.clear()
    lazy.counters.reset()
    got = [real_np.asarray(x) for x in statements(np)]
    return got, lazy.counters.take()


@pytest.mark.parametrize("name", sorted(KERNEL_STORES))
def test_a_window_store_as_a_kernel_equals_the_select_and_stock_numpy(name, np_shim, monkeypatch):
    """The two lowerings of a planned store and stock numpy, value for value:
    the kernel (interpreted here) evaluates the expression's own operators in
    their own order on strips; the counters say which lowering ran."""
    statements, n_stores = KERNEL_STORES[name]
    want = [real_np.asarray(x) for x in statements(real_np)]
    as_select, select_counts = _run_statements(np_shim, statements)
    with monkeypatch.context() as patch:
        _as_on_a_tpu(patch)
        as_kernel, kernel_counts = _run_statements(np_shim, statements)
        # the same statements again: the same programs, counted per execution
        lazy.counters.reset()
        again = [real_np.asarray(x) for x in statements(np_shim)]
        assert lazy.counters.take()["kernel_stores"] == n_stores
    for k, s, a, w in zip(as_kernel, as_select, again, want, strict=True):
        assert k.dtype == w.dtype and real_np.array_equal(k, w) and real_np.array_equal(s, w)
        assert real_np.array_equal(a, w)
    assert (select_counts["aligned_stores"], select_counts["kernel_stores"]) == (n_stores, 0)
    assert (kernel_counts["aligned_stores"], kernel_counts["kernel_stores"]) == (n_stores, n_stores)
    assert kernel_counts["fallbacks"] == 0 and kernel_counts["programs"] == select_counts["programs"]
    if name == "two steps, both grids donated":
        assert kernel_counts["donated_bytes"] == select_counts["donated_bytes"] == 2 * 4 * ROWS * LANES


def _not_rank_1(np):
    a = ints(np, (ROWS * LANES,))
    a[1:-1] = 0.5 * (a[:-2] + a[2:])
    return (a,)


def _not_rank_3(np):
    a, b = grids(np, 2, (8, 16, 128))
    b[1:-1, 1:-1, 1:-1] = 0.25 * (a[2:, 1:-1, 1:-1] + a[:-2, 1:-1, 1:-1] + a[1:-1, 2:, 1:-1])
    return (b,)


def _stencil_of(dtype, shape=(ROWS, LANES)):
    def statements(np):
        a, b = (x.astype(dtype) for x in grids(np, 2, shape))
        b[1:-1, 1:-1] = a[1:-1, 1:-1] + a[2:, 1:-1] + a[1:-1, :-2]
        return (b,)
    return statements


def _not_float64(np):
    with jax.enable_x64(True):
        return (real_np.asarray(_stencil_of("float64")(np)[0]),)


def _not_a_shift_beyond_the_halo(np):
    a, b = grids(np, 2)
    b[9:, :] = a[9:, :] + a[:-9, :]
    c, d = grids(np, 2, (ROWS, 4 * LANES))
    d[:, 129:] = c[:, 129:] + c[:, :-129]
    return b, d


def _not_the_target_at_a_shift(np):
    a, b = grids(np, 2)
    a[1:-1, 1:-1] = 0.5 * (b[1:-1, 1:-1] + a[2:, 1:-1])  # reads rows the kernel would have overwritten
    return (a,)


def _not_a_view_still_held(np):
    a, b = grids(np, 2)
    view = b[1:-1, 1:-1] + b[2:, 2:]
    a[1:-1, 1:-1] = 0.5 * view  # not even planned: the window itself is asked for
    return a, view * 1.0


# name: (the statements, the stores that keep the full-shape select; the rest keep the slices)
NO_KERNEL = {
    "rank 1": (_not_rank_1, 1), "rank 3": (_not_rank_3, 1), "float64": (_not_float64, 1),
    "int8": (_stencil_of("int8"), 1), "bfloat16": (_stencil_of(ml_dtypes.bfloat16), 1),
    "a last dimension of 192": (_stencil_of("float32", (ROWS, 192)), 1),
    "rows that are no whole registers": (_stencil_of("float32", (60, LANES)), 1),
    "too few rows for four blocks": (_stencil_of("float32", (24, LANES)), 1),
    "a shift beyond the halo": (_not_a_shift_beyond_the_halo, 2),
    "the target read at a shift": (_not_the_target_at_a_shift, 1),
    "a view the user holds": (_not_a_view_still_held, 0),
}


@pytest.mark.parametrize("name", sorted(NO_KERNEL))
def test_a_store_the_kernel_does_not_take_keeps_the_select(name, np_shim, as_on_a_tpu):
    """Each thing `_kernel_plan` and `stencil.block_rows` refuse, on what would
    be a TPU: the values are stock numpy's and no store ran as a kernel."""
    statements, selects = NO_KERNEL[name]
    want = [real_np.asarray(x) for x in statements(real_np)]
    got, counts = _run_statements(np_shim, statements)
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and real_np.array_equal(g, w)
    assert (counts["aligned_stores"], counts["kernel_stores"], counts["fallbacks"]) == (selects, 0, 0)


def test_a_kernel_that_is_refused_never_fails_the_turn(np_shim, monkeypatch, caplog):
    """On a CPU Mosaic is not there to compile the kernel: the program is
    built again with every store as a select, runs, and is the runner from
    then on; nothing is counted as a kernel store."""
    monkeypatch.setattr(lazy, "_platform", lambda leaves: "tpu")  # and no interpreter
    want = [real_np.asarray(x) for x in _k_two_steps_donated(real_np)]
    for misses in (3, 0):  # each grid's creation and the steps; then all from the cache
        if misses:
            lazy._exec_cache.clear()
        lazy.counters.reset()
        got = [real_np.asarray(x) for x in _k_two_steps_donated(np_shim)]
        counts = lazy.counters.take()
        assert all(real_np.array_equal(g, w) for g, w in zip(got, want, strict=True))
        assert (counts["aligned_stores"], counts["kernel_stores"]) == (4, 0)
        assert counts["exec_cache_misses"] == misses and counts["donated_bytes"] == 2 * 4 * ROWS * LANES
    assert sum("kernel was refused" in r.message for r in caplog.records) == 1


def test_a_donated_grid_is_paired_with_the_output_stored_into_it(np_shim):
    """fdtd's three fields, asked for in another order than they were made
    (the payload prints hz first): each is updated in ITS buffer. jax pairs
    donated arguments with outputs of their shape by position, so `_paired`
    returns the outputs in the leaves' order; unpaired, every field would be
    written into another's buffer, which an in-place kernel pays with a copy
    of the grid on the way in and one on the way out."""
    ex, ey, hz = grids(np_shim, 3)
    fields = {"ex": ex, "ey": ey, "hz": hz}
    for field in fields.values():
        float(field[0, 1])  # computed: a leaf of the next program
    before = {name: field._concrete.unsafe_buffer_pointer() for name, field in fields.items()}
    for _ in range(2):
        ey[1:, :] -= 0.5 * (hz[1:, :] - hz[:-1, :])
        ex[:, 1:] -= 0.5 * (hz[:, 1:] - hz[:, :-1])
        hz[:-1, :-1] -= 0.75 * (ex[:-1, 1:] - ex[:-1, :-1] + ey[1:, :-1] - ey[:-1, :-1])
    float(hz[0, 1])  # one program computes all three
    assert {name: field._concrete.unsafe_buffer_pointer() for name, field in fields.items()} == before
    assert lazy.counters.take()["donated_bytes"] == 3 * 4 * ROWS * LANES


def test_the_kernels_blocks_fit_the_memory_they_state():
    """`block_rows` from the shapes alone: the run's grids get the largest
    divisor under the limits, and every block it names fits `VMEM_LIMIT_BYTES`
    with the pipeline's second buffer."""
    jacobi = [{(0, 0), (0, -1), (0, 1), (1, 0), (-1, 0)}]
    assert stencil.block_rows((24576, 24576), jacobi) == 128
    assert stencil.block_rows((17920, 23296), [{(0, 1), (0, 0)}, {(1, 0), (0, 0)}]) == 80  # fdtd's hz
    assert stencil.block_rows((17920, 23296), [{(0, 0), (-1, 0)}]) == 128
    assert stencil.block_rows((ROWS, LANES), jacobi) == 16
    assert stencil.block_rows((32, 2**20), jacobi) is None  # a block of 8 rows is 32 MiB
    for shape, reads in (((24576, 24576), jacobi), ((17920, 23296), [{(0, 1)}, {(1, 0)}])):
        rows = stencil.block_rows(shape, reads)
        blocks = 4 + 3 * len(reads)
        assert blocks * rows * (shape[1] + 256) * 4 + 4 * 8 * shape[1] * 4 <= stencil.VMEM_LIMIT_BYTES


# -- creation from index grids -------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "float64", "int32", "uint16"])
@pytest.mark.parametrize("shape", [(N,), (64, 80), (8, 20, 30)])
def test_indices_on_the_device_equal_numpys(np_shim, dtype, shape):
    got = np_shim.indices(shape, dtype=dtype)
    want = real_np.indices(shape, dtype=dtype)
    assert isinstance(got, TpuArray) and got._node is not None, "lazy: nothing ran, nothing crossed the host"
    assert got.shape == want.shape
    # a float64 request is computed in float32 (the shim's float policy)
    assert got.dtype == (real_np.float32 if dtype == "float64" else want.dtype)
    assert real_np.array_equal(real_np.asarray(got), want.astype(got.dtype))


@pytest.mark.parametrize("kwargs", [{}, {"dtype": int}, {"dtype": "int64"}, {"dtype": "float32", "sparse": True},
                                    {"dtype": "complex64"}])
def test_indices_that_stay_on_the_host(np_shim, kwargs):
    """numpy's default dtype is the platform int64, which the device would
    wrap; sparse grids are small; a complex grid is nobody's."""
    got = np_shim.indices((64, 80), **kwargs)
    want = real_np.indices((64, 80), **kwargs)
    parts = got if isinstance(got, tuple) else (got,)
    assert all(isinstance(p, real_np.ndarray) for p in parts)
    for g, w in zip(parts, want if isinstance(want, tuple) else (want,)):
        assert g.dtype == w.dtype and real_np.array_equal(g, w)


def test_small_grids_stay_on_the_host(np_shim):
    assert isinstance(np_shim.indices((10, 10), dtype="float32"), real_np.ndarray)
    small = np_shim.fromfunction(lambda i, j: i + j, (10, 10), dtype="float32")
    assert isinstance(small, real_np.ndarray) and small.dtype == real_np.float32


@pytest.mark.parametrize("dtype", ["float32", float, "int32"])
@pytest.mark.parametrize("shape, function", [
    ((N,), lambda i: (i + 1) / 7),
    ((64, 80), lambda i, j: i * (j + 2) / 64),
    ((8, 20, 30), lambda i, j, k: i - j + 2 * k),
], ids=["rank1", "rank2", "rank3"])
def test_fromfunction_on_the_device_equals_numpys(np_shim, dtype, shape, function):
    got = np_shim.fromfunction(function, shape, dtype=dtype)
    want = real_np.fromfunction(function, shape, dtype=dtype)
    assert isinstance(got, TpuArray) and got._node is not None
    assert got.shape == want.shape
    assert got.dtype == (real_np.float32 if want.dtype == real_np.float64 else want.dtype)
    assert real_np.allclose(real_np.asarray(got), want, rtol=1e-6)
    assert lazy.counters.take()["h2d_bytes"] == 0


def test_fromfunction_default_dtype_gives_float_grids(np_shim):
    """numpy's default is `float`: `i / 2` keeps its halves."""
    got = np_shim.fromfunction(lambda i, j: i / 2 + j, (64, 80))
    assert isinstance(got, TpuArray) and got.dtype == real_np.float32
    assert float(got[3, 5]) == 6.5


def test_fromfunction_passes_keyword_arguments_on(np_shim):
    got = np_shim.fromfunction(lambda i, j, offset=0: i + j + offset, (64, 80), dtype="float32", offset=5)
    assert isinstance(got, TpuArray) and float(got[1, 2]) == 8.0


@pytest.mark.parametrize("function", [
    lambda i, j: real_np.asarray(memoryview(i)) + j,
    lambda i, j: real_np.frombuffer(i, dtype=real_np.float32).reshape(64, 80) + j,
], ids=["memoryview", "frombuffer"])
def test_a_function_the_shim_cannot_trace_runs_under_stock_numpy(np_shim, function):
    """It wants a real buffer, which a TpuArray refuses with a TypeError: the
    shim's own signal. Stock numpy then, and counted."""
    got = np_shim.fromfunction(function, (64, 80), dtype="float32")
    assert isinstance(got, real_np.ndarray)
    assert real_np.array_equal(got, real_np.fromfunction(lambda i, j: i + j, (64, 80), dtype="float32"))
    assert lazy.counters.take()["fallbacks"] == 1


def test_an_error_of_the_functions_own_is_the_callers_and_it_ran_once(np_shim):
    calls = []

    def function(i, j):
        calls.append(type(i))
        return (i + j) / 0 if len(calls) > 5 else [][1]

    with pytest.raises(IndexError):
        np_shim.fromfunction(function, (64, 80), dtype="float32")
    assert calls == [TpuArray], "not a second time under stock numpy"
    assert lazy.counters.take()["fallbacks"] == 0


def test_a_jnp_function_that_refuses_its_arguments_is_a_counted_fallback(np_shim):
    a = np_shim.ones(N, dtype="float32")
    out = real_np.empty(N, dtype=real_np.float32)
    np_shim.add(a, 1.0, out=out)  # jnp takes no `out`: stock numpy on a host copy
    assert out[0] == 2.0
    assert lazy.counters.take()["fallbacks"] == 1


def test_fromfunction_with_an_int64_dtype_stays_on_the_host(np_shim):
    got = np_shim.fromfunction(lambda i, j: i * j, (64, 80), dtype=int)
    assert isinstance(got, real_np.ndarray) and got.dtype == real_np.int64


def test_an_outer_product_of_small_vectors_goes_where_its_result_belongs(np_shim):
    """Two vectors under the threshold, a matrix over it: on the device, and
    nothing but the vectors crosses."""
    u = real_np.arange(64, dtype=real_np.float32)
    v = real_np.arange(80, dtype=real_np.float32)
    got = np_shim.outer(u, v)
    assert isinstance(got, TpuArray)
    assert real_np.array_equal(real_np.asarray(got), real_np.outer(u, v))
    assert lazy.counters.take()["h2d_bytes"] == u.nbytes + v.nbytes
    assert isinstance(np_shim.outer(u[:10], v[:10]), real_np.ndarray)


def test_a_list_indexes_the_first_axis_as_numpys_does(np_shim):
    a = np_shim.arange(N, dtype="float32")
    assert real_np.asarray(a[[1, 5, 7]]).tolist() == [1.0, 5.0, 7.0]


# -- a host array meets a device array -------------------------------------------


def small(values=(1.0, 2.0, 3.0, 4.0)):
    """A host vector under the threshold, times N / 4 so that it broadcasts
    against nothing by accident: shape (N,)."""
    return real_np.tile(real_np.asarray(values, dtype=real_np.float32), N // 4)


@pytest.mark.parametrize("op", ["__iadd__", "__isub__", "__imul__", "__itruediv__"])
def test_an_in_place_update_of_a_host_argument_is_seen_by_the_caller(np_shim, op):
    """`x += <device array>` inside a function, x a host ndarray (a vector
    under the threshold, as gemver's x and w): stock numpy updates the
    caller's array, and so does the shim. It does not rebind the name."""
    device = np_shim.arange(N, dtype="float32") + 1.0
    assert isinstance(device, TpuArray)

    def update(x):
        x = getattr(x, op)(device * 2.0)  # what `x += device * 2.0` compiles to

    x, want = small(), small()
    update(x)
    getattr(want, op)((real_np.arange(N, dtype=real_np.float32) + 1.0) * 2.0)
    assert type(x) is real_np.ndarray and real_np.array_equal(x, want)


def test_gemver_updates_the_vectors_its_caller_holds(np_shim):
    """The source's `kernel()` returns nothing: x and w are read by the caller."""
    scope = {"__name__": "__main__", "P": {"N": 300, "ALPHA": 1.5}}
    exec(compile(SOURCES["gemver"], "gemver.py", "exec"), scope)
    assert type(scope["x"]) is real_np.ndarray and type(scope["w"]) is real_np.ndarray
    assert isinstance(scope["A"], TpuArray)
    assert scope["x"].any() and scope["w"].any(), "zeros before the kernel"


def test_an_in_place_update_that_numpy_would_refuse_is_refused(np_shim):
    counts = real_np.zeros(N, dtype=real_np.int64)
    with pytest.raises(TypeError, match="same_kind"):
        counts += np_shim.ones(N, dtype="float32") / 2.0
    assert not counts.any()


@pytest.mark.parametrize("op, want", [
    (lambda h, d: h + d, lambda h, d: h + d),
    (lambda h, d: h - d, lambda h, d: h - d),
    (lambda h, d: h / d, lambda h, d: h / d),
    (lambda h, d: h @ d, lambda h, d: h @ d),
    (lambda h, d: h < d, lambda h, d: h < d),
    (lambda h, d: h >= d, lambda h, d: h >= d),
    (lambda h, d: real_np.float32(1.5) * d, lambda h, d: real_np.float32(1.5) * d),
    (lambda h, d: real_np.subtract(h, d), lambda h, d: h - d),
], ids=["add", "sub", "div", "matmul", "lt", "ge", "scalar", "ufunc"])
def test_a_host_operand_on_the_left_goes_where_the_reflected_operator_goes(np_shim, op, want):
    host = small((3.0, 1.0, 4.0, 1.5))
    device = np_shim.arange(N, dtype="float32") + 1.0
    got = op(host, device)
    assert isinstance(got, TpuArray), "on the device, lazily, as before `__array_ufunc__`"
    expected = want(host, real_np.arange(N, dtype=real_np.float32) + 1.0)
    assert real_np.allclose(real_np.asarray(got), expected, rtol=1e-6)


def test_any_other_ufunc_of_stock_numpy_is_computed_on_a_host_copy(np_shim):
    device = np_shim.arange(N, dtype="float32")
    assert type(real_np.sqrt(device)) is real_np.ndarray
    assert float(real_np.add.reduce(device)) == N * (N - 1) / 2
    total = real_np.zeros(3, dtype=real_np.float32)
    real_np.add.at(total, [0, 0, 2], device[:3])
    assert total.tolist() == [1.0, 0.0, 2.0]
    with pytest.raises(TypeError):
        real_np.sqrt(small(), out=device)


# -- abstract evaluation, remembered ---------------------------------------------


def test_the_same_op_at_the_same_shapes_is_evaluated_abstractly_once(np_shim, monkeypatch):
    calls = []
    eval_shape = jax.eval_shape
    monkeypatch.setattr(lazy.jax, "eval_shape", lambda *a, **k: calls.append(1) or eval_shape(*a, **k))
    lazy._aval_memo.clear()

    def turn(n):
        a = np_shim.ones(n, dtype="float32")
        a[1:-1] = 0.5 * (a[:-2] + a[2:])
        return a

    first = turn(N)
    built = len(calls)
    assert built >= 5
    again = turn(N)
    assert len(calls) == built, "a second turn of the same source asks nothing"
    assert again.shape == first.shape == (N,) and float(again[1]) == 1.0
    before = len(calls)
    other = turn(N + 2)  # other shapes: asked anew
    assert len(calls) == before + built and other.shape == (N + 2,)


def test_the_memo_tells_dtypes_statics_and_weak_types_apart(np_shim):
    a = np_shim.ones(N, dtype="float32")
    b = np_shim.ones(N, dtype="int32")
    assert (a * 2).dtype == real_np.float32 and (b * 2).dtype == real_np.int32
    assert (b * 2.5).dtype == real_np.float32 and (b * real_np.float32(2.5)).dtype == real_np.float32
    assert a[2:].shape == (N - 2,) and a[3:].shape == (N - 3,)
    assert a.sum(axis=0).shape == () and a.reshape(4, -1).sum(axis=0).shape == (N // 4,)
    assert a.astype("int16").dtype == real_np.int16 and a.astype("float16").dtype == real_np.float16


def test_an_op_that_does_not_hash_is_evaluated_every_time(np_shim):
    class Doubler:
        __hash__ = None

        def __call__(self, x):
            return x * 2

    a = np_shim.ones(N, dtype="float32")
    before = len(lazy._aval_memo)
    for _ in range(2):
        node = lazy.build_node("doubler", Doubler(), (a,), {})
        assert node is not None and node.aval.shape == (N,)
    assert len(lazy._aval_memo) == before


def test_the_memo_is_cleared_whole_at_its_limit(np_shim, monkeypatch):
    monkeypatch.setattr(lazy, "_AVAL_MEMO_LIMIT", 3)
    lazy._aval_memo.clear()
    a = np_shim.ones(N, dtype="float32")
    for k in range(2, 8):
        assert a[k:].shape == (N - k,)
        assert 1 <= len(lazy._aval_memo) <= 3


# -- donation --------------------------------------------------------------------


def test_the_reference_count_is_read_in_one_place_and_calibrated():
    """What the call itself adds to sys.getrefcount is measured at import: an
    object that only its container holds reads 0, and each name more is one."""
    box = [object()]
    assert lazy._refs_beyond(box, 0) == 0
    name = box[0]
    assert lazy._refs_beyond(box, 0) == 1
    other = {"k": name}
    assert lazy._refs_beyond(box, 0) == 2 and lazy._refs_beyond(other, "k") == 2


def concrete(np, n=N):
    a = np.arange(n, dtype="float32")
    a.block_until_ready()
    assert a._node is None
    return a


@pytest.mark.parametrize("update", [
    lambda a: a.__setitem__(slice(0, 4), 7.0),
    lambda a: a.__iadd__(1.0),
    lambda a: a.fill(3.0),
], ids=["setitem", "iadd", "fill"])
def test_a_dead_leaf_is_donated_to_the_program_that_overwrites_it(np_shim, update):
    a = concrete(np_shim)
    before = a._concrete
    want = real_np.arange(N, dtype=real_np.float32)
    update(want)
    update(a)
    del before  # the test's own name for it
    lazy.counters.reset()
    assert real_np.array_equal(real_np.asarray(a), want)
    assert lazy.counters.take()["donated_bytes"] == 4 * N


def test_an_array_rebound_to_its_own_expression_is_donated(np_shim):
    a = concrete(np_shim)
    a = a * 2.0 + 1.0  # the old wrapper dies with the name
    lazy.counters.reset()
    assert float(a[3]) == 7.0
    assert lazy.counters.take()["donated_bytes"] == 4 * N


# What holds `a`, and what it has to read afterwards, from a's old values.
HOLDERS = {
    "a second wrapper of the same buffer": (lambda np, a: np.asarray(a), lambda old: old),
    "a lazy view": (lambda np, a: a[2:50], lambda old: old[2:50]),
    "a pending expression": (lambda np, a: a * 2.0, lambda old: old * 2.0),
    "the jax array itself": (lambda np, a: a.device_array, lambda old: old),
    "a wrapper built from it": (lambda np, a: TpuArray(a), lambda old: old),
}


@pytest.mark.parametrize("update", [
    lambda a: a.__setitem__(slice(0, 60), -1.0),
    lambda a: a.__iadd__(100.0),
], ids=["setitem", "iadd"])
@pytest.mark.parametrize("holder", sorted(HOLDERS))
def test_an_array_held_elsewhere_is_never_donated(np_shim, holder, update):
    """numpy reads operands at call time: what was taken from `a` before the
    update holds a's old values afterwards, whichever is computed first."""
    a = concrete(np_shim)
    hold, reads = HOLDERS[holder]
    held = hold(np_shim, a)
    old = reads(real_np.arange(N, dtype=real_np.float32))
    want = real_np.arange(N, dtype=real_np.float32)
    update(want)
    update(a)
    lazy.counters.reset()
    assert real_np.array_equal(real_np.asarray(a), want)
    assert lazy.counters.take()["donated_bytes"] == 0
    assert real_np.array_equal(real_np.asarray(held), old)


def test_a_pending_expression_is_computed_once_from_the_value_it_points_at(np_shim):
    """`b` points into `a`'s history; forcing `a` keeps the node `b` reads
    as an output, so `b` neither runs the history again nor loses its
    operand to a donation."""
    a = np_shim.arange(N, dtype="float32")
    a += 1.0
    b = a[10:20] * 3.0
    a += 1.0
    lazy.counters.reset()
    assert float(a[0]) == 2.0
    first = lazy.counters.take()
    assert real_np.asarray(b).tolist() == [3.0 * (i + 1) for i in range(10, 20)]
    second = lazy.counters.take()
    assert first["programs"] == 1 and second["programs"] == 1
    assert second["nodes"] == 2, "the slice and the product: not arange and the first += again"


def test_chained_in_place_updates_hold_the_array_once(np_shim):
    """k in-place updates of one array, each forced: the bytes alive on the
    device stay under twice the array. No cyclic collection: what is dead is
    gone at its last use."""
    n = 1 << 20
    gc.collect()
    gc.disable()
    try:
        base = sum(x.nbytes for x in jax.live_arrays())
        a = concrete(np_shim, n)
        size = 4 * n
        for k in range(8):
            a[k] = -1.0
            a += 1.0
            a[1:-1] = 0.5 * (a[:-2] + a[2:])
            a.block_until_ready()
            assert sum(x.nbytes for x in jax.live_arrays()) - base < 2 * size, k
        taken = lazy.counters.take()
        assert taken["donated_bytes"] == 8 * size and taken["programs"] == 9
    finally:
        gc.enable()


# -- the counters ----------------------------------------------------------------


def test_counters_of_a_hand_made_graph(np_shim):
    host = real_np.arange(N, dtype=real_np.float32)
    a = np_shim.ones(N, dtype="float32")  # 1 node
    b = a * 2.0  # 2
    c = np_shim.add(b, host)  # 3, and the host array crosses
    s = c.sum()  # 4
    assert lazy.counters.programs == 0, "nothing ran yet"
    assert float(s) == 2.0 * N + host.sum()
    taken = lazy.counters.take()
    # (the shipped copy of `host` is dead after the add, and c has its shape)
    stages = {name: taken[name] for name in ("h2d_s", "host_s", "dispatch_s", "wait_s", "d2h_s")}
    assert taken == {"programs": 1, "exec_cache_misses": 1, "nodes": 4, "flushes": 0, "load_files": 0, "load_bytes": 0,
                     "load_s": 0.0, "h2d_arrays": 1, "h2d_bytes": host.nbytes, "donated_bytes": host.nbytes,
                     "aligned_stores": 0, "kernel_stores": 0, "histograms": 0, "dots": 0, "dot_flops": 0, "ufunc_methods": 0,
                     "fallbacks": 0, "d2h_arrays": 1, "d2h_bytes": 4, **stages}
    # (this copy was made while its node was built, and is no second of `host_s`: ISSUE 39)
    assert all(0.0 < seconds < 60.0 for seconds in stages.values()), stages
    # a, b and c came back as outputs: each now reads in a program of one node
    assert float(b[1]) == 2.0 and float(c[2]) == 4.0
    assert lazy.counters.take()["nodes"] == 2
    # a window store from two shifted windows: two reads, the sum, the product,
    # the store, and the pick that forces them; once per execution, traced or not
    for misses in (1, 0):
        a[1:-1] = 0.5 * (c[:-2] + c[2:])
        assert float(a[1]) == 3.0
        taken = lazy.counters.take()
        assert (taken["aligned_stores"], taken["nodes"], taken["exec_cache_misses"]) == (1, 6, misses)
        assert taken["kernel_stores"] == 0, "a rank-1 store, and on the CPU: the select"
    assert lazy.counters.take() == dict.fromkeys(lazy.Counters.FIELDS, 0), "taken is zeroed"


def test_the_same_structure_again_is_no_miss(np_shim):
    for expected in (1, 0, 0):
        a = np_shim.ones(N, dtype="float32")
        assert float((a * 3.0).sum()) == 3.0 * N
        assert lazy.counters.take()["exec_cache_misses"] == expected


def test_a_loop_past_the_cap_counts_its_flushes(np_shim):
    a = np_shim.ones(N, dtype="float32")
    for _ in range(2 * lazy.MAX_GRAPH_NODES + 50):
        a = a + 1.0
    assert float(a[0]) == 1.0 + 2 * lazy.MAX_GRAPH_NODES + 50
    taken = lazy.counters.take()
    assert taken["flushes"] == 2 and taken["programs"] == 3
    assert taken["nodes"] == 2 * lazy.MAX_GRAPH_NODES + 50 + 2  # ones, the adds, the pick


def test_a_clear_all_of_the_runner_cache_shows_as_misses(np_shim, monkeypatch):
    monkeypatch.setattr(lazy, "_CACHE_LIMIT", 2)
    lazy._exec_cache.clear()

    def structure(k):
        a = np_shim.ones(N, dtype="float32")
        return float((a * float(k)).sum())

    for k in (1, 2):
        structure(k)
    assert lazy.counters.take()["exec_cache_misses"] == 2 and len(lazy._exec_cache) == 2
    structure(3)  # at the limit: everything goes, this one is built
    assert lazy.counters.take()["exec_cache_misses"] == 1 and len(lazy._exec_cache) == 1
    structure(1)  # was cached before the clear-all
    assert lazy.counters.take()["exec_cache_misses"] == 1


def test_each_program_runs_under_a_shim_materialize_annotation(np_shim, monkeypatch):
    """In a profiled turn the capture's host plane then holds one
    `shim.materialize` event per program, beside the runner's stages."""
    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(lazy.jax.profiler, "TraceAnnotation", Annotation)
    a = np_shim.ones(N, dtype="float32")
    assert float((a * 2.0).sum()) == 2.0 * N and float(a[0]) == 1.0
    programs = [name for name in seen if name == "shim.materialize"]
    assert programs == ["shim.materialize"] * lazy.counters.take()["programs"] == ["shim.materialize"] * 2
    # and each printed value's wait and copy back beside it (ISSUE 39)
    assert seen == ["shim.materialize", "shim.wait", "shim.d2h"] * 2


def test_take_counters_is_none_without_the_shim():
    assert npdispatch.take_counters() is None
    npdispatch.install(threshold=THRESHOLD)
    try:
        assert set(npdispatch.take_counters()) == set(lazy.Counters.FIELDS)
    finally:
        npdispatch.uninstall()
