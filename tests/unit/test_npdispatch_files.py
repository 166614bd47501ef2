"""What the deployment `npbench-files-1chip` asks of the numpy shim (ISSUE
35), on the CPU at small sizes over the threshold: an array read from a file
(`np.fromfile`, `np.load`, `np.frombuffer`) is device-resident from birth and
crosses once, whatever uses it; operators on it build the lazy graph; every
host-to-device copy is counted (a list's arrays, the eager path); what is
written back is what was read; and NPBench's softmax, arc_distance and
azimint_hist over seeded files against stock numpy. Nothing here times
anything."""

import gc
import mmap
import random

import jax
import numpy as real_np
import pytest

from bee_code_interpreter_fs_tpu.ops import npdispatch
from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy, shim
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


def seeded_file(path, words: int):
    """`words` 32-bit words of seeded bytes, as the benchmark's generator makes a file."""
    path.write_bytes(random.Random(f"7/{path.name}").randbytes(4 * words))
    return path


def as_bin(host, tmp):
    host.tofile(tmp / "a.bin")
    return tmp / "a.bin"


def as_npy(host, tmp):
    real_np.save(tmp / "a.npy", host)
    return tmp / "a.npy"


# How an array comes from a file or a buffer: (what makes the source from a
# host array and a directory, the call under whatever `np` is given).
LOADERS = {
    "fromfile": (as_bin, lambda np, src, dtype: np.fromfile(src, dtype=dtype)),
    "fromfile_open": (as_bin, lambda np, src, dtype: np.fromfile(open(src, "rb"), dtype=dtype, count=-1)),
    "load": (as_npy, lambda np, src, dtype: np.load(src)),
    "frombuffer": (lambda host, tmp: host.tobytes(), lambda np, src, dtype: np.frombuffer(src, dtype=dtype)),
    "frombuffer_memoryview": (lambda host, tmp: memoryview(host.tobytes()),
                              lambda np, src, dtype: np.frombuffer(src, dtype=dtype)),
}


def host_array(dtype, n=N):
    return (real_np.arange(n) % 251).astype(dtype)


# -- placement on load ------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "uint32", "int32", "uint8", "float16", "bool", "complex64"])
@pytest.mark.parametrize("loader", sorted(LOADERS))
def test_an_array_read_at_the_threshold_lives_on_the_device(np_shim, tmp_path, loader, dtype):
    make, load = LOADERS[loader]
    host = host_array(dtype)
    got = load(np_shim, make(host, tmp_path), dtype)
    assert isinstance(got, TpuArray) and got._node is None and isinstance(got._concrete, jax.Array)
    assert got.dtype == host.dtype and got.shape == host.shape
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (1, host.nbytes) and taken["h2d_s"] > 0
    assert real_np.asarray(got).tobytes() == host.tobytes(), "np.asarray gives the bytes that were read"


@pytest.mark.parametrize("loader", sorted(LOADERS))
def test_an_array_read_under_the_threshold_is_stock_numpys(np_shim, tmp_path, loader):
    make, load = LOADERS[loader]
    host = host_array("float32", THRESHOLD - 1)
    got = load(np_shim, make(host, tmp_path), "float32")
    assert type(got) is real_np.ndarray and got.tobytes() == host.tobytes()
    assert lazy.counters.take()["h2d_arrays"] == 0


@pytest.mark.parametrize("dtype, held", [("float64", "float32"), ("complex128", "complex64")])
def test_64_bit_floats_are_held_in_32_and_announced_once(np_shim, tmp_path, capsys, monkeypatch, dtype, held):
    monkeypatch.setattr(shim, "_policy_announced", False)
    host = host_array(dtype) / 3
    host.tofile(tmp_path / "a.bin")
    first = np_shim.fromfile(tmp_path / "a.bin", dtype=dtype)
    second = np_shim.fromfile(tmp_path / "a.bin", dtype=dtype)
    assert isinstance(first, TpuArray) and first.dtype == second.dtype == real_np.dtype(held)
    assert real_np.array_equal(real_np.asarray(first), host.astype(held))
    assert capsys.readouterr().err.count("precision policy") == 1
    assert lazy.counters.take()["h2d_bytes"] == 2 * host.nbytes // 2, "what crosses is what the device holds"


def structured(n):
    return real_np.zeros(n, dtype=[("a", "<f4"), ("b", "<i4")])


@pytest.mark.parametrize("host", [
    host_array("int64"), host_array("uint64"), structured(N), host_array("float32").astype("S4"),
], ids=["int64", "uint64", "structured", "bytes"])
def test_what_the_device_cannot_hold_as_numpy_does_stays_on_the_host(np_shim, tmp_path, host):
    host.tofile(tmp_path / "a.bin")
    got = np_shim.fromfile(tmp_path / "a.bin", dtype=host.dtype)
    assert type(got) is real_np.ndarray and got.tobytes() == host.tobytes()
    assert lazy.counters.take()["h2d_arrays"] == 0


def test_object_arrays_archives_and_memmaps_are_numpys_own(np_shim, tmp_path):
    objects = real_np.empty(N, dtype=object)
    objects[:] = 1
    real_np.save(tmp_path / "o.npy", objects, allow_pickle=True)
    assert type(np_shim.load(tmp_path / "o.npy", allow_pickle=True)) is real_np.ndarray
    real_np.save(tmp_path / "a.npy", host_array("float32"))
    assert type(np_shim.load(tmp_path / "a.npy", mmap_mode="r")) is real_np.memmap
    real_np.savez(tmp_path / "z.npz", a=host_array("float32"))
    with np_shim.load(tmp_path / "z.npz") as archive:
        assert type(archive["a"]) is real_np.ndarray
    assert lazy.counters.take()["h2d_arrays"] == 0


def anonymous_map(payload: bytes):
    mapped = mmap.mmap(-1, len(payload))
    mapped[:] = payload
    return mapped


@pytest.mark.parametrize("dtype", ["float32", "int64"])
@pytest.mark.parametrize("make", [bytearray, anonymous_map], ids=["bytearray", "mmap"])
def test_frombuffer_over_memory_that_can_be_written_stays_numpys_view_of_it(np_shim, make, dtype):
    """As under stock numpy: `f.readinto(buf)` after the call is seen in the
    array, a write through the array reaches the buffer; nothing is shipped."""
    host = host_array(dtype)
    buffer = make(host.tobytes())
    got = np_shim.frombuffer(buffer, dtype=dtype)
    assert type(got) is real_np.ndarray and got.flags.writeable and got.size >= THRESHOLD
    buffer[0:host.itemsize] = real_np.array([99], dtype=dtype).tobytes()
    assert got[0] == 99, "a later write to the buffer is seen in the array"
    got[1:] = 7
    assert real_np.frombuffer(bytes(buffer), dtype=dtype)[-1] == 7, "a write through the array reaches the buffer"
    assert lazy.counters.take()["h2d_arrays"] == 0
    if dtype == "float32":
        assert isinstance(np_shim.exp(got), TpuArray), "a shim function takes it to the device, as any host array"
    del got


# -- operators on a loaded array ----------------------------------------------------


def test_operators_on_a_loaded_array_build_the_graph_and_nothing_runs_on_the_host(np_shim, tmp_path):
    host = host_array("float32")
    host.tofile(tmp_path / "x.bin")
    x = np_shim.fromfile(tmp_path / "x.bin", dtype=np_shim.float32)
    y = x * 2.0 + 1.0
    assert isinstance(y, TpuArray) and y._node is not None and lazy.counters.programs == 0
    assert real_np.array_equal(real_np.asarray(y), host * 2.0 + 1.0)
    taken = lazy.counters.take()
    assert taken["fallbacks"] == 0 and taken["programs"] == 1 and taken["nodes"] == 2
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (1, host.nbytes)


@pytest.mark.parametrize("uses", [1, 2, 5])
def test_a_loaded_array_crosses_once_however_often_it_is_used(np_shim, tmp_path, uses):
    host = host_array("float32")
    host.tofile(tmp_path / "x.bin")
    x = np_shim.fromfile(tmp_path / "x.bin", dtype="float32")
    total = 0.0
    for k in range(uses):  # a function of the module and an operator, each time
        total += float(np_shim.max(x, axis=-1)) + float((x - float(k)).sum())
    assert total == pytest.approx(sum(float(host.max()) + float((host - k).sum()) for k in range(uses)), rel=1e-6)
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"], taken["fallbacks"]) == (1, host.nbytes, 0)


def test_a_stock_ndarray_operand_crosses_at_every_call_and_each_is_counted(np_shim):
    """What placement on load spares: the same host array, used by two calls."""
    host = host_array("float32")
    assert float(np_shim.max(host)) == float(host.max()) and float(np_shim.sum(host - 1.0)) == float((host - 1.0).sum())
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (2, 2 * host.nbytes)


def test_a_dead_loaded_array_is_donated_to_the_program_that_overwrites_it(np_shim, tmp_path):
    host = host_array("float32")
    host.tofile(tmp_path / "x.bin")
    x = np_shim.fromfile(tmp_path / "x.bin", dtype="float32")
    x += 1.0
    x[1:-1] = 0.5 * (x[:-2] + x[2:])
    want = host + 1.0
    want[1:-1] = 0.5 * (want[:-2] + want[2:])
    assert real_np.array_equal(real_np.asarray(x), want)
    taken = lazy.counters.take()
    assert taken["donated_bytes"] == host.nbytes and taken["h2d_arrays"] == 1


def test_the_hosts_copy_is_let_go_once_shipped(np_shim, tmp_path):
    host_array("float32").tofile(tmp_path / "x.bin")
    gc.collect()
    before = {id(o) for o in gc.get_objects() if type(o) is real_np.ndarray}
    x = np_shim.fromfile(tmp_path / "x.bin", dtype="float32")
    left = [o for o in gc.get_objects() if type(o) is real_np.ndarray and id(o) not in before and o.size >= N]
    assert isinstance(x, TpuArray) and not left


# -- every copy is counted -----------------------------------------------------------


@pytest.mark.parametrize("join", ["concatenate", "stack", "vstack", "hstack", "dstack", "column_stack"])
def test_a_join_of_loaded_shards_ships_each_once_and_concatenate_is_a_node_of_the_graph(np_shim, tmp_path, join):
    hosts = [host_array("float32") + k for k in range(3)]
    for k, host in enumerate(hosts):
        host.tofile(tmp_path / f"x_{k:02d}.bin")
    shards = [np_shim.fromfile(tmp_path / f"x_{k:02d}.bin", dtype="float32") for k in range(3)]
    joined = getattr(np_shim, join)([s * 2.0 for s in shards])
    assert isinstance(joined, TpuArray)
    if join == "concatenate":  # shards, join and what follows are ONE program; the other joins keep the eager path
        assert joined._node is not None and lazy.counters.programs == 0
    assert real_np.array_equal(real_np.asarray(joined), getattr(real_np, join)([h * 2.0 for h in hosts]))
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (3, 3 * hosts[0].nbytes)
    assert join != "concatenate" or taken["programs"] == 1


def test_a_join_with_an_axis_and_of_a_tuple(np_shim):
    a, b = np_shim.ones((40, 50), dtype="float32"), np_shim.zeros((40, 50), dtype="float32")
    got = np_shim.concatenate((a, b), axis=1)
    assert got.shape == (40, 100) and float(got.sum()) == 2000.0
    assert np_shim.concatenate((a, b), 1).shape == (40, 100), "a positional axis keeps the eager call"


def test_host_arrays_inside_a_list_are_counted(np_shim):
    hosts = [host_array("float32"), host_array("float32") + 1]
    joined = np_shim.concatenate(hosts)
    assert isinstance(joined, TpuArray) and real_np.array_equal(real_np.asarray(joined), real_np.concatenate(hosts))
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (2, 2 * hosts[0].nbytes)


def test_a_histogram_of_host_arrays_counts_what_it_ships(np_shim):
    """Both operands cross once and are counted; the edges, made on the host
    from two scalars, are an operand of the program and no array of the user's."""
    radius, data = host_array("float32") / 251, host_array("float32") + 1
    counts, edges = np_shim.histogram(radius, 16, weights=data)
    want, want_edges = real_np.histogram(radius, 16, weights=data)
    assert real_np.allclose(real_np.asarray(counts), want, rtol=1e-6)
    assert real_np.allclose(real_np.asarray(edges), want_edges, rtol=1e-6)
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"], taken["fallbacks"]) == (2, radius.nbytes + data.nbytes, 0)
    assert taken["histograms"] == 1


# -- np.histogram: one program of the shim's own, on numpy's edges -----------------------


def grid_floats(n=N, seed=11, scale=1.0):
    """Seeded float32 values on a grid of 2**-24, as the benchmark's files hold them."""
    words = real_np.random.default_rng(seed).integers(0, 2 ** 24, n, dtype=real_np.uint32)
    return words.astype(real_np.float32) * real_np.float32(scale * 2.0 ** -24)


def on_the_edges():
    """Elements exactly on the first, an inner and the last edge of four bins over [0, 1]."""
    x = grid_floats()
    x[:6] = [0.0, 0.25, 0.5, 0.5, 1.0, 1.0]
    return x


# name: (the vector, what the call states besides it)
HISTOGRAMS = {
    "a_count_of_bins": (grid_floats, {"bins": 16}),
    "the_default_bins": (grid_floats, {}),
    "a_thousand_bins": (grid_floats, {"bins": 1000}),
    "weights": (grid_floats, {"bins": 16, "weights": lambda: grid_floats(seed=12)}),
    "a_range_inside_the_data": (grid_floats, {"bins": 10, "range": (0.2, 0.7)}),
    "a_range_outside_the_data": (grid_floats, {"bins": 5, "range": (-1, 2)}),
    "a_range_and_weights": (grid_floats, {"bins": 7, "range": (0.1, 0.9), "weights": lambda: grid_floats(seed=13)}),
    "elements_on_an_inner_and_on_the_last_edge": (on_the_edges, {"bins": 4, "range": (0.0, 1.0)}),
    "elements_on_the_edges_of_the_data": (on_the_edges, {"bins": 4}),
    "all_elements_equal": (lambda: real_np.full(N, 0.75, real_np.float32), {"bins": 6}),
    "two_dimensions": (lambda: grid_floats().reshape(40, -1), {"bins": 9}),
    "two_dimensions_and_weights": (lambda: grid_floats().reshape(40, -1),
                                   {"bins": 9, "weights": lambda: grid_floats(seed=14).reshape(40, -1)}),
    "edges_that_are_not_uniform": (grid_floats, {"bins": [0.0, 0.1, 0.35, 0.8, 1.0]}),
    "edges_between_two_floats": (lambda: grid_floats(scale=0.4), {"bins": real_np.linspace(0.0, 0.4, 12)}),
    "edges_that_are_integers": (lambda: grid_floats(scale=8.0), {"bins": [0, 1, 2, 5, 8]}),
    "edges_inside_the_data_and_weights": (grid_floats, {"bins": [0.25, 0.5, 0.75], "weights": lambda: grid_floats(seed=15)}),
    "density": (grid_floats, {"bins": 7, "density": True}),
    "density_over_edges_that_are_not_uniform": (grid_floats, {"bins": [0.0, 0.1, 0.35, 0.8, 1.0], "density": True}),
    "density_and_weights": (grid_floats, {"bins": 5, "density": True, "weights": lambda: grid_floats(seed=16)}),
    "one_block_and_a_few": (lambda: grid_floats(n=shim._HISTOGRAM_BLOCK + 7), {"bins": 3}),
    "an_empty_array": (lambda: real_np.empty(0, real_np.float32), {"bins": 4}),
    "an_empty_array_and_a_range": (lambda: real_np.empty(0, real_np.float32), {"bins": 4, "range": (2, 3)}),
}


# (an empty host array is under the threshold, and stock numpy's to count)
@pytest.mark.parametrize("name, held", [(name, held) for name in sorted(HISTOGRAMS) for held in ("host", "device")
                                        if held == "device" or "empty" not in name])
def test_a_histogram_equals_stock_numpys(np_shim, name, held):
    """Counts EQUAL to numpy's, edges bit for bit, weighted sums within the
    rounding of a float32 sum; from a host array over the threshold and from
    an array that lives on the device; one program a call."""
    make, call = HISTOGRAMS[name]
    call = {key: value() if callable(value) else value for key, value in call.items()}
    x = make()
    want, want_edges = real_np.histogram(x, **call)
    mine = dict(call)
    if held == "device":
        x = TpuArray(x)
        if "weights" in mine:
            mine["weights"] = TpuArray(mine["weights"])
    lazy.counters.reset()
    got, edges = np_shim.histogram(x, **mine)
    taken = lazy.counters.take()
    assert type(edges) is real_np.ndarray and edges.dtype == want_edges.dtype and real_np.array_equal(edges, want_edges)
    assert got.shape == want.shape
    if call.get("density"):
        assert type(got) is real_np.ndarray and got.dtype == want.dtype
    else:
        assert isinstance(got, TpuArray) and got.dtype == ("float32" if "weights" in call else "int32")
    if "weights" in call or call.get("density"):
        assert real_np.abs(real_np.asarray(got) - want).max() <= 2e-6 * max(real_np.abs(want).max(), 1e-30)
    else:
        assert real_np.array_equal(real_np.asarray(got), want)
    assert (taken["histograms"], taken["fallbacks"]) == (1, 0)
    wants_the_extent = "range" not in call and real_np.ndim(call.get("bins", 10)) == 0 and x.size
    assert taken["programs"] == (1 if wants_the_extent else 0), "the least and the greatest value, where numpy reads them"


@pytest.mark.parametrize("fault, call, error", [
    ("a_nan_in_the_data", {"bins": 8}, "autodetected range of \\[nan, nan\\] is not finite"),
    ("no_nan", {"bins": 8, "range": (0, real_np.inf)}, "supplied range of \\[0, inf\\] is not finite"),
    ("no_nan", {"bins": 8, "range": (real_np.nan, 1)}, "is not finite"),
    ("no_nan", {"bins": 8, "range": (1, 0)}, "max must be larger than min in range parameter"),
    ("no_nan", {"bins": [0.0, 0.5, 0.25]}, "`bins` must increase monotonically, when an array"),
    ("weights_of_another_shape", {"bins": 8}, "weights should have the same shape as a"),
])
def test_a_histogram_raises_what_numpy_raises(np_shim, fault, call, error):
    x = grid_floats()
    if fault == "a_nan_in_the_data":
        x[17] = real_np.nan
    if fault == "weights_of_another_shape":
        call = dict(call, weights=grid_floats(n=N - 1))
    with pytest.raises(ValueError, match=error):
        real_np.histogram(x, **call)
    with pytest.raises(ValueError, match=error):
        np_shim.histogram(TpuArray(x), **call)
    assert lazy.counters.take()["histograms"] == 0


def test_a_nan_beside_a_stated_range_falls_in_no_bin(np_shim):
    x = grid_floats()
    x[::7] = real_np.nan
    want, _ = real_np.histogram(x, 8, range=(0, 1))
    got, _ = np_shim.histogram(TpuArray(x), 8, range=(0, 1))
    assert real_np.array_equal(real_np.asarray(got), want) and want.sum() < x.size


def test_a_bin_counts_past_the_end_of_a_float32s_integers(np_shim):
    """`jnp.histogram` sums float32 ones and stops at 2**24; the program counts in int32."""
    n = 2 ** 24 + 3
    counts, edges = np_shim.histogram(np_shim.full(n, 0.75, dtype="float32"), 4)
    assert real_np.asarray(counts).tolist() == [0, 0, n, 0] and edges.tolist() == [0.25, 0.5, 0.75, 1.0, 1.25]


def test_two_datasets_of_one_shape_share_one_executable(np_shim):
    """The edges are an operand, so what differs between two turns' data compiles nothing."""
    np_shim.histogram(TpuArray(grid_floats(seed=21)), 16)
    compiled = shim._histogram_program._cache_size()
    np_shim.histogram(TpuArray(grid_floats(seed=22, scale=3.0)), 16)
    assert shim._histogram_program._cache_size() == compiled


@pytest.fixture
def todays_histogram(monkeypatch):
    """`jnp.histogram` as the shim's dispatcher takes it when it is installed: the calls it got."""
    calls = []
    inner = jax.numpy.histogram

    def histogram(*args, **kwargs):
        calls.append(kwargs)
        return inner(*args, **kwargs)

    monkeypatch.setattr(jax.numpy, "histogram", histogram)
    return calls


# What keeps `jnp.histogram`, op by op, as before the program existed: (the vector, the call)
KEPT = {
    "an_estimators_name": (grid_floats, {"bins": "auto"}),
    "integers": (lambda: real_np.arange(N, dtype=real_np.int32) % 97, {"bins": 8}),
    "sixteen_bit_floats": (lambda: grid_floats().astype(real_np.float16), {"bins": 8}),
    "complex_weights": (grid_floats, {"bins": 8, "weights": lambda: grid_floats(seed=3).astype(real_np.complex64)}),
    "integer_weights": (grid_floats, {"bins": 8, "weights": lambda: real_np.arange(N, dtype=real_np.int32) % 5}),
    "an_edge_at_infinity": (grid_floats, {"bins": [0.0, 0.5, real_np.inf]}),
    "no_bin": (grid_floats, {"bins": 0}),
    "more_edges_than_comparing_against_each_saves": (grid_floats, {"bins": 64}),
}


@pytest.mark.parametrize("name", sorted(KEPT))
def test_what_the_program_does_not_take_keeps_todays_path(todays_histogram, np_shim, monkeypatch, name):
    make, call = KEPT[name]
    call = {key: value() if callable(value) else value for key, value in call.items()}
    if name == "more_edges_than_comparing_against_each_saves":
        monkeypatch.setattr(shim, "_HISTOGRAM_MAX_BINS", 63)
    x = TpuArray(make())
    try:
        got = np_shim.histogram(x, **call)
    except ValueError:
        got = None  # (what `jnp.histogram` raises of no bin is the caller's to see, as before)
    assert len(todays_histogram) == 1, "jnp.histogram ran, once"
    assert lazy.counters.take()["histograms"] == 0
    if name in ("integers", "more_edges_than_comparing_against_each_saves", "an_edge_at_infinity"):
        want, _ = real_np.histogram(real_np.asarray(x), **call)
        assert real_np.array_equal(real_np.asarray(got[0]), want)


def test_a_vector_whose_bins_could_pass_int32_keeps_numpy(todays_histogram, np_shim, monkeypatch):
    assert shim._histogram_takes(2 ** 31 - 1, real_np.dtype("float32"), 4, None) == "program"
    assert shim._histogram_takes(2 ** 31, real_np.dtype("float32"), 4, None) == "numpy"
    monkeypatch.setattr(shim, "_HISTOGRAM_MAX_ELEMENTS", N)
    counts, edges = np_shim.histogram(TpuArray(grid_floats()), 8)
    assert type(counts) is real_np.ndarray and counts.dtype == real_np.int64 and counts.sum() == N
    taken = lazy.counters.take()
    assert (taken["histograms"], taken["fallbacks"], len(todays_histogram)) == (0, 1, 0)


@pytest.mark.parametrize("size, dtype, bins, weights, route", [
    (10 ** 7, "float32", 1000, None, "program"),
    (10 ** 7, "float32", 1000, "float32", "program"),
    (10 ** 7, "float32", tuple(range(1001)), None, "program"),
    (10 ** 7, "float32", "fd", None, "jnp"),
    (10 ** 7, "int32", 1000, None, "jnp"),
    (10 ** 7, "bool", 2, None, "jnp"),
    (10 ** 7, "float32", 1000, "complex64", "jnp"),
    (10 ** 7, "float32", 1000, "object", "jnp"),
    (10 ** 7, "float32", 10.5, None, "jnp"),
    (10 ** 7, "float32", ((0, 1), (2, 3)), None, "jnp"),
    (10 ** 7, None, 1000, None, "jnp"),
    (2 ** 30, "float32", 1000, None, "program"),
    (10 ** 7, "float32", 2 ** 16, None, "program"),
    (10 ** 7, "float32", 2 ** 16 + 1, None, "jnp"),
    (2 ** 18, "float32", range(2 ** 16 + 2), None, "jnp"),
    (2 ** 31, "float32", 1000, None, "numpy"),
])
def test_the_route_is_read_from_the_call(size, dtype, bins, weights, route):
    dtype, weights = (None if name is None else real_np.dtype(name) for name in (dtype, weights))
    assert shim._histogram_takes(size, dtype, bins, weights) == route


def test_each_copy_runs_under_a_shim_h2d_annotation(np_shim, tmp_path, monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(lazy.jax.profiler, "TraceAnnotation", Annotation)
    host_array("float32").tofile(tmp_path / "x.bin")
    x = np_shim.fromfile(tmp_path / "x.bin", dtype="float32")
    assert float(np_shim.add(x, host_array("float32")).sum()) == 2.0 * float(host_array("float32").sum())
    assert [name for name in seen if name in ("shim.h2d", "shim.materialize")] == ["shim.h2d", "shim.h2d", "shim.materialize"]
    # with numpy's read before them and the printed value's wait and copy back after (ISSUE 39)
    assert seen == ["shim.load", "shim.h2d", "shim.h2d", "shim.materialize", "shim.wait", "shim.d2h"]


# -- what is written back is what was read -----------------------------------------------


@pytest.mark.parametrize("write", ["tofile", "tofile_open", "save", "tobytes"])
def test_file_writes_round_trip_the_bytes_that_were_read(np_shim, tmp_path, write):
    source = seeded_file(tmp_path / "in.bin", N)
    x = np_shim.fromfile(source, dtype="uint32")
    assert isinstance(x, TpuArray)
    if write == "tofile":
        x.tofile(tmp_path / "out.bin")
        got = (tmp_path / "out.bin").read_bytes()
    elif write == "tofile_open":
        with open(tmp_path / "out.bin", "wb") as out:
            x.tofile(out)
        got = (tmp_path / "out.bin").read_bytes()
    elif write == "save":
        np_shim.save(tmp_path / "out.npy", x)
        got = real_np.load(tmp_path / "out.npy").tobytes()
        assert isinstance(np_shim.load(tmp_path / "out.npy"), TpuArray)
    else:
        got = x.tobytes()
    assert got == source.read_bytes()


# -- the three kernels over files ------------------------------------------------------

# NPBench's softmax, arc_distance and azimint_hist, `kernel()` as the source's
# and `initialize()` reading files, this file's own copies (the benchmark's
# payloads are the benchmark's; `tests/chipbench` rehearses those). `P` holds
# the sizes and `LOWP` for the control in bfloat16.
FROM_FILE = """
import numpy as np
def from_file(path):
    raw = np.fromfile(path, dtype=np.uint32)
    return (raw >> 8).astype(np.float32) * np.float32(2.0 ** -24)
"""
SOURCES = {
    "softmax": FROM_FILE + """
def softmax(x):
    tmp_max = np.max(x, axis=-1, keepdims=True)
    tmp_out = np.exp(x - tmp_max)
    tmp_sum = np.sum(tmp_out, axis=-1, keepdims=True)
    return tmp_out / tmp_sum
x = np.concatenate([from_file(f"x_{i:02d}.bin") for i in range(P["SHARDS"])]).reshape(P["N"], P["H"], P["SM"], P["SM"])
if P.get("LOWP"):
    import ml_dtypes
    x = x.astype(ml_dtypes.bfloat16)
out = softmax(x)
""",
    "arc_distance": FROM_FILE + """
def arc_distance(theta_1, phi_1, theta_2, phi_2):
    temp = np.sin((theta_2 - theta_1) / 2)**2 + np.cos(theta_1) * np.cos(theta_2) * np.sin((phi_2 - phi_1) / 2)**2
    distance_matrix = 2 * (np.arctan2(np.sqrt(temp), np.sqrt(1 - temp)))
    return distance_matrix
t0, p0, t1, p1 = (from_file(f"{name}.bin") for name in ("theta_1", "phi_1", "theta_2", "phi_2"))
if P.get("LOWP"):
    import ml_dtypes
    t0, p0, t1, p1 = (a.astype(ml_dtypes.bfloat16) for a in (t0, p0, t1, p1))
out = arc_distance(t0, p0, t1, p1)
""",
    "azimint_hist": FROM_FILE + """
def azimint_hist(data, radius, npt):
    histu = np.histogram(radius, npt)[0]
    histw = np.histogram(radius, npt, weights=data)[0]
    return histw / histu
data, radius = from_file("data.bin"), from_file("radius.bin")
if P.get("LOWP"):
    import ml_dtypes
    data, radius = data.astype(ml_dtypes.bfloat16), radius.astype(ml_dtypes.bfloat16)
out = azimint_hist(data, radius, P["NPT"])
""",
}
# (sizes, the files a turn reads with their words, the payload's `rel_limit`)
KERNELS = {
    "softmax": ({"N": 4, "H": 2, "SM": 32, "SHARDS": 2}, {"x_00.bin": 4096, "x_01.bin": 4096}, 2e-5),
    "arc_distance": ({"N": N}, dict.fromkeys(("theta_1.bin", "phi_1.bin", "theta_2.bin", "phi_2.bin"), N), 2e-5),
    "azimint_hist": ({"N": 8 * N, "NPT": 16}, {"data.bin": 8 * N, "radius.bin": 8 * N}, 2e-3),
}


def run_kernel(name: str, params: dict, tmp_path, monkeypatch):
    """The kernel under whatever `import numpy` gives now, in a directory
    that holds its seeded files; `out` as host float64."""
    for file, words in KERNELS[name][1].items():
        seeded_file(tmp_path / file, words)
    monkeypatch.chdir(tmp_path)
    scope = {"__name__": "__main__", "P": params}
    exec(compile(SOURCES[name], f"{name}.py", "exec"), scope)
    return real_np.asarray(scope["out"]).astype(real_np.float64)


def widest_gap(got, want) -> float:
    return float(real_np.abs(got - want).max() / real_np.abs(want).max())


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_over_files_equals_stock_numpy_and_its_control_does_not(name, tmp_path, monkeypatch):
    params, files, rel_limit = KERNELS[name]
    want = run_kernel(name, params, tmp_path, monkeypatch)
    npdispatch.install(threshold=THRESHOLD)
    try:
        lazy.counters.reset()
        got = run_kernel(name, params, tmp_path, monkeypatch)
        taken = lazy.counters.take()
        control = run_kernel(name, dict(params, LOWP=1), tmp_path, monkeypatch)
    finally:
        npdispatch.uninstall()
    assert got.shape == want.shape and widest_gap(got, want) <= rel_limit
    assert widest_gap(control, want) > 3 * rel_limit
    # every file crossed once, as it was read, and nothing of its size ran on the host
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (len(files), 4 * sum(files.values()))
    assert taken["fallbacks"] == 0 and taken["programs"] >= 1
