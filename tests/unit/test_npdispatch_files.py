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


def test_the_eager_path_counts_what_it_ships(np_shim):
    radius, data = host_array("float32") / 251, host_array("float32") + 1
    counts, edges = np_shim.histogram(radius, 16, weights=data)
    want, want_edges = real_np.histogram(radius, 16, weights=data)
    assert real_np.allclose(real_np.asarray(counts), want, rtol=1e-6)
    assert real_np.allclose(real_np.asarray(edges), want_edges, rtol=1e-6)
    taken = lazy.counters.take()
    assert (taken["h2d_arrays"], taken["h2d_bytes"], taken["fallbacks"]) == (2, radius.nbytes + data.nbytes, 0)


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
    assert seen == ["shim.h2d", "shim.h2d", "shim.materialize"]


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
