"""Inside a turn's user code (ISSUE 39): the shim times numpy's reads of
files, its copies to the device, its own host work, the calls that hand the
device a program, the waits for a value and the copies back, as stages of
`lazy.counters` that tile the wall and none of which holds another's second;
and every way a `TpuArray` gives its caller a host value is ONE counted copy
through `lazy.fetch`. On the CPU, under the installed shim."""

import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as real_np
import pytest

from bee_code_interpreter_fs_tpu.ops import npdispatch
from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy, shim

STAGES = ("load_s", "h2d_s", "host_s", "dispatch_s", "wait_s", "d2h_s")
N = 5000  # over the threshold the fixture installs


@pytest.fixture()
def np():
    npdispatch.install(threshold=1000)
    import numpy

    lazy.counters.reset()
    try:
        yield numpy
    finally:
        npdispatch.uninstall()


def test_a_turn_that_reads_computes_prints_and_writes_fills_every_stage(np, tmp_path, capsys):
    real_np.arange(N, dtype="float32").tofile(tmp_path / "in.bin")
    real_np.arange(7, dtype="int64").tofile(tmp_path / "small.bin")
    started = time.perf_counter()
    a = np.fromfile(tmp_path / "in.bin", dtype="float32")
    small = np.fromfile(tmp_path / "small.bin", dtype="int64")  # stays numpy's: counted as read all the same
    b = a * 2.0 + 1.0
    print(float(b.sum()))
    b.tofile(tmp_path / "out.bin")
    wall = time.perf_counter() - started
    taken = lazy.counters.take()
    assert capsys.readouterr().out.strip() == str(float(N * (N - 1) + N))
    assert type(small) is real_np.ndarray and isinstance(a, shim.TpuArray)
    assert (taken["load_files"], taken["load_bytes"]) == (2, 4 * N + 8 * 7)
    assert (taken["h2d_arrays"], taken["h2d_bytes"]) == (1, 4 * N), "only the placed array crossed"
    assert (taken["d2h_arrays"], taken["d2h_bytes"]) == (2, 4 + 4 * N), "the printed sum, the array written out"
    assert taken["programs"] == 1 and taken["fallbacks"] == 0, "one program: the sum, and b, which the turn holds"
    for stage in STAGES:
        assert taken[stage] > 0, stage
    assert sum(taken[stage] for stage in STAGES) <= wall, "the stages tile the wall: no second is in two of them"
    assert real_np.array_equal(real_np.fromfile(tmp_path / "out.bin", dtype="float32"),
                               real_np.arange(N, dtype="float32") * 2 + 1)


@pytest.fixture()
def slow_copies(monkeypatch):
    """Every copy to the device takes 50 ms longer: a stage that also held
    the copy's seconds would show them."""
    asarray = jnp.asarray

    def slow(*args, **kwargs):
        time.sleep(0.05)
        return asarray(*args, **kwargs)

    monkeypatch.setattr(lazy.jnp, "asarray", slow)


def test_host_s_holds_no_second_of_a_leaf_shipped_while_a_node_is_built(np, slow_copies):
    a = np.ones(N, dtype="float32")
    lazy.counters.reset()
    b = a + real_np.ones(N, dtype="float32")  # the ndarray operand is shipped inside `build_node`'s clock
    taken = lazy.counters.take()
    assert isinstance(b, shim.TpuArray) and taken["h2d_arrays"] == 1
    assert taken["h2d_s"] >= 0.05 and taken["host_s"] < 0.04


def test_host_s_holds_no_second_of_a_leaf_shipped_inside_run(np, slow_copies):
    """An ndarray leaf that reaches `_run` as it is (a node built by hand:
    `build_node` ships its own) is shipped inside `materialize`'s clock."""
    on_device = jax.device_put(real_np.ones(N, dtype="float32"))
    leaves = [(lazy._REF_LEAF, on_device), (lazy._REF_LEAF, real_np.full(N, 2.0, dtype="float32"))]
    node = lazy.Node("add", jnp.add, leaves, {}, jax.ShapeDtypeStruct((N,), jnp.float32), 1)
    lazy.counters.reset()
    value = lazy.materialize(node)
    taken = lazy.counters.take()
    assert float(value[0]) == 3.0 and (taken["programs"], taken["h2d_arrays"]) == (1, 1)
    assert taken["h2d_s"] >= 0.05 and taken["dispatch_s"] > 0
    assert taken["host_s"] < 0.04, "neither the copy nor the runner's call is the shim's own host time"


def test_a_wait_is_a_stage_of_its_own_and_the_copy_comes_after_it(np):
    a = np.ones(N, dtype="float32") * 3.0
    assert a.block_until_ready() is a
    taken = lazy.counters.take()
    assert taken["wait_s"] > 0 and taken["programs"] == 1 and taken["d2h_arrays"] == 0, "a wait copies nothing"
    host = lazy.fetch(a._arr)
    taken = lazy.counters.take()
    assert type(host) is real_np.ndarray and host[0] == 3.0
    assert taken["wait_s"] > 0 and taken["d2h_s"] > 0 and (taken["d2h_arrays"], taken["d2h_bytes"]) == (1, 4 * N)


def test_the_histogram_program_and_an_eager_call_are_dispatches(np):
    a = np.linspace(0.0, 1.0, N, dtype="float32")
    a.block_until_ready()
    lazy.counters.take()
    counts, _edges = np.histogram(a, bins=10)
    taken = lazy.counters.take()
    assert taken["histograms"] == 1 and taken["dispatch_s"] > 0
    before = taken["dispatch_s"]
    parts = np.split(a, 2)  # eager only (`_EAGER_ONLY`): `shim.eager_device`
    taken = lazy.counters.take()
    assert len(parts) == 2 and taken["programs"] == 0 and taken["dispatch_s"] > 0 and before > 0


SITES = {
    "__array__": lambda t, tmp: real_np.asarray(t),
    "float": lambda t, tmp: float(t[0]),
    "int": lambda t, tmp: int(t[1]),
    "index": lambda t, tmp: list(range(4))[t.astype("int32")[1]],
    "bool": lambda t, tmp: bool(t[1]),
    "complex": lambda t, tmp: complex(t[1]),
    "item": lambda t, tmp: t.item(2),
    "tolist": lambda t, tmp: t.tolist(),
    "tobytes": lambda t, tmp: t.tobytes(),
    "tofile": lambda t, tmp: t.tofile(tmp / "site.bin"),
    "repr": lambda t, tmp: repr(t),
    "str": lambda t, tmp: str(t),
    "format": lambda t, tmp: f"{t}",
    "format of a scalar": lambda t, tmp: f"{t[1]:.3f}",
    "iter": lambda t, tmp: list(t),
    "flat": lambda t, tmp: list(t.flat),
    "astype to a host dtype": lambda t, tmp: t.astype("int64"),
    "astype under numpy's casting": lambda t, tmp: t.astype("float64", casting="safe"),
    "a reduction numpy promotes": lambda t, tmp: t.astype("int32").sum(),
    "an operator with a 64-bit integer": lambda t, tmp: t + real_np.arange(N, dtype="int64"),
    "a function that falls back": lambda t, tmp: shim._unwrap_np([t]),
}


@pytest.mark.parametrize("site", SITES)
def test_every_way_to_a_host_value_is_one_counted_copy(np, tmp_path, site):
    """A site added later that copies without `lazy.fetch` counts nothing
    here, and reads a device value where no stage sees it."""
    t = np.arange(N, dtype="float32") + 0.0
    t.block_until_ready()
    if site in ("index", "a reduction numpy promotes"):
        t = t.astype("int32") if site != "index" else t
    lazy.counters.take()
    SITES[site](t, tmp_path)
    taken = lazy.counters.take()
    assert taken["d2h_arrays"] == 1 and taken["d2h_bytes"] > 0 and taken["d2h_s"] > 0 and taken["wait_s"] > 0


@pytest.mark.parametrize("convert, error", [(float, TypeError), (int, TypeError), (complex, TypeError), (bool, ValueError)])
def test_what_is_no_scalar_is_refused_before_anything_is_copied(np, convert, error):
    t = np.arange(N, dtype="float32") + 0.0
    t.block_until_ready()
    lazy.counters.take()
    with pytest.raises(error):
        convert(t)
    assert lazy.counters.take()["d2h_arrays"] == 0
    one = t[:1]
    assert bool(one + 1.0) is True, "numpy's and jax's rule: an array of one element has a truth value"


def test_no_site_of_the_shim_copies_to_the_host_by_itself():
    """The source's side of the table above: a device value becomes a host
    value in `lazy.fetch` and nowhere else of the package."""
    package = Path(lazy.__file__).parent
    direct = re.compile(r"real_np\.(asarray|array)\([^()]*(\._arr|\._force\(\)|_concrete)\b|\b(float|int|bool|complex)\(self\._arr\)|_arr\.item\(")
    for name in ("shim.py", "random.py", "lazy.py", "stencil.py"):
        for number, line in enumerate((package / name).read_text().splitlines(), 1):
            assert not direct.search(line), f"{name}:{number} copies a device value without lazy.fetch: {line.strip()}"
    fetch = (package / "lazy.py").read_text()
    assert fetch.count("real_np.asarray(arr)") == 1, "the one copy, in `fetch`"
