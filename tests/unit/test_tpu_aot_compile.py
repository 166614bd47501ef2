"""Ask the TPU's compiler, without a TPU: the kernels and programs of the
served main path, compiled ahead of time at their real sizes for a DESCRIBED
v5e:2x2 topology (on-chip-measurement guide §2, third rehearsal). What the
chip's compiler refuses — a tile not aligned to Mosaic's rules, a kernel over
its VMEM budget, a program that does not fit 16 GB — fails here and costs no
chip time. Nothing runs on a device, so this says nothing about results or
speed; chip_smoke.py does.

The topology is described inside a module-scoped fixture and only there:
describing it loads the TPU's library, which one process at a time may hold,
so nothing here touches it at import, in a skipif, in a parametrize argument
or in conftest.py, and every compile happens in the test's own process. Keep
these tests in this one file (a second file may go to another xdist worker,
where the fixture would skip in silence).
"""

import json
import re
import runpy
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bee_code_interpreter_fs_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_partial,
)

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps it from being described
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


class _AotJit:
    """Stands in for `jax.jit` around code that jits and calls in one go (the
    shim's lazy engine, the pre-warm snippets): a call compiles the function
    for the described chip at the arguments' shapes, keeps the executable,
    and returns zeros of the output shape — nothing executes anywhere."""

    def __init__(self, sharding, zeros=jnp.zeros):
        self.real_jit = jax.jit
        self.sharding = sharding  # None: the program's own mesh names devices
        self.zeros = zeros
        self.compiled = []

    def __call__(self, fn, **jit_kwargs):
        def call(*args):
            specs = jax.tree.map(
                lambda a: _spec(a.shape, a.dtype, self.sharding), args
            )
            placed = dict(jit_kwargs)
            if not args and self.sharding is not None:
                # no argument names the chip (a program that creates its
                # arrays): its outputs do, or it compiles for the CPU
                placed["out_shardings"] = self.sharding
            self.compiled.append(
                self.real_jit(fn, **placed).lower(*specs).compile()
            )
            return jax.tree.map(
                lambda o: self.zeros(o.shape, o.dtype), jax.eval_shape(fn, *args)
            )

        return call


def _shim_jits_ahead_of_time(monkeypatch, aot: _AotJit) -> _AotJit:
    """The shim's lazy engine with its `jax.jit` swapped for `aot`, its
    programs' arrays taken to live where `aot` compiles for (they are zeros
    on the host: nothing here touches a chip), and an empty runner cache,
    until the test ends."""
    from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy

    class JaxWithAotJit:
        jit = staticmethod(aot)

        def __getattr__(self, name):
            return getattr(jax, name)

    monkeypatch.setattr(lazy, "jax", JaxWithAotJit())
    monkeypatch.setattr(lazy, "_exec_cache", {})
    if aot.sharding is not None:
        (device,) = aot.sharding.device_set
        monkeypatch.setattr(lazy, "_platform", lambda leaves: device.platform)
    return aot


@pytest.mark.parametrize(
    "shape,dtype,kwargs",
    [
        # chip_smoke.py's shape (examples/benchmark-attention.py)
        ((1, 16384, 4, 128), jnp.bfloat16, {}),
        # Llama width: 32 heads of 128
        ((1, 4096, 32, 128), jnp.bfloat16, {}),
        # a ragged length: the padding + block-clamp path
        ((1, 900, 4, 128), jnp.bfloat16, {}),
        ((1, 2048, 8, 64), jnp.bfloat16, {}),
        # sliding window with attention sinks, f32
        ((1, 4096, 8, 128), jnp.float32, {"window": 1024, "sinks": 4}),
    ],
)
def test_flash_attention_compiles_for_v5e(one_chip, shape, dtype, kwargs):
    x = _spec(shape, dtype, one_chip)
    compiled = (
        jax.jit(lambda q, k, v: flash_attention(q, k, v, **kwargs))
        .lower(x, x, x)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()  # Mosaic, not the interpreter
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_flash_attention_partial_compiles_for_v5e(one_chip):
    b, t, h, d = 1, 4096, 8, 128
    x = _spec((b, t, h, d), jnp.bfloat16, one_chip)
    acc = _spec((b, h, t, d), jnp.float32, one_chip)
    stat = _spec((b, h, t), jnp.float32, one_chip)
    compiled = (
        jax.jit(
            lambda q, k, v, acc, m, l: flash_attention_partial(
                q, k, v, acc, m, l, q_offset=0, k_offset=0
            )
        )
        .lower(x, x, x, acc, stat, stat)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_llama_flash_forward_compiles_for_v5e(one_chip, monkeypatch):
    """One models/llama.py forward at Llama-2-7B widths (depth cut to two
    layers) with attn_impl="flash". The model picks interpret mode from
    jax.default_backend(), which under AOT still says `cpu` — the test says
    `tpu`, so the Mosaic branch is what gets compiled."""
    from bee_code_interpreter_fs_tpu.models.llama import (
        LlamaConfig,
        forward,
        init_params,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LlamaConfig(n_layers=2, attn_impl="flash")
    params = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)),
    )
    tokens = _spec((1, cfg.max_seq_len), jnp.int32, one_chip)
    compiled = (
        jax.jit(lambda p, t: forward(p, t, cfg)).lower(params, tokens).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_headline_payload_programs_fit_one_chip(one_chip, monkeypatch):
    """examples/benchmark-numpy.py, unchanged at N = 1e8, run against the
    shim with its lazy engine's jit swapped for the AOT one: every fused
    program the engine emits for it ((b*b).sum(), b + 1e-9, the random
    draw) compiles for one v5e chip and fits its 16 GB beside the live
    arrays the payload holds (a, b and a temporary: 400 MB each in f32)."""
    from bee_code_interpreter_fs_tpu.ops import npdispatch

    aot = _shim_jits_ahead_of_time(monkeypatch, _AotJit(one_chip))
    npdispatch.install()
    try:
        runpy.run_path(
            str(REPO_ROOT / "examples" / "benchmark-numpy.py"), run_name="__main__"
        )
    finally:
        npdispatch.uninstall()
    assert len(aot.compiled) >= 3  # the draw, the single shot, the chained passes
    live_arrays = 4 * 100_000_000 * 4
    for compiled in aot.compiled:
        assert _device_bytes(compiled) + live_arrays < V5E_HBM_BYTES


def _untouched_zeros(shape, dtype):
    """Zeros that cost no memory until they are read, which nothing here
    does: numpy's untouched pages, handed to jax as they are. (2.4 GB a grid
    otherwise, on a machine this sandbox shares.)"""
    return jax.dlpack.from_dlpack(np.zeros(shape, dtype))


def _entry_ops(compiled, min_elements: int) -> list[tuple[str, set[str], list[str]]]:
    """(opcode, the arrays of at least `min_elements` in the result's type,
    the operands' types) of every instruction of the entry computation whose
    result holds such an array."""
    text = compiled.as_text()
    entry = text[text.index("ENTRY "):]
    instruction = r"^\s*(?:ROOT )?(%[\w.-]+) = (\(?[a-z0-9]+\[[^=]*?) ([a-z-]+)\(([^)]*)\)"
    types = {name: result for name, result, _, _ in re.findall(instruction, entry, re.M)}
    found = []
    for _, result, opcode, operands in re.findall(instruction, entry, re.M):
        big = {
            f"{dtype}[{dims}]" for dtype, dims in re.findall(r"([a-z0-9]+)\[([0-9,]+)\]", result)
            if np.prod([int(d) for d in dims.split(",")]) >= min_elements
        }
        if big:
            found.append((opcode, big, [types.get(o.strip(), "") for o in operands.split(",")]))
    return found


@pytest.mark.parametrize("payload, steps, grids, stores_a_step", [
    ("jacobi_2d", {"TSTEPS": 3}, 2, 2),
    ("fdtd_2d", {"TMAX": 2}, 3, 3),
])
def test_npbench_stencil_programs_are_one_aligned_pass_per_window_store(
    one_chip, monkeypatch, capsys, payload, steps, grids, stores_a_step
):
    """`benchmarks/chip/payloads/jacobi_2d.py` and `fdtd_2d.py` at the run's
    grid sizes, two time steps, through the shim with its jit swapped for the
    AOT one and its plan told that the arrays live on the described chip. A
    window store (`B[1:-1, 1:-1] = 0.2 * (A[...] + ...)`) is ONE Mosaic
    `custom-call` whose operands are whole grids and whose result takes its
    target's buffer (`stencil.window_store`): no window-shaped temporary, no
    `dynamic-update-slice` of one, no copy of a grid, and Mosaic took the
    tiles and the VMEM the kernel states. Structure only."""
    from bee_code_interpreter_fs_tpu.ops import npdispatch
    from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy

    payloads = REPO_ROOT / "benchmarks" / "chip" / "payloads"
    params = {**json.loads((payloads / f"{payload}.json").read_text())["params"], **steps}
    aot = _shim_jits_ahead_of_time(monkeypatch, _AotJit(one_chip, zeros=_untouched_zeros))
    npdispatch.install()
    try:
        lazy.counters.reset()
        runpy.run_path(str(payloads / f"{payload}.py"), init_globals={"P": params}, run_name="__main__")
        taken = lazy.counters.take()
    finally:
        npdispatch.uninstall()
    assert capsys.readouterr().out.startswith(f"{payload} ")
    n_steps = 2
    assert taken["kernel_stores"] == taken["aligned_stores"] == stores_a_step * n_steps
    assert taken["fallbacks"] == 0

    side = [v for k, v in params.items() if k in ("N", "NX", "NY")]
    grid_shape = f"f32[{side[0]},{side[-1]}]"
    grid_bytes = 4 * side[0] * side[-1]
    kernel = aot.compiled[0]  # creation and the time loop: the first value asked for needs them all
    big = _entry_ops(kernel, side[0] * side[-1] // 2)
    for opcode, arrays, operands in big:
        # every big value is a whole grid, never a window of one, and none is a copy of another
        assert arrays == {grid_shape}, (opcode, arrays)
        assert opcode in ("fusion", "custom-call", "get-tuple-element", "tuple", "dynamic-update-slice"), (opcode, arrays)
        if opcode == "dynamic-update-slice":  # fdtd's `ey[0, :] = _fict_[t]`, a row in place: today's lowering
            assert grid_shape in operands[0] and grid_shape not in operands[1], operands
        if opcode == "custom-call":
            # the target first, every source after it, each a whole grid; of a target
            # that the expression does not read (jacobi's) only four edges besides
            edges = [o for o in operands if o and grid_shape not in o]  # ("": one behind an index comment)
            assert grid_shape in operands[0] and len(edges) == (4 if payload == "jacobi_2d" else 0), operands
            assert all(f"f32[8,{side[-1]}]" in o or f"f32[{side[0]},128]" in o for o in edges), edges
    # one kernel a store, and nothing else over a grid but the creation of each
    assert sum(opcode == "custom-call" for opcode, _, _ in big) == stores_a_step * n_steps
    assert kernel.as_text().count('custom_call_target="tpu_custom_call"') == stores_a_step * n_steps
    assert sum(opcode == "fusion" for opcode, _, _ in big) <= grids
    # No temporary of a grid's size: the kernels write into the grids the program
    # made. (The edges of jacobi's target, sliced off for one store at a time,
    # are two registers of every row and two strips: a ninetieth of a grid.)
    assert kernel.memory_analysis().temp_size_in_bytes < 0.02 * grid_bytes
    for compiled in aot.compiled:
        assert _device_bytes(compiled) + grids * grid_bytes < V5E_HBM_BYTES


# (payload, the matrices the turn holds when its first value is asked for, the
# `dot_general`s of its one big program)
LINALG = [
    ("gemm", lambda p: 4 * (p["NI"] * p["NJ"] + p["NI"] * p["NK"] + p["NK"] * p["NJ"]), 1),
    ("k3mm", lambda p: 4 * (p["NI"] * p["NK"] + p["NK"] * p["NJ"] + p["NJ"] * p["NM"] + p["NM"] * p["NL"]
                            + p["NI"] * p["NL"]), 3),
    ("floyd_warshall", lambda p: 4 * p["N"] * p["N"], 0),
]


@pytest.mark.parametrize("payload, held_bytes, products", LINALG, ids=[case[0] for case in LINALG])
def test_nplinalg_payload_programs_fit_one_chip(one_chip, monkeypatch, capsys, payload, held_bytes, products):
    """`benchmarks/chip/payloads/gemm.py`, `k3mm.py` and `floyd_warshall.py`
    at the run's sizes, through the shim with its jit swapped for the AOT one:
    every program of the turn compiles for one v5e chip; the first, which
    makes the matrices, multiplies them and takes the printed elements, fits
    the chip's 16 GB and returns what the turn holds, with temporaries of
    less than that (gemm's product is written where C goes; k3mm's two
    intermediates); no operand of a product is held in bfloat16; and
    floyd_warshall's sixteen steps are one program in s32 with no 64-bit
    value of the matrix's size. The counters read the payloads' floors at
    the run's sizes. Structure and sizes only."""
    from bee_code_interpreter_fs_tpu.ops import npdispatch
    from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy

    payloads = REPO_ROOT / "benchmarks" / "chip" / "payloads"
    params = json.loads((payloads / f"{payload}.json").read_text())["params"]
    aot = _shim_jits_ahead_of_time(monkeypatch, _AotJit(one_chip, zeros=_untouched_zeros))
    npdispatch.install()
    try:
        lazy.counters.reset()
        runpy.run_path(str(payloads / f"{payload}.py"), init_globals={"P": params}, run_name="__main__")
        taken = lazy.counters.take()
    finally:
        npdispatch.uninstall()
    assert capsys.readouterr().out.startswith(f"{payload} ")
    floor = json.loads((payloads / f"{payload}.json").read_text())["floor"]
    assert taken["dots"] == products and taken["fallbacks"] == 0 and taken["programs"] == len(aot.compiled) == 2
    assert taken["dot_flops"] == eval(floor.get("flops", "0"), {"__builtins__": {}}, dict(params))
    assert taken["ufunc_methods"] == (params["K"] if payload == "floyd_warshall" else 0)
    main = aot.compiled[0]
    text = main.as_text()
    held = held_bytes(params)
    memory = main.memory_analysis()
    # what the program returns is what the turn holds (the picked elements besides)
    assert held <= memory.output_size_in_bytes < 1.01 * held  # (rows padded to the tiling)
    assert _device_bytes(main) < V5E_HBM_BYTES
    assert memory.temp_size_in_bytes < held
    if payload == "floyd_warshall":
        assert "s64[" not in text and "f32[16800" not in text
    else:
        assert not re.search(r"bf16\[\d{4,},\d{4,}\]", text), "no operand of a product is rounded to bfloat16"
    for compiled in aot.compiled:
        assert _device_bytes(compiled) + held < V5E_HBM_BYTES


@pytest.mark.parametrize("weighted", [False, True], ids=["counts", "weighted"])
def test_the_histogram_is_one_program_with_its_edges_as_an_operand(one_chip, weighted):
    """`np.histogram` over `npfiles.c3`'s vector (`azimint_hist`: N 10,000,000,
    npt 1000): the shim's program compiled for the described chip. The edges
    are a parameter, so two datasets of one shape share the executable; no
    `while` (the scan `jnp.histogram` searches with) and no gather; and the
    comparison of every element against every edge is never written out: the
    temporaries stay under three times the vector's bytes (a padded copy of
    each operand and the partial sums), where `N x (bins + 1)` would be 10 GB."""
    from bee_code_interpreter_fs_tpu.ops.npdispatch import shim

    n, edges = 10_000_000, 1001
    vector = _spec((n,), jnp.float32, one_chip)
    compiled = shim._histogram_program.lower(
        vector, _spec((edges,), jnp.float32, one_chip), vector if weighted else None).compile()
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    parameters = re.findall(r"= (\S+) parameter\(\d\)", entry[:entry.index("\n}")])
    assert sorted(p.split("{")[0] for p in parameters) == sorted(
        ["f32[10000000]", "f32[1001]"] + ["f32[10000000]"] * weighted)
    constants = re.findall(r"= (\S+) constant\(", text)
    assert constants and not any(c.startswith(("f32[1001]", "f32[1000]")) for c in constants), "the edges are no constant"
    assert " while(" not in text and " gather(" not in text and " scatter(" not in text
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 3 * 4 * n
    assert memory.output_size_in_bytes <= 4096, "the bins, padded to a tile"


def test_prewarm_kernel_set_compiles_for_v5e(topo, one_chip, monkeypatch):
    """The pre-warm snippets run at every service start
    (services/compile_cache.PREWARM_SOURCES). Each is executed with jax.jit
    swapped for the AOT one; the fused-dispatch snippet builds its "jobs"
    mesh from jax.devices(), which here are the described four chips."""
    from bee_code_interpreter_fs_tpu.services.compile_cache import PREWARM_SOURCES

    for name, source in PREWARM_SOURCES:
        on_mesh = name == "batched_dispatch"
        aot = _AotJit(None if on_mesh else one_chip)
        with monkeypatch.context() as patch:
            patch.setattr(jax, "jit", aot)
            if on_mesh:
                patch.setattr(jax, "devices", lambda: list(topo.devices))
            exec(compile(source, f"<prewarm {name}>", "exec"), {"__name__": "__main__"})
        assert len(aot.compiled) == 1, name
        if on_mesh:
            # one program over all four chips: each holds its own block
            assert len(aot.compiled[0].input_shardings[0][0].device_set) == 4
    assert {name for name, _ in PREWARM_SOURCES} >= {"matmul", "batched_dispatch"}
