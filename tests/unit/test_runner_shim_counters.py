"""The warm runner takes the numpy shim's counters at the end of a turn, the
way it takes its stage clocks, and `/reset` zeroes them (ISSUE 31); the
control plane stamps them into `Result.phases` under fixed names, as numbers,
and none of them is a latency. On the modules themselves: no server, no
timing."""

import importlib.util
from pathlib import Path

import pytest

from bee_code_interpreter_fs_tpu.ops import npdispatch
from bee_code_interpreter_fs_tpu.services.code_executor import (
    LATENCY_PHASES,
    SHIM_PHASES,
    STAGE_PHASES,
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.perf_observer import OBSERVED_PHASES

RUNNER_PY = Path(__file__).resolve().parents[2] / "executor" / "runner.py"
COUNTERS = ("programs", "exec_cache_misses", "nodes", "flushes", "load_files", "load_bytes", "load_s",
            "h2d_arrays", "h2d_bytes", "h2d_s", "donated_bytes", "aligned_stores", "kernel_stores", "histograms",
            "dots", "dot_flops", "ufunc_methods", "fallbacks", "host_s", "dispatch_s", "wait_s",
            "d2h_arrays", "d2h_bytes", "d2h_s")
# the stages of a turn's user code and their counts (ISSUE 39)
STAGE_COUNTERS = ("load_files", "load_bytes", "load_s", "dispatch_s", "wait_s", "d2h_arrays", "d2h_bytes", "d2h_s")


@pytest.fixture()
def runner():
    spec = importlib.util.spec_from_file_location("runner_under_test_shim", RUNNER_PY)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def run_script(runner, tmp_path, text: str) -> None:
    script = tmp_path / "script.py"
    script.write_text(text)
    runner._begin_stages("prepare", runner.time.monotonic())
    code, violation = runner._run_one({
        "source_path": str(script), "stdout_path": str(tmp_path / "out"),
        "stderr_path": str(tmp_path / "err"), "env": {},
    })
    assert (code, violation) == (0, None), (tmp_path / "err").read_text()


ARRAY_TURN = "import numpy as np\na = np.ones(5000, dtype='float32')\na += 1.0\nprint(float(a.sum()))\n"


def test_a_runner_without_the_shim_takes_nothing(runner, tmp_path):
    run_script(runner, tmp_path, ARRAY_TURN)
    assert runner._take_shim() is None


def test_a_turns_counters_are_taken_once_and_a_turn_without_arrays_reads_zero(runner, tmp_path):
    npdispatch.install(threshold=1000)
    try:
        runner._take_shim()
        run_script(runner, tmp_path, ARRAY_TURN)
        taken = runner._take_shim()
        assert tuple(taken) == COUNTERS
        assert taken["programs"] == 1 and taken["nodes"] == 3 and taken["flushes"] == 0
        assert taken["h2d_bytes"] == 0 and taken["host_s"] > 0
        assert taken["dispatch_s"] > 0 and taken["wait_s"] > 0 and taken["load_files"] == 0
        assert (taken["d2h_arrays"], taken["d2h_bytes"]) == (1, 4) and taken["d2h_s"] > 0, "the printed sum"
        assert runner._take_shim() == dict.fromkeys(COUNTERS, 0), "taken is zeroed"
        run_script(runner, tmp_path, "print(6 * 7)\n")
        assert runner._take_shim() == dict.fromkeys(COUNTERS, 0), "present, and 0 where the shim did nothing"
    finally:
        npdispatch.uninstall()


# over threshold=1000: a product, a chain of three, three steps of all-pairs paths (ISSUE 37)
LINALG_TURNS = {
    "gemm": ("import numpy as np\n"
             "A = np.fromfunction(lambda i, j: (i * j + 1) % 7 / 7, (40, 50), dtype=np.float32)\n"
             "B = np.fromfunction(lambda i, j: (i * j + 2) % 5 / 5, (50, 60), dtype=np.float32)\n"
             "C = np.fromfunction(lambda i, j: (i + j) % 3 / 3, (40, 60), dtype=np.float32)\n"
             "C[:] = 1.5 * A @ B + 1.2 * C\nprint(float(C.sum()))\n",
             {"dots": 1, "dot_flops": 2 * 40 * 50 * 60, "ufunc_methods": 0}),
    "k3mm": ("import numpy as np\n"
             "A, B, C, D = (np.fromfunction(lambda i, j: (i * j + 1) % 7 / 7, s, dtype=np.float32)\n"
             "              for s in ((40, 50), (50, 45), (45, 60), (60, 55)))\n"
             "print(float((A @ B @ C @ D).sum()))\n",
             {"dots": 3, "dot_flops": 2 * 40 * (50 * 45 + 45 * 60 + 60 * 55), "ufunc_methods": 0}),
    "floyd_warshall": ("import numpy as np\n"
                       "path = np.fromfunction(lambda i, j: i * j % 7 + 1, (40, 40), dtype=np.int32)\n"
                       "for k in range(3):\n"
                       "    path[:] = np.minimum(path[:], np.add.outer(path[:, k], path[k, :]))\n"
                       "print(int(path[3, 5]))\n",
                       {"dots": 0, "dot_flops": 0, "ufunc_methods": 3}),
    "nothing": ("print(6 * 7)\n", {"dots": 0, "dot_flops": 0, "ufunc_methods": 0}),
}


@pytest.mark.parametrize("name", LINALG_TURNS)
def test_a_turns_contractions_and_ufunc_methods_are_taken_and_stamped(runner, tmp_path, name):
    """From the user's code to `Result.phases`: the runner takes the three
    counters with the others, the control plane stamps them as numbers, 0 on
    a turn that did nothing."""
    source, want = LINALG_TURNS[name]
    npdispatch.install(threshold=1000)
    try:
        runner._take_shim()
        run_script(runner, tmp_path, source)
        taken = runner._take_shim()
    finally:
        npdispatch.uninstall()
    assert {key: taken[key] for key in want} == want and taken["fallbacks"] == 0
    phases = CodeExecutor._shim_phases({"shim": taken})
    assert {key: phases[SHIM_PHASES[key]] for key in want} == {key: float(value) for key, value in want.items()}


def test_reset_zeroes_what_a_turn_left_untaken(runner, tmp_path):
    """A turn that died before its reply (a timeout kill is a respawn, but a
    batch or a snapshot op in between is not) leaves counts behind: the next
    tenant's first turn must not be stamped with them."""
    from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy

    npdispatch.install(threshold=1000)
    try:
        lazy.counters.reset()
        run_script(runner, tmp_path, ARRAY_TURN)
        assert lazy.counters.programs == 1
        # the main loop's reset branch, as far as the counters go
        source = RUNNER_PY.read_text()
        branch = source[source.index('if req.get("op") == "reset":'):source.index('elif req.get("op") == "snapshot":')]
        assert "_take_shim()" in branch
        runner._take_shim()
        assert lazy.counters.programs == 0
    finally:
        npdispatch.uninstall()


def test_a_counter_that_fails_never_fails_the_turn(runner, monkeypatch):
    class Broken:
        @staticmethod
        def take_counters():
            raise RuntimeError("boom")

    monkeypatch.setitem(runner.sys.modules, "bee_code_interpreter_fs_tpu.ops.npdispatch", Broken)
    assert runner._take_shim() is None


# -- the control plane's side


def test_shim_phases_are_stamped_under_fixed_names_as_numbers():
    body = {"shim": {"programs": 5, "exec_cache_misses": 0, "nodes": 306, "flushes": 1, "h2d_arrays": 8, "h2d_bytes": 1114112,
                     "h2d_s": 0.0123456789, "donated_bytes": 4831838208, "aligned_stores": 26, "kernel_stores": 24, "histograms": 4, "dots": 3, "dot_flops": 5280000000000, "ufunc_methods": 16, "fallbacks": 2, "host_s": 0.123456789, "minted_by_user_code": 7}}
    phases = CodeExecutor._shim_phases(body)
    assert phases == {
        "shim_programs": 5.0, "shim_exec_cache_misses": 0.0, "shim_nodes": 306.0, "shim_flushes": 1.0,
        "shim_h2d_arrays": 8.0, "shim_h2d_bytes": 1114112.0, "shim_h2d": 0.012346, "shim_donated_bytes": 4831838208.0, "shim_aligned_stores": 26.0, "shim_kernel_stores": 24.0, "shim_histograms": 4.0, "shim_dots": 3.0, "shim_dot_flops": 5280000000000.0, "shim_ufunc_methods": 16.0, "shim_fallbacks": 2.0, "shim_host": 0.123457,
    }, "and none of the stage keys from a block without them (a runner from before ISSUE 39)"
    assert all(isinstance(v, float) for v in phases.values())


def test_the_stages_of_the_users_code_are_stamped_from_a_block_that_holds_them():
    """ISSUE 39: numpy's reads, the dispatches, the waits and the copies back,
    under `shim_<name>` with a duration's `_s` left off; a non-number reads 0;
    a block without them stamps none of them, so that a stage metric finds
    nothing to read in a turn of an older runner, never a 0."""
    block = {"load_files": 8, "load_bytes": 1610612736, "load_s": 1.8512345678, "dispatch_s": 0.0041, "wait_s": 0.0258,
             "d2h_arrays": 5, "d2h_bytes": 36, "d2h_s": "fast", "programs": 1}
    assert CodeExecutor._shim_phases({"shim": block}) == {
        "shim_load_files": 8.0, "shim_load_bytes": 1610612736.0, "shim_load": 1.851235, "shim_dispatch": 0.0041,
        "shim_wait": 0.0258, "shim_d2h_arrays": 5.0, "shim_d2h_bytes": 36.0, "shim_d2h": 0.0, "shim_programs": 1.0,
    }
    older = CodeExecutor._shim_phases({"shim": {"programs": 1, "host_s": 0.01}})
    assert older == {"shim_programs": 1.0, "shim_host": 0.01}
    assert not {SHIM_PHASES[name] for name in STAGE_COUNTERS} & set(older)


@pytest.mark.parametrize("body, want", [
    ({"user_cpu_s": 1.3000004}, {"runner_user_cpu": 1.3}),
    ({"user_cpu_s": 0}, {"runner_user_cpu": 0.0}),
    ({"user_cpu_s": -0.5}, {"runner_user_cpu": 0.0}),
    ({}, {}), ({"user_cpu_s": None}, {}), ({"user_cpu_s": "1.3"}, {}), ({"user_cpu_s": True}, {}),
])
def test_the_turns_host_cpu_is_stamped_from_a_reply_that_has_it(body, want):
    """`runner_user_cpu` from the reply's `user_cpu_s`, a number of its own
    beside the `shim` block; nothing from a cold run or an older runner."""
    assert CodeExecutor._user_cpu_phase(body) == want
    assert "runner_user_cpu" not in LATENCY_PHASES and "runner_user_cpu" not in OBSERVED_PHASES


def test_the_runner_measures_the_cpu_of_the_users_code_and_hands_it_over_once(runner, tmp_path):
    run_script(runner, tmp_path, "x = 0\nfor i in range(300000):\n    x += i * i\nprint(x)\n")
    stages = dict((name, seconds) for name, _, seconds in runner._take_stages(runner.time.monotonic()) or [])
    cpu = runner._take_user_cpu()
    assert isinstance(cpu, float) and 0 < cpu <= stages["user_code"] + 0.05, "one thread computed: CPU is about the wall"
    assert runner._take_user_cpu() is None, "taken once"
    run_script(runner, tmp_path, "import time\ntime.sleep(0.2)\n")
    assert runner._take_user_cpu() < 0.1, "a turn that slept used no CPU"


@pytest.mark.parametrize("body", [{}, {"shim": None}, {"shim": "5"}, {"shim": [1, 2]}])
def test_no_shim_block_no_shim_phase(body):
    """No shim installed, a cold run, an older binary: the keys are absent,
    and a per-turn metric that reads them finds nothing to read."""
    assert CodeExecutor._shim_phases(body) == {}


def test_a_block_from_the_users_process_is_read_as_numbers_only():
    phases = CodeExecutor._shim_phases({"shim": {"programs": "many", "nodes": True, "flushes": -3, "host_s": None}})
    assert phases == dict.fromkeys(["shim_programs", "shim_nodes", "shim_flushes", "shim_host"], 0.0)
    phases = CodeExecutor._shim_phases({"shim": dict.fromkeys(SHIM_PHASES, "many")})
    assert phases == dict.fromkeys(SHIM_PHASES.values(), 0.0)


@pytest.mark.parametrize("key", ["shim_dots", "shim_dot_flops", "shim_ufunc_methods"])
def test_the_linalg_keys_are_stamped_and_are_no_latency(key):
    """Nothing from a block that lacks the counter (a runner from before it:
    no key, since ISSUE 39, where it read 0), the number where it has it, and
    in no histogram's allowlist."""
    assert key in SHIM_PHASES.values()
    name = next(name for name, stamped in SHIM_PHASES.items() if stamped == key)
    assert key not in CodeExecutor._shim_phases({"shim": {"programs": 1}})
    assert CodeExecutor._shim_phases({"shim": {name: 17437680000000}})[key] == 17437680000000.0
    assert key not in LATENCY_PHASES and key not in OBSERVED_PHASES and key not in STAGE_PHASES


def test_no_shim_phase_is_a_latency_phase():
    """The histogram's allowlist and the perf observer's baselines see none
    of the new keys (the PR 6 / PR 7 discipline)."""
    keys = set(SHIM_PHASES.values())
    assert len(keys) == 24 and tuple(SHIM_PHASES) == COUNTERS
    assert "runner_user_cpu" not in keys and "auto_profiled" not in keys
    assert not keys & LATENCY_PHASES
    assert not keys & set(OBSERVED_PHASES)
    assert not keys & set(STAGE_PHASES)


def test_one_list_of_the_shims_fields():
    """The meanings are in `lazy.Counters`' docstring, the names in what its
    `reset` sets, the mapping in `SHIM_PHASES`: the three agree, and nothing
    else spells the list out (ISSUE 39)."""
    from bee_code_interpreter_fs_tpu.ops.npdispatch import lazy

    assert lazy.Counters.FIELDS == COUNTERS == tuple(lazy.Counters().take())
    for name in COUNTERS:
        assert f"\n    {name} " in lazy.Counters.__doc__, f"{name} has no line in Counters' docstring"
        assert SHIM_PHASES[name] == "shim_" + (name[:-2] if name.endswith("_s") else name)
    assert "lazy.Counters" in npdispatch.take_counters.__doc__ and "programs" not in npdispatch.take_counters.__doc__
    taker = RUNNER_PY.read_text()
    docstring = taker[taker.index("def _take_shim()"):taker.index("shim = sys.modules.get(")]
    assert "lazy.Counters" in docstring and "flushes" not in docstring


def test_the_attach_is_timed_step_by_step(runner, monkeypatch):
    """ISSUE 39: the ready line says where the attach's seconds went, in the
    order the steps run; a runner that imports no jax has no attach to time."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    info = runner._warm_import()
    assert info["ready"] and list(info["attach_stages"]) == [
        "interpreter_start", "import_jax", "distributed_init", "devices", "first_compile"]
    assert 0.0 < info["attach_stages"]["interpreter_start"] < 3600.0, "this process's age: /proc's record"
    assert all(isinstance(seconds, float) and seconds >= 0.0 for seconds in info["attach_stages"].values())
    monkeypatch.setenv("APP_WARM_IMPORT_JAX", "0")
    assert "attach_stages" not in runner._warm_import()
