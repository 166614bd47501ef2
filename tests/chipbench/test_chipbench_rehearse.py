"""`run.py --rehearse` end to end through the real service at tiny sizes on
a stated CPU, and the comparison seeing `correct` come out false: for the
lower-precision control, and for each fault of the timed path that a cell
can have (an answer altered where it is produced, an exit code lost, a
changed file dropped or leaked, a session's order broken, a turn never
answered). All in this one file, so that one worker runs the service.

Nothing here is keyed by a cell's or a metric's name: a cell's faults follow
the `order` of its traffic file, and the metrics a rehearsal may not print
are those whose `source` is `device_trace`. A cell that a later PR adds as
data gets every case of this file with no edit to it."""

import contextlib
import copy
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench_helpers import (ALL_CELLS, BENCH, CELLS, ROOT, SESSIONS_CELL, SESSIONS_JSON, load_runner,
                               manifest_of, order_of)
from lib import compare

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def cell_args(cell: str) -> list[str]:
    return ["--workload", cell] + (["--benchmark-json", str(SESSIONS_JSON)] if cell == SESSIONS_CELL else [])


def rehearse(cell: str, *extra: str) -> tuple[int, dict | None, str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), *cell_args(cell), "--seed", "2147483650",
         "--seconds", "2", "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    lines = proc.stdout.strip().splitlines()
    return proc.returncode, (json.loads(lines[-1]) if lines else None), proc.stderr[-3000:]


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_rehearse_prints_the_contracts_last_line(cell):
    code, line, err = rehearse(cell, "--trace", "0")
    assert code == 0 and line is not None, err
    assert list(line) == CONTRACT_KEYS + ["checks"], "the compared numbers come last, under a key of their own"
    assert line["correct"] is True, err
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and "busy_s" not in line["device"]
    assert set(line["metrics"]) >= {"turn_p90_ms", "setup_s"}
    for value, limit in (v for v in line["checks"].values() if isinstance(v, list)):
        assert value <= limit
    assert "compared (value, limit)" in err.splitlines()[-1]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_traced_reports_layer_metrics_and_no_device_metric(cell):
    """Every per-layer metric of the cell that the host can read is on the
    line, with the unit BENCHMARK.json gives it; none that needs the device's
    trace; and nothing compiled inside the window."""
    code, line, err = rehearse(cell, "--trace", "1")
    assert code == 0 and line["correct"] is True, err
    manifest = manifest_of(cell)
    declared = {m["name"]: m for m in manifest.metrics("per_layer", cell)}
    device_trace = {name for name, m in declared.items() if m["source"] == "device_trace"}
    assert not device_trace & set(line["metrics"]), "a CPU run reports no device metric"
    assert set(line["metrics"]) == set(declared) - device_trace, "a metric that reads the host finds something to read"
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        if manifest.layer_metric(name)[0].get("args", {}).get("counter") == "compile_cache_misses":
            assert got["value"] == 0.0, f"{name}: something compiled inside the window"
    assert "breakdown" not in line and "busy_s" not in line["device"]


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_control_at_tiny_sizes_reads_not_correct(cell):
    code, line, err = rehearse(cell, "--trace", "0", "--control")
    assert code == 0 and line["correct"] is False, err
    over = [k for k, v in line["checks"].items() if isinstance(v, list) and v[0] > v[1]]
    assert over and all(k.startswith("rel_gap.") for k in over)


def test_a_run_that_attaches_no_tpu_prints_no_result():
    """Not a rehearsal, and the stated platform is the CPU: exit 1, nothing
    on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", APP_EXECUTOR_POD_QUEUE_TARGET_LENGTH="1")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_without_the_program_there_is_nothing_to_measure(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: exit 1, nothing on stdout."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip", ignore=shutil.ignore_patterns(".work", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1 and proc.stdout.strip() == ""
    assert "nothing to measure" in proc.stderr


# -- the timed path broken underneath -----------------------------------------


@pytest.fixture(scope="module")
def runner():
    return load_runner()


# Each fault is planted in EVERY served turn, so that it is there whichever
# turns a short window under a loaded machine happens to hold.


def alter_answer(record):
    """A number altered where an array turn prints it; a word elsewhere."""
    if record["status"] == 200:
        altered = record["stdout"].replace("= ", "= 1").replace("acc=", "acc=1")
        record["stdout"] = altered if altered != record["stdout"] else record["stdout"] + "altered\n"


def lose_exit_code(record):
    if record["status"] == 200:
        record["exit_code"] = 0 if record["exit_code"] else 1


def wrong_changed_files(record):
    """A changed file dropped where the turn changed one, one leaked where
    it changed none."""
    if record["status"] == 200:
        if record["files"]:
            record["files"].pop(sorted(record["files"])[0])
        else:
            record["files"]["/workspace/leaked.bin"] = "0" * 64


def break_order(record):
    if record.get("session_seq") is not None:
        record["session_seq"] = 1 if record["place"] else 2  # the session lost its state, or kept another's


def never_answer(record):
    record.update(status=0, error="timed out")


# The faults a cell can have follow the order of its traffic, not its name:
# only a session has an order to break.
FAULTS_OF_ORDER = {
    "deck": [alter_answer, lose_exit_code, wrong_changed_files, never_answer],
    "sessions": [alter_answer, lose_exit_code, wrong_changed_files, break_order, never_answer],
}


def faults_of(cell: str) -> list:
    return FAULTS_OF_ORDER.get(order_of(cell), [])


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_a_cells_order_is_one_whose_faults_are_planted(cell):
    """A third order would come with the generator's code for it, in a PR
    that may edit this file: until then no cell goes without its faults."""
    assert order_of(cell) in FAULTS_OF_ORDER and alter_answer in faults_of(cell)


@pytest.fixture(scope="module")
def broken_runs(runner):
    """One whole rehearsed run per cell, driven in this process (no look for
    a chip), with what `execute` returns broken underneath: every array
    turn's answer altered where the harness receives it. Kept: the result
    line, and what the comparison was given, with each turn as it was before
    the fault."""
    cache = {}

    def run(cell):
        if cell in cache:
            return cache[cell]
        sound_execute, sound_judge = runner.execute, runner.compare.judge
        kept = {}

        def broken(client, turn, hashes, executor_id):
            record = sound_execute(client, turn, hashes, executor_id)
            if turn["chain"].startswith("warmup") or turn["payload"] == "probe" or (executor_id or "").startswith("bench-warmup"):
                return record
            record["_sound"] = copy.deepcopy(record)
            alter_answer(record)
            return record

        def judge(turns, expected, limits):
            kept.update(turns=[t["_sound"] for t in turns], expected=expected, limits=limits)
            return sound_judge(turns, expected, limits)

        patch = pytest.MonkeyPatch()
        patch.setattr(runner, "execute", broken)
        patch.setattr(runner.compare, "judge", judge)
        patch.setenv("JAX_PLATFORMS", "cpu")
        out = io.StringIO()
        try:
            with contextlib.redirect_stdout(out):
                code = runner.main([*cell_args(cell), "--seed", "77", "--seconds", "1.5", "--trace", "0", "--rehearse"])
        finally:
            patch.undo()
        lines = out.getvalue().strip().splitlines()
        cache[cell] = (code, json.loads(lines[-1]) if lines else None, kept)
        return cache[cell]

    return run


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_an_answer_altered_where_it_is_produced_reads_not_correct(broken_runs, cell):
    code, line, kept = broken_runs(cell)
    assert code == 0 and line is not None
    assert line["correct"] is False
    assert any(v[0] > v[1] for v in line["checks"].values() if isinstance(v, list))
    # and the same window as it was before the fault compares as correct
    assert compare.judge(copy.deepcopy(kept["turns"]), kept["expected"], kept["limits"])["correct"]


@pytest.mark.parametrize("cell, fault", [(c, f) for c in ALL_CELLS for f in faults_of(c) if f is not alter_answer],
                         ids=lambda v: getattr(v, "__name__", v))
def test_each_other_fault_of_the_timed_path_reads_not_correct(broken_runs, cell, fault):
    """The window of that run, each turn as the service answered it, with one
    more kind of fault planted: `correct` comes out false for each."""
    _, _, kept = broken_runs(cell)
    turns = copy.deepcopy(kept["turns"])
    for record in turns:
        fault(record)
    verdict = compare.judge(turns, kept["expected"], kept["limits"])
    assert verdict["correct"] is False
    assert any(v[0] > v[1] for v in verdict["checks"].values() if isinstance(v, list))


def test_judge_passes_a_sound_window_and_names_each_fault():
    expected = {"a": [{"stdout": "x 1.000000\n", "exit_code": 0, "files": {"f": "h1"}}]}
    turn = {"payload": "p", "chain": "a", "place": 0, "status": 200, "stdout": "x 1.000001\n",
            "exit_code": 0, "files": {"/workspace/f": "h1", "/workspace/profile.zip": "zz"}}
    sound = compare.judge([copy.deepcopy(turn)], expected, {"p": 1e-5})
    assert sound["correct"] and sound["checks"]["rel_gap.p"][0] == pytest.approx(1e-6)
    assert not compare.judge([copy.deepcopy(turn)], expected, {"p": 1e-7})["correct"]
    for change, name in (({"exit_code": 3}, "exit_code"), ({"stdout": "y 1.0\n"}, "text"),
                         ({"files": {"/workspace/f": "h2"}}, "files"), ({"status": 502}, "unanswered")):
        verdict = compare.judge([dict(copy.deepcopy(turn), **change)], expected, {"p": 1e-5})
        assert not verdict["correct"] and verdict["checks"][name] == [1, 0], name
    assert not compare.judge([], expected, {"p": 1e-5})["correct"], "nothing compared is not correct"
