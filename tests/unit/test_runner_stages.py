"""The warm runner's stage clock (ISSUE 27) on the module itself, no server:
the stages of a serial turn tile it from the server's clock reading on; a
turn that does not run under the JAX profiler constructs no TraceAnnotation,
and one that does wraps its stages from the profiler's start to its stop.
Nothing here times anything."""

import importlib.util
from pathlib import Path

import pytest

RUNNER_PY = Path(__file__).resolve().parents[2] / "executor" / "runner.py"


@pytest.fixture()
def runner(monkeypatch):
    spec = importlib.util.spec_from_file_location("runner_under_test", RUNNER_PY)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    built = []

    class Annotation:
        def __init__(self, name):
            self.name, self.open = name, None
            built.append(self)

        def __enter__(self):
            self.open = True
            return self

        def __exit__(self, *exc):
            self.open = False
            return False

    monkeypatch.setattr(module, "_trace_annotation", Annotation)
    module.built = built
    return module


def one_turn(runner, tmp_path, env=None):
    script = tmp_path / "script.py"
    script.write_text("x = 6 * 7\n")
    sent = runner.time.monotonic()
    runner._begin_stages("prepare", runner.time.monotonic())
    code, violation = runner._run_one({
        "source_path": str(script),
        "stdout_path": str(tmp_path / "out"),
        "stderr_path": str(tmp_path / "err"),
        "env": env or {},
    })
    assert (code, violation) == (0, None)
    return sent, runner._take_stages(sent)


def test_stages_tile_the_turn_and_an_unprofiled_turn_builds_no_annotation(runner, tmp_path):
    sent, stages = one_turn(runner, tmp_path)
    assert [s[0] for s in stages] == [
        "pickup", "prepare", "limits_arm", "user_code", "limits_restore", "finish",
    ]
    assert stages[0][1] == 0.0
    for (_name, start, seconds), (_next, next_start, _s) in zip(stages, stages[1:]):
        assert seconds >= 0 and abs(start + seconds - next_start) < 2e-6
    assert runner.built == [], "an unprofiled turn pays no profiler call"
    assert runner._STAGES == [] and runner._STAGE_ANNOTATION == []
    # no clock reading from the server (an older binary): no stages, no failure
    runner._begin_stages("prepare", runner.time.monotonic())
    assert runner._take_stages(None) is None


def test_a_profiled_turn_wraps_its_stages_between_profiler_start_and_stop(runner, tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(runner, "_start_profile", lambda: calls.append("start") or "trace-dir")
    monkeypatch.setattr(runner, "_finish_profile", lambda trace_dir: calls.append(("stop", [a.name for a in runner.built if a.open])))
    _sent, stages = one_turn(runner, tmp_path, env={"APP_JAX_PROFILE": "1"})
    assert [s[0] for s in stages] == [
        "pickup", "prepare", "profile_start", "limits_arm", "user_code",
        "limits_restore", "profile_stop", "finish",
    ]
    # one annotation per stage from the profiler's start on, none left open,
    # and none open while the profiler stops
    assert [a.name for a in runner.built] == [
        "profile_start", "limits_arm", "user_code", "limits_restore",
    ]
    assert not any(a.open for a in runner.built)
    assert calls == ["start", ("stop", [])]


def test_the_collection_after_a_reset_is_reported_once(runner):
    now = runner.time.monotonic()
    runner._GC_AFTER_RESET[:] = [(now - 0.5, 0.02)]
    runner._begin_stages("prepare", now)
    stages = runner._take_stages(now)
    assert stages[0] == ["gc_after_reset", -0.5, 0.02]
    runner._begin_stages("prepare", now)
    assert [s[0] for s in runner._take_stages(now)] == ["pickup", "prepare"]
