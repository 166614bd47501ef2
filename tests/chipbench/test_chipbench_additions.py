"""The guard: a later PR adds a configuration, a cell, a payload and per-layer
metrics as new files and new entries, edits nothing, and the yardstick's own
tests still collect and pass, with new cases for what was added.

What such a PR would bring is kept under `fixtures/additions/`: `entries.json`
(what it appends to BENCHMARK.json's `configs`, `workloads` and `per_layer`) and,
under `files/`, its files by their path in the repository: a second
configuration with its plain reference, a second cell in the `deck` order over
a payload the benchmark has and a new array payload that states a floor, and
one metric for each kind of reader, each listing only the new cell. This test
lays them into a copy of the benchmark under `tmp_path` and runs
`pytest tests/chipbench` there.

The copy holds BENCHMARK.json and the two directories of its `paths`; PERF.md
(the list of layers) and the program that the rehearsals start are links to
this checkout's. The rehearsals of the cells this checkout's own run of the
suite covers are deselected there; those of the added cell run.
"""

import json
import os
import re
import shutil
import subprocess
import sys

from chipbench_helpers import ALL_CELLS, BENCH, DOC, FIXTURES, ROOT

ADDED = FIXTURES / "additions"
READER_KINDS = {"stage_mean", "phase_mean", "phase_sum", "roofline", "device_idle"}


def copy_of_the_benchmark(root, add: bool) -> None:
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    junk = shutil.ignore_patterns(".work", "__pycache__")
    shutil.copytree(BENCH, root / "benchmarks" / "chip", ignore=junk)
    shutil.copytree(ROOT / "tests" / "chipbench", root / "tests" / "chipbench", ignore=junk)
    for name in ("PERF.md", "bee_code_interpreter_fs_tpu", "executor"):
        (root / name).symlink_to(ROOT / name)
    if not add:
        return
    files = [p for p in (ADDED / "files").rglob("*") if p.is_file()]
    for source in files:
        target = root / source.relative_to(ADDED / "files")
        assert target.parent.is_dir() and not target.exists(), f"{target}: an addition edits no file and needs no new directory"
        shutil.copy(source, target)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    for section, entries in json.loads((ADDED / "entries.json").read_text()).items():
        assert not {e["name"] for e in entries} & {e["name"] for e in doc[section]}, "an addition edits no entry"
        doc[section] += entries
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))


def pytest_there(root, *args: str) -> tuple[int, str]:
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_") and k != "BENCH_RUN"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/chipbench", "-q", "-p", "no:cacheprovider", f"--rootdir={root}", *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )
    return proc.returncode, proc.stdout + proc.stderr[-2000:]


def collected_there(root) -> list[str]:
    code, out = pytest_there(root, "--collect-only")
    assert code == 0, out[-6000:]
    return [line for line in out.splitlines() if "::" in line and line.startswith("tests/chipbench/")]


def left_out(test_id: str) -> bool:
    """What the copy's run leaves out: this file (it would copy the copy),
    and in the rehearsals' file every case of a cell that this checkout's own
    run of that file drives through the service."""
    if "test_chipbench_additions.py" in test_id:
        return True
    return "test_chipbench_rehearse.py" in test_id and any(name in test_id for name in [*ALL_CELLS, "attaches_no_tpu"])


def test_what_the_fixture_adds_is_one_of_each():
    """The additions are what the docstring says: so that the guard below
    keeps guarding every table a test file could key by a name."""
    entries = json.loads((ADDED / "entries.json").read_text())
    assert set(entries) == {"configs", "workloads", "per_layer"}
    [config], [cell] = entries["configs"], entries["workloads"]
    assert cell["config"] == config["name"] and config["name"] not in {c["name"] for c in DOC["configs"]}
    data = ADDED / "files" / "benchmarks" / "chip"
    assert (data / "configs" / f"{config['name']}.reference.py").is_file()
    traffic = json.loads((data / "workloads" / f"{cell['name']}.json").read_text())
    new = [p for p in traffic["mix"] if (data / "payloads" / f"{p}.json").is_file()]
    had = [p for p in traffic["mix"] if (BENCH / "payloads" / f"{p}.json").is_file()]
    assert traffic["order"] == "deck" and len(new) == len(had) == 1 and len(traffic["mix"]) == 2
    assert "floor" in json.loads((data / "payloads" / f"{new[0]}.json").read_text())
    specs = {m["name"]: json.loads((data / "layer_metrics" / f"{m['name']}.json").read_text()) for m in entries["per_layer"]}
    assert {s["reader"] for s in specs.values()} == READER_KINDS and len(specs) == len(READER_KINDS)
    assert all(m["workloads"] == [cell["name"]] for m in entries["per_layer"])
    assert all("test" in s for s in specs.values()), "a later metric brings its hand-made case in its own file"
    forms = [set(s["test"]) - {"want"} for s in specs.values()]
    assert {"phases"} in forms and {"turns", "busy"} in forms and {"parent_lacks"} in forms and set() in forms


def test_a_second_configuration_cell_payload_and_metrics_come_as_files_and_entries_only(tmp_path):
    copy_of_the_benchmark(tmp_path / "unchanged", add=False)
    before = collected_there(tmp_path / "unchanged")
    copy_of_the_benchmark(tmp_path / "added", add=True)
    after = collected_there(tmp_path / "added")
    assert set(before) <= set(after), "every case of the unchanged benchmark still collects"
    rehearsals = [t for t in before if "test_chipbench_rehearse.py" in t]
    assert len([t for t in after if "test_chipbench_rehearse.py" in t]) > len(rehearsals) >= 17
    [cell] = json.loads((ADDED / "entries.json").read_text())["workloads"]
    assert not any(cell["name"] in t for t in before) and sum(cell["name"] in t for t in after) >= 10

    code, out = pytest_there(tmp_path / "added", *(f"--deselect={t}" for t in after if left_out(t)))
    assert code == 0, out[-8000:]
    passed = int(re.search(r"\b(\d+) passed", out).group(1))
    assert passed == len([t for t in after if not left_out(t)]), out[-3000:]
    assert passed > len([t for t in before if not left_out(t)]), "nothing new was tested"
