"""Every payload's `test` block under stock python, through the plain
reference of the configuration whose cell deals the payload."""

import json

import pytest

from chipbench_helpers import ALL_CELLS, BENCH, DOC, PAYLOADS, ROOT, SESSIONS_JSON, found_cell
from lib import compare
from lib.traffic import Plan, evaluate, seeded_bytes


# the yardstick's payloads, and the one of the tests' own sessions cell
WHERE = {name: BENCH / "payloads" for name in PAYLOADS}
WHERE.update({p.stem: p.parent for p in (SESSIONS_JSON.parent / "data" / "payloads").glob("*.json")})
ALL_PAYLOADS = sorted(WHERE)


def spec_of(name: str) -> dict:
    spec = json.loads((WHERE[name] / f"{name}.json").read_text())
    spec["name"], spec["text"] = name, (WHERE[name] / f"{name}.py").read_text()
    return spec


def reference_of(name: str):
    """The plain reference of the first cell whose traffic names the payload;
    of the first configuration where no cell deals it yet."""
    for found in filter(None, map(found_cell, ALL_CELLS)):
        traffic = found["traffic"]
        if name in traffic.get("mix", {}) or name == traffic.get("session", {}).get("payload"):
            return found["reference"]
    config = ROOT / DOC["configs"][0]["file"]
    return config.with_name(config.stem + ".reference.py")


def one_turn(spec: dict, params: dict, control: bool = False) -> dict:
    """The turn as the generator renders it, at the test block's sizes."""
    traffic = {"clients": 1, "order": "deck", "mix": {spec["name"]: 1}}
    tiny = dict(spec, params=dict(spec["params"], **params), draw={})
    plan = Plan(traffic, {spec["name"]: tiny}, 1, control=control)
    return plan.stateless(0)


@pytest.mark.parametrize("name", ALL_PAYLOADS)
def test_payload_test_block_under_stock_python(name, tmp_path):
    spec = spec_of(name)
    block = spec["test"]
    turn = one_turn(spec, block["params"])
    got = compare.load_reference(reference_of(name)).run(
        [{"source": turn["reference_source"], "files": turn["inputs"]}], tmp_path)[0]
    assert got["exit_code"] == block["exit_code"], got["stderr_tail"]
    if "stdout" in block:
        assert got["stdout"] == block["stdout"]
    else:
        assert got["stdout"].startswith(block["stdout_starts"]), got["stdout"]
    assert sorted(got["files"]) == block["files"]


@pytest.mark.parametrize("name", [n for n in ALL_PAYLOADS if "control" in spec_of(n)])
def test_the_control_of_an_array_payload_reads_over_its_limit(name, tmp_path):
    """Under stock numpy too, bfloat16 products and sums land far outside
    the payload's limit, and the sound source inside it against itself."""
    spec = spec_of(name)
    run = compare.load_reference(reference_of(name)).run
    # a chain of products shows the rounding only at some size: its own block
    params = spec["test"].get("control_params", spec["test"]["params"])
    sound, control = one_turn(spec, params), one_turn(spec, params, control=True)
    want = run([{"source": sound["reference_source"], "files": sound["inputs"]}], tmp_path)[0]
    got = run([{"source": control["source"], "files": control["inputs"]}], tmp_path)[0]
    assert got["exit_code"] == 0, got["stderr_tail"]
    diff, gap = compare.compare_text(got["stdout"], want["stdout"])
    assert diff is None
    assert gap > 3 * spec["rel_limit"]
    assert compare.compare_text(want["stdout"], want["stdout"]) == (None, 0.0)


@pytest.mark.parametrize("name", [n for n in ALL_PAYLOADS if "floor" in spec_of(n)])
def test_a_payload_that_states_a_floor_states_it_at_every_size_and_has_a_control(name):
    """A floor is in bytes or flops or both, each an expression of the
    parameters that is above 0 at the measured, the rehearsed and the test
    block's sizes; and a payload that runs device programs has the control
    that has to read not correct."""
    spec = spec_of(name)
    assert spec["floor"] and set(spec["floor"]) <= {"bytes", "flops"}
    for sizes in (spec["params"], dict(spec["params"], **spec.get("rehearse", {})),
                  dict(spec["params"], **spec["test"]["params"])):
        assert all(evaluate(expr, sizes) > 0 for expr in spec["floor"].values())
    assert spec["control"] and spec["rel_limit"] > 0


def test_floors_are_expressions_of_the_parameters():
    spec = spec_of("sumsq")
    assert evaluate(spec["floor"]["bytes"], spec["params"]) == 9155 * 131072 * 4
    assert 0.25 * 2**34 < 9155 * 131072 * 4 < 0.5 * 2**34  # the array: 28 % of the chip's 16 GiB


def test_an_input_is_the_text_its_file_states_or_seeded_bytes():
    stated = one_turn(spec_of("read_file"), {})
    assert stated["inputs"] == {"hello.txt": b"Hello, World!\n"}
    again = Plan({"clients": 1, "order": "deck", "mix": {"read_file": 1}}, {"read_file": spec_of("read_file")}, 99)
    assert again.stateless(0)["input_keys"] == stated["input_keys"], "whatever the seed: uploaded once"


def test_seeded_bytes_depend_on_salt_and_name_only():
    assert seeded_bytes("7", "a", 64) == seeded_bytes("7", "a", 64)
    assert seeded_bytes("7", "a", 64) != seeded_bytes("8", "a", 64)
    assert seeded_bytes("7", "a", 64) != seeded_bytes("7", "b", 64)
    assert len(seeded_bytes("7", "a", 65536)) == 65536
