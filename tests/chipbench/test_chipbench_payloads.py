"""Every payload's `test` block under stock python, through the plain
reference that the stateless configuration uses."""

import json

import pytest

from chipbench_helpers import BENCH, PAYLOADS, SESSIONS_JSON
from lib import compare
from lib.traffic import Plan, seeded_bytes

REFERENCE = BENCH / "configs" / "toolcalls-1chip.reference.py"


# the yardstick's payloads, and the one of the tests' own sessions cell
WHERE = {name: BENCH / "payloads" for name in PAYLOADS}
WHERE.update({p.stem: p.parent for p in (SESSIONS_JSON.parent / "data" / "payloads").glob("*.json")})
ALL_PAYLOADS = sorted(WHERE)


def spec_of(name: str) -> dict:
    spec = json.loads((WHERE[name] / f"{name}.json").read_text())
    spec["name"], spec["text"] = name, (WHERE[name] / f"{name}.py").read_text()
    return spec


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
    got = compare.load_reference(REFERENCE).run(
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
    run = compare.load_reference(REFERENCE).run
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


def test_floors_are_expressions_of_the_parameters():
    from lib.traffic import evaluate

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
