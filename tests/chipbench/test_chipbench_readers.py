"""Each per-layer reader on hand-made turns: what it reads, and that one
which finds nothing to read returns nothing (never 0).

Where a metric's case comes from. The seventeen metrics accepted so far have
theirs in tables of the test files (`WANT` here, `stage_tables.py`): their own
files may not be edited. Every later metric brings it in its own file, as a
payload does, under the key `test`:

    "test": {"want": <number>,              what the reader returns, compared with approx
             "phases": {"<key>": [a, b, c]},  laid into the three served turns of TURNS, in their
                                            order (one number: the same in all three); or
             "turns": [...],                its own hand-made turns in place of TURNS
             "busy": {...},                 its own device time in place of TRACED
             "parent_lacks": ["<key>"]}     `phases` keys that a program from before the metric
                                            does not stamp: without them the reader returns None

A metric with neither fails here, with a message that says which to add.
"""

import copy

import pytest

from chipbench_helpers import DOC, PAYLOADS
from lib.manifest import Manifest
from lib.peaks import peaks_of
from lib.traffic import evaluate

TURNS = [
    {"payload": "sumsq", "params": {"R": 9155, "C": 131072}, "status": 200, "profiled": False, "equal": True,
     "client_s": 0.30, "phases": {"queue_wait": 0.10, "upload": 0.01, "exec": 0.15, "download": 0.01,
                                  "compile_cache_misses": 0.0}},
    {"payload": "ls", "params": {}, "status": 200, "profiled": False, "equal": False, "client_s": 0.20,
     "phases": {"queue_wait": 0.08, "upload": 0.0, "exec": 0.10, "download": 0.0, "compile_cache_misses": 1.0}},
    {"payload": "sumsq", "params": {"R": 9155, "C": 131072}, "status": 200, "profiled": True, "equal": True,
     "client_s": 1.20, "busy_s": 0.0083, "phases": {"queue_wait": 0.1, "upload": 0.0, "exec": 1.05, "download": 0.0,
                                                    "compile_cache_misses": 0.0}},
    {"payload": "fib", "params": {}, "status": 502, "profiled": False, "client_s": 0.5},
]
FLOOR = {"sumsq": {"floor": {"bytes": "R * C * 4"}}}


def ctx(busy, payloads=None):
    """The context a reader is given. `payloads`: the floors it may look up;
    the hand-made table's own unless a case brings the payloads' files."""
    return {"window_s": 10.0, "payloads": FLOOR if payloads is None else payloads, "device_kind": "TPU v5 lite",
            "busy": busy, "peaks": peaks_of("TPU v5 lite"), "evaluate": evaluate}


TRACED = {"busy_s": 2.0, "per_payload": {"sumsq": 2.0}, "turn_busy": {"sumsq": [0.0083, 0.0084, 0.0082]}, "ops": {}}
WANT = {
    "queue_wait_ms": 90.0,  # mean of the two served, unprofiled turns
    "transfer_ms": 10.0,
    "exec_ms": 125.0,
    "turn_other_ms": 25.0,  # (0.30 - 0.27 + 0.20 - 0.18) / 2
    "compiles_in_window": 1.0,  # all served turns, the profiled one too
    "device_idle": 80.0,
    "sumsq_roofline": 100.0 * (9155 * 131072 * 4 / 819e9) / 0.0083,
    "traced_turn_p50_ms": 300.0,
    "traced_turns_per_s": 0.2,  # the two served turns that equal the reference, over 10 s
}
METRICS = [m["name"] for m in DOC["per_layer"]]


def lay_phases(turns: list[dict], phases: dict) -> list[dict]:
    """`phases` of a `test` block laid into the served turns, in their order."""
    served = [t for t in turns if "phases" in t]
    for key, values in phases.items():
        values = values if isinstance(values, list) else [values] * len(served)
        assert len(values) == len(served), f"{key}: one value for each of the {len(served)} served turns"
        for turn, value in zip(served, values):
            assert turn["phases"].setdefault(key, value) == value, f"{key} is one of the hand-made table's own keys"
    return turns


def case_of(name: str) -> dict:
    """The metric's hand-made case: `turns`, `busy`, `payloads`, `want`, and
    the keys a parent lacks. From the tables for the accepted metrics, from
    the metric's own `test` block for every other."""
    if name in WANT:
        return {"turns": TURNS, "busy": TRACED, "payloads": FLOOR, "want": WANT[name], "parent_lacks": []}
    block = Manifest().layer_metric(name)[0].get("test")
    if block is None:
        pytest.fail(
            f"the per-layer metric {name!r} has no hand-made case: a PR that adds a metric gives its file, "
            f"benchmarks/chip/layer_metrics/{name}.json, a `test` block (this file's docstring; README, 'Adding "
            "without editing'); only a `benchmark` PR adds to WANT here or to stage_tables.py instead"
        )
    turns = copy.deepcopy(block["turns"]) if "turns" in block else lay_phases(copy.deepcopy(TURNS), block.get("phases", {}))
    payloads = dict({p: Manifest().payload(p) for p in PAYLOADS}, **FLOOR)
    return {"turns": turns, "busy": block.get("busy", TRACED), "payloads": payloads, "want": block["want"],
            "parent_lacks": block.get("parent_lacks", [])}


@pytest.mark.parametrize("name", METRICS)
def test_reader_on_hand_made_turns(name):
    spec, read = Manifest().layer_metric(name)
    case = case_of(name)
    assert read(case["turns"], spec.get("args", {}), ctx(case["busy"], case["payloads"])) == pytest.approx(case["want"])
    if case["parent_lacks"]:
        older = [dict(t, phases={k: v for k, v in t["phases"].items() if k not in case["parent_lacks"]})
                 if "phases" in t else t for t in case["turns"]]
        assert read(older, spec.get("args", {}), ctx(case["busy"], case["payloads"])) is None, \
            "a program from before the metric gives it nothing to read, and no error"


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_that_finds_nothing_returns_nothing(name):
    spec, read = Manifest().layer_metric(name)
    case = case_of(name)
    unserved = [t for t in TURNS if t["status"] != 200]
    assert read(unserved, spec.get("args", {}), ctx(None, case["payloads"])) is None
    if spec["source"] == "device_trace":
        assert read(case["turns"], spec.get("args", {}), ctx(None, case["payloads"])) is None, \
            "nothing traced: no share of a roofline"


def test_a_floor_in_flops_reads_against_the_chips_bfloat16_peak():
    """A compute-bound payload states its floor in flops and its roofline as
    data (`"bound": "flops", "peak": "bf16_flops_per_s"`): the reader is there."""
    read = Manifest().layer_metric("sumsq_roofline")[1]  # the reader `roofline`
    turns = [{"payload": "matmul", "params": {"N": 8192}, "status": 200, "profiled": True, "busy_s": 0.0070}]
    busy = {"busy_s": 0.7, "per_payload": {"matmul": 0.7}, "turn_busy": {"matmul": [0.0070, 0.0072, 0.0068]}, "ops": {}}
    payloads = {"matmul": {"floor": {"flops": "2 * N * N * N", "bytes": "3 * N * N * 2"}}}
    args = {"payloads": ["matmul"], "bound": "flops", "peak": "bf16_flops_per_s"}
    assert peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert read(turns, args, ctx(busy, payloads)) == pytest.approx(100.0 * (2 * 8192**3 / 197e12) / 0.0070)
    assert read(turns, dict(args, bound="bytes", peak="bytes_per_s"), ctx(busy, payloads)) == \
        pytest.approx(100.0 * (3 * 8192**2 * 2 / 819e9) / 0.0070)
    assert read(turns, args, ctx(None, payloads)) is None
