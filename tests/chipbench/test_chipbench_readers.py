"""Each per-layer reader on hand-made turns: what it reads, and that one
which finds nothing to read returns nothing (never 0)."""

import pytest

from chipbench_helpers import DOC
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


def ctx(busy):
    return {"window_s": 10.0, "payloads": FLOOR, "device_kind": "TPU v5 lite", "busy": busy,
            "peaks": peaks_of("TPU v5 lite"), "evaluate": evaluate}


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


@pytest.mark.parametrize("name", [m["name"] for m in DOC["per_layer"]])
def test_reader_on_hand_made_turns(name):
    spec, read = Manifest().layer_metric(name)
    assert read(TURNS, spec.get("args", {}), ctx(TRACED)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", [m["name"] for m in DOC["per_layer"]])
def test_a_reader_that_finds_nothing_returns_nothing(name):
    spec, read = Manifest().layer_metric(name)
    unserved = [t for t in TURNS if t["status"] != 200]
    assert read(unserved, spec.get("args", {}), ctx(None)) is None
    if spec["source"] == "device_trace":
        assert read(TURNS, spec.get("args", {}), ctx(None)) is None, "nothing traced: no share of a roofline"
