"""The eight stage metrics (PR 27) as the benchmark declares them: which
they are, which `phases` keys they read, and that a program from before the
stage spans gives them nothing to read and no error."""

import copy

import pytest

from chipbench_helpers import CELLS, DOC
from lib.manifest import Manifest
from stage_tables import STAGE_PHASES_OF_TURNS, STAGE_WANT, lay_into
from test_chipbench_readers import TRACED, TURNS, ctx

STAGE_METRICS = [m["name"] for m in DOC["per_layer"] if Manifest().layer_metric(m["name"])[0]["reader"] == "stage_mean"]


def test_the_stage_metrics_are_the_eight_and_their_keys_the_eleven():
    assert sorted(STAGE_METRICS) == sorted(STAGE_WANT)
    keys = [k for name in STAGE_METRICS for k in Manifest().layer_metric(name)[0]["args"]["phases"]]
    assert len(keys) == len(set(keys)) == 11
    assert all(set(keys) == set(phases) for phases in STAGE_PHASES_OF_TURNS)


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_a_stage_metric_is_declared_as_the_others_of_its_cell(name):
    [entry] = [m for m in DOC["per_layer"] if m["name"] == name]
    spec = Manifest().layer_metric(name)[0]
    assert entry["workloads"] == CELLS and entry["moves"] == spec["moves"] == "turn_p90_ms"
    assert (entry["unit"], entry["better"], entry["source"]) == ("ms", "lower", "program_span")
    assert entry["layer"] == spec["layer"] and spec["args"]["scale"] == 1000.0


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_a_stage_metric_of_a_program_without_the_stage_spans_is_left_out(name):
    """Turns as a program from before the stage spans answers them (no such
    key in `phases`): nothing to read, and no error. With the keys, the
    table's value."""
    spec, read = Manifest().layer_metric(name)
    turns = copy.deepcopy(TURNS)
    lay_into(turns, {})
    assert read(turns, spec["args"], ctx(TRACED)) == pytest.approx(STAGE_WANT[name])
    keys = set(spec["args"]["phases"])
    older = [dict(t, phases={k: v for k, v in t["phases"].items() if k not in keys}) if "phases" in t else t
             for t in turns]
    assert read(older, spec["args"], ctx(TRACED)) is None
