"""The eight stage metrics (PR 27) as the benchmark declares them: which
they are, which `phases` keys they read, and that a program from before the
stage spans gives them nothing to read and no error.

The eight are those that `stage_tables.py` names, not every metric that uses
their reader: a stage metric that a later PR adds is tested from its own
file's `test` block (`test_chipbench_readers.py`), and a cell that a later PR
adds gets metrics of its own, since these eight's `workloads` lists are the
benchmark's and are not appended to."""

import copy

import pytest

from chipbench_helpers import CELLS, DOC
from lib.manifest import Manifest
from stage_tables import STAGE_PHASES_OF_TURNS, STAGE_WANT, lay_into
from test_chipbench_readers import TRACED, TURNS, ctx

STAGE_METRICS = sorted(STAGE_WANT)
ACCEPTED_WITH_THEM = "toolcalls.c4"  # the cell PR 27 declared them for


def test_the_stage_metrics_are_the_eight_and_their_keys_the_eleven():
    """Each name of the table is a per-layer metric read by `stage_mean`, and
    together they read every key of the table's turns once."""
    assert set(STAGE_METRICS) <= {m["name"] for m in DOC["per_layer"]}
    assert all(Manifest().layer_metric(name)[0]["reader"] == "stage_mean" for name in STAGE_METRICS)
    keys = [k for name in STAGE_METRICS for k in Manifest().layer_metric(name)[0]["args"]["phases"]]
    assert len(keys) == len(set(keys)) == len(STAGE_PHASES_OF_TURNS[0])
    assert all(set(keys) == set(phases) for phases in STAGE_PHASES_OF_TURNS)


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_a_stage_metric_is_declared_as_the_others_of_its_cell(name):
    [entry] = [m for m in DOC["per_layer"] if m["name"] == name]
    spec = Manifest().layer_metric(name)[0]
    assert ACCEPTED_WITH_THEM in entry["workloads"] and set(entry["workloads"]) <= set(CELLS)
    assert entry["moves"] == spec["moves"] == "turn_p90_ms"
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
