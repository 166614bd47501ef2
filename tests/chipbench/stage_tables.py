"""What `test_chipbench_readers.py`'s hand-made tables need for the eight
stage metrics (PR 27): the eleven `phases` keys of its three served turns, in
the turns' order, and the value each metric reads from them.

They are kept here, and laid into that module's tables by `conftest.py`,
because the file is one of the benchmark's own (`BENCHMARK.json`'s `paths`):
a PR that is not a `benchmark` PR adds files there and edits none. Keys are
only ever added; no value, turn or assertion of that file is changed."""

STAGE_PHASES_OF_TURNS = [
    # sumsq, served, unprofiled
    {"edge_before": 0.012, "edge_after": 0.004, "turnover_before": 0.020, "pool_idle_before": 0.006,
     "exec_wire": 0.003, "sandbox_before_run": 0.002, "sandbox_after_run": 0.005, "runner_pickup": 0.020,
     "runner_before_user": 0.010, "runner_user_code": 0.100, "runner_after_user": 0.008},
    # ls, served, unprofiled
    {"edge_before": 0.008, "edge_after": 0.002, "turnover_before": 0.030, "pool_idle_before": 0.004,
     "exec_wire": 0.001, "sandbox_before_run": 0.002, "sandbox_after_run": 0.003, "runner_pickup": 0.010,
     "runner_before_user": 0.006, "runner_user_code": 0.070, "runner_after_user": 0.006},
    # sumsq, served, profiled: the profiler's start and stop sit in the runner's stages
    {"edge_before": 0.010, "edge_after": 0.003, "turnover_before": 0.025, "pool_idle_before": 0.005,
     "exec_wire": 0.002, "sandbox_before_run": 0.002, "sandbox_after_run": 0.010, "runner_pickup": 0.020,
     "runner_before_user": 0.300, "runner_user_code": 0.150, "runner_after_user": 0.560},
]

# the mean over the two served, unprofiled turns, in ms
STAGE_WANT = {
    "edge_ms": 13.0,  # (0.012 + 0.004 + 0.008 + 0.002) / 2
    "turnover_ms": 25.0,
    "pool_idle_ms": 5.0,
    "exec_wire_ms": 2.0,
    "exec_server_ms": 6.0,  # (0.002 + 0.005 + 0.002 + 0.003) / 2
    "runner_pickup_ms": 15.0,
    "exec_runner_ms": 15.0,  # (0.010 + 0.008 + 0.006 + 0.006) / 2
    "exec_user_code_ms": 85.0,
}


def lay_into(turns: list[dict], want: dict) -> None:
    """Add the stage keys to the served turns' phases and the eight values
    to `want`. Adding twice changes nothing; a key that is there with
    another value is a clash with the file's own tables, and an error."""
    served = [t for t in turns if "phases" in t]
    assert len(served) == len(STAGE_PHASES_OF_TURNS)
    for turn, extra in zip(served, STAGE_PHASES_OF_TURNS):
        for key, value in extra.items():
            assert turn["phases"].setdefault(key, value) == value, key
    for name, value in STAGE_WANT.items():
        assert want.setdefault(name, value) == value, name
