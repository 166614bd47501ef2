"""Stage spans end to end (ISSUE 27): one `/v1/execute` through the HTTP API
and the real C++ executor gives ONE connected trace in which the request's
edges, the pool's acquire and every stage of the sandbox's exec hang under
the right parents; the turnover that follows is a trace of its own; and the
same timings reach `Result.phases` as eleven keys that no histogram and no
baseline reads. Nothing here times anything: order, nesting and sums only.
"""

import asyncio

import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")
pytest.importorskip("aiohttp", reason="optional e2e dependency not installed")

from bee_code_interpreter_fs_tpu.services.code_executor import (
    LATENCY_PHASES,
    STAGE_PHASES,
)
from bee_code_interpreter_fs_tpu.services.perf_observer import OBSERVED_PHASES

from test_tracing_e2e import make_client

PARENTS = {
    # span -> its parent's name
    "edge.before_queue": "http POST /v1/execute",
    "edge.parse": "edge.before_queue",
    "edge.quota": "edge.before_queue",
    "edge.memo_lookup": "edge.before_queue",
    "edge.resolve": "edge.before_queue",
    "scheduler.queue_wait": "http POST /v1/execute",
    "pool.acquire": "scheduler.queue_wait",
    "transfer.upload": "http POST /v1/execute",
    "executor.execute": "http POST /v1/execute",
    "sandbox.parse": "executor.execute",
    "sandbox.install": "executor.execute",
    "sandbox.scan_before": "sandbox.install",
    "sandbox.exec": "executor.execute",
    "sandbox.guard_start": "sandbox.exec",
    "sandbox.runner_wait": "sandbox.exec",
    "sandbox.runner.pickup": "sandbox.exec",
    "sandbox.runner.prepare": "sandbox.exec",
    "sandbox.runner.limits_arm": "sandbox.exec",
    "sandbox.runner.user_code": "sandbox.exec",
    "sandbox.runner.limits_restore": "sandbox.exec",
    "sandbox.runner.finish": "sandbox.exec",
    "sandbox.guard_stop": "sandbox.exec",
    "sandbox.collect": "executor.execute",
    "sandbox.scan_after": "sandbox.collect",
    "sandbox.outputs": "sandbox.collect",
    "sandbox.cache_scan": "sandbox.collect",
    "transfer.download": "http POST /v1/execute",
    "edge.after_download": "http POST /v1/execute",
    "edge.result": "edge.after_download",
    "edge.usage_commit": "edge.after_download",
    "edge.release": "edge.after_download",
    "edge.memo_record": "edge.after_download",
    "edge.observe": "edge.after_download",
}
TURNOVER_PARENTS = {
    "sandbox.reset": "pool.turnover",
    "sandbox.reset_client": "sandbox.reset",
    "sandbox.runner_reset": "sandbox.reset",
    "sandbox.runner.pickup": "sandbox.runner_reset",
    "sandbox.runner.scrub": "sandbox.runner_reset",
    "sandbox.wipe": "sandbox.reset",
    "pool.append": "pool.turnover",
}
# the parts of phases.exec, which with the reply line's way back make it up
EXEC_PARTS = (
    "exec_wire", "sandbox_before_run", "runner_pickup", "runner_before_user",
    "runner_user_code", "runner_after_user", "sandbox_after_run",
)


def by_name(spans):
    names = [s["name"] for s in spans]
    assert len(names) == len(set(names)), sorted(names)
    return {s["name"]: s for s in spans}


def assert_parents(spans, parents):
    found = by_name(spans)
    assert set(found) >= set(parents), sorted(set(parents) - set(found))
    ids = {s["span_id"]: s["name"] for s in spans}
    for name, parent in parents.items():
        assert ids.get(found[name]["parent_id"]) == parent, name


async def turnover_traces(executor, want: int) -> list[list[dict]]:
    """The `pool.turnover` traces in the ring, oldest first, once `want` of
    them have ended (a turnover runs off the request's path)."""
    for _ in range(200):
        roots = [
            s for s in executor.tracer.ring.export_jsonl().splitlines()
            if '"name": "pool.turnover"' in s
        ]
        if len(roots) >= want:
            break
        await asyncio.sleep(0.05)
    import json

    ids = [json.loads(line)["trace_id"] for line in roots]
    assert len(ids) >= want
    return [executor.tracer.ring.trace(trace_id) for trace_id in ids]


async def test_stage_spans_and_phases_of_two_turns(tmp_path):
    client, executor = await make_client(tmp_path)
    try:
        bodies = []
        for source in ("print(6 * 7)", "print(7 * 6)"):
            resp = await client.post("/v1/execute", json={"source_code": source})
            assert resp.status == 200
            bodies.append(await resp.json())
            # the second turn is to pop the sandbox this one's turnover put back
            await turnover_traces(executor, len(bodies))
        first, second = (body["phases"] for body in bodies)

        # -- one connected trace per turn, the new children under the right parents
        for phases, source in ((first, "spawn"), (second, "pool")):
            resp = await client.get(f"/traces/{phases['trace_id']}")
            spans = (await resp.json())["spans"]
            assert_parents(spans, PARENTS)
            found = by_name(spans)
            # nothing was pooled when the first turn came: it spawned its own
            assert found["pool.acquire"]["attributes"]["source"] == source
            roots = [s for s in spans if s["parent_id"] is None]
            assert [s["name"] for s in roots] == ["http POST /v1/execute"]

        # -- a second trace for each turnover
        turnovers = await turnover_traces(executor, 2)
        for spans in turnovers[:2]:
            assert_parents(spans, TURNOVER_PARENTS)
            root = by_name(spans)["pool.turnover"]
            assert root["parent_id"] is None
            assert root["attributes"]["outcome"] == "recycled"
            assert root["attributes"]["lane"] == 0 and root["attributes"]["sandbox"]
            assert root["trace_id"] not in (first["trace_id"], second["trace_id"])

        # -- phases: all eleven keys, floats >= 0, parts of exec within exec
        for phases in (first, second):
            for key in STAGE_PHASES:
                assert isinstance(phases[key], float) and phases[key] >= 0.0, key
            assert sum(phases[key] for key in EXEC_PARTS) <= phases["exec"] + 1e-3
            assert phases["runner_user_code"] > 0 and phases["edge_before"] > 0
            assert phases["edge_after"] > 0
        # the first turn ran on a fresh spawn, the second on a sandbox that
        # a turnover had put back
        assert first["turnover_before"] == 0.0
        assert second["turnover_before"] > 0.0
        assert second["pool_idle_before"] >= 0.0
    finally:
        await client.close()
        await executor.close()


def test_no_stage_phase_is_a_latency_phase():
    """The histogram's allowlist and the perf observer's baselines (and with
    them the auto-profiler's trigger) see none of the new keys."""
    assert len(STAGE_PHASES) == 11 and len(set(STAGE_PHASES)) == 11
    assert not set(STAGE_PHASES) & LATENCY_PHASES
    assert not set(STAGE_PHASES) & set(OBSERVED_PHASES)
