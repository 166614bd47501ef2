#!/usr/bin/env python3
"""Wedge-recovery microbench: a seeded attach-hang wedges one lane's host
through the real local backend + C++ executor, and the detect→act loop
must restore the lane to serving — detection, lease fence, drain, dispose,
respawn, clean-streak re-admission — inside a bounded wall-clock, with
zero manual intervention.

This is the ISSUE 13 acceptance gate made executable: unactuated, a wedged
host stays wedged until someone intervenes by hand (BENCH_r03-r05 are runs
that ended on a device that never attached). The gate here asserts the
automated loop closes in seconds:

- the probe detects the wedge (``device_wedge_detected_total``);
- the actuator fences it (``device_fence_total{outcome="fenced"}``), the
  host is disposed and a replacement spawns with a NEWER lease generation;
- a stale-generation claim against the successor is refused with the typed
  409 (the re-wedge vector is closed);
- the replacement re-admits only after the configured clean-probe streak
  (``host_readmitted_total``), and an Execute on the lane then succeeds;
- total time-to-restore (first probe -> serving execute) is under the
  bound.

Usage:
    python scripts/bench_recovery.py [--out BENCH_recovery.json] [--smoke]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

# Stay off the chip by default: this bench measures the control plane.
os.environ.setdefault("JAX_PLATFORMS", os.environ.get("BENCH_PLATFORM", "cpu"))

import httpx  # noqa: E402

from bee_code_interpreter_fs_tpu.config import Config  # noqa: E402
from bee_code_interpreter_fs_tpu.services.backends.faults import (  # noqa: E402
    FaultInjectingBackend,
    FaultSpec,
)
from bee_code_interpreter_fs_tpu.services.backends.local import (  # noqa: E402
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import (  # noqa: E402
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.device_health import (  # noqa: E402
    DeviceHealthProbe,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage  # noqa: E402

LANE = 0
SEED = 7
READMIT_STREAK = 2
# Probe dynamics for the bench: tight budgets so detection is sub-second;
# production budgets are minutes by design (legitimate TPU init is slow).
PROBE_INTERVAL = 0.1
ATTACH_BUDGET = 0.5
WEDGE_AFTER = 0.5
# The smoke gate's time-to-restore bound (detection + drain + respawn +
# re-admission streak on the cadence above, plus CI scheduling slack).
RESTORE_BOUND_S = 20.0


def counter(metric) -> dict:
    return {tuple(l.values()): v for l, v in metric.samples()}


async def run_bench() -> dict:
    tmp = Path(tempfile.mkdtemp(prefix="bench-recovery-"))
    spec = (
        f"attach_hang:1.0,attach_hang_lane:{LANE},attach_hang_max:1,"
        f"seed:{SEED}"
    )
    config = Config(
        file_storage_path=str(tmp / "storage"),
        local_sandbox_root=str(tmp / "sandboxes"),
        jax_compilation_cache_dir="",
        executor_pod_queue_target_length=1,
        compile_cache_prewarm=False,
        executor_fault_spec=spec,
        device_probe_interval=PROBE_INTERVAL,
        device_probe_timeout=5.0,
        device_probe_attach_budget=ATTACH_BUDGET,
        device_probe_op_grace=5.0,
        device_probe_wedge_after=WEDGE_AFTER,
        device_probe_readmit_streak=READMIT_STREAK,
        default_execution_timeout=30.0,
    )
    backend = FaultInjectingBackend(
        LocalSandboxBackend(config, warm_import_jax=False),
        FaultSpec.parse(spec),
    )
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    probe = DeviceHealthProbe(executor)
    executor.device_health = probe
    timeline: dict[str, float] = {}
    checks: dict[str, bool] = {}
    try:
        # Lane up: one real executor host, which the seeded fault will
        # report as a wedged attach from its first probe.
        result = await executor.execute("print('up')", chip_count=LANE)
        assert result.exit_code == 0
        doomed = next(
            s for lane, s in executor.live_hosts() if lane == LANE
        )
        old_lease = doomed.meta["lease"]

        start = time.perf_counter()
        probe.start()
        deadline = start + RESTORE_BOUND_S

        def since_start() -> float:
            return round(time.perf_counter() - start, 3)

        # Detection.
        while time.perf_counter() < deadline:
            if counter(executor.metrics.device_wedges).get((str(LANE),), 0):
                timeline["detected_s"] = since_start()
                break
            await asyncio.sleep(0.02)
        checks["wedge_detected"] = "detected_s" in timeline

        # Fence + dispose + respawn.
        replacement = None
        while time.perf_counter() < deadline:
            fenced = counter(executor.metrics.device_fences).get(
                (str(LANE), "fenced"), 0
            )
            if fenced and executor.live_sandbox(doomed.id) is None:
                replacement = next(
                    (
                        s
                        for lane, s in executor.live_hosts()
                        if lane == LANE
                    ),
                    None,
                )
                if replacement is not None:
                    timeline.setdefault("replaced_s", since_start())
                    break
            await asyncio.sleep(0.02)
        checks["fenced_and_replaced"] = replacement is not None
        checks["lease_revoked"] = bool(old_lease.revoked)
        checks["generation_advanced"] = bool(
            replacement is not None
            and replacement.meta["lease"].generation > old_lease.generation
        )

        # The stale-generation claim dies typed at the successor.
        stale_refused = False
        if replacement is not None:
            async with httpx.AsyncClient() as raw:
                resp = await raw.post(
                    f"{replacement.url}/execute",
                    json={"source_code": "print('stale')", "timeout": 5},
                    headers={"x-lease-token": old_lease.wire_token},
                )
            stale_refused = (
                resp.status_code == 409
                and resp.json().get("error") == "stale_lease"
            )
        checks["stale_claim_409"] = stale_refused

        # Gated re-admission, then the lane serves again.
        while time.perf_counter() < deadline:
            if counter(executor.metrics.host_readmitted).get((str(LANE),), 0):
                timeline["readmitted_s"] = since_start()
                break
            await asyncio.sleep(0.02)
        checks["readmitted_after_streak"] = "readmitted_s" in timeline
        restored = False
        if checks["readmitted_after_streak"]:
            result = await executor.execute(
                "print('restored')", chip_count=LANE
            )
            restored = result.exit_code == 0
            timeline["restored_s"] = since_start()
        checks["lane_serves_again"] = restored
        checks["restored_within_bound"] = (
            restored and timeline["restored_s"] <= RESTORE_BOUND_S
        )
    finally:
        await probe.stop()
        await executor.close()
    # Collect subprocess transports while the loop is alive.
    import gc

    gc.collect()
    await asyncio.sleep(0)
    return {
        "metric": (
            "wall-clock from probe start to the wedged lane serving again "
            "(detect -> fence -> drain -> dispose -> respawn -> "
            "clean-streak re-admission), seeded attach_hang on the real "
            "local backend + C++ executor"
        ),
        "config": {
            "fault_spec": spec,
            "probe_interval_s": PROBE_INTERVAL,
            "attach_budget_s": ATTACH_BUDGET,
            "wedge_after_s": WEDGE_AFTER,
            "readmit_streak": READMIT_STREAK,
            "restore_bound_s": RESTORE_BOUND_S,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
        },
        "timeline_s": timeline,
        "baseline": {
            "manual_recovery": "not measured (unactuated, the lane stays "
            "down until someone intervenes by hand)",
        },
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="BENCH_recovery.json")
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="CI gate mode: exit nonzero when any check fails",
    )
    args = parser.parse_args()
    body = asyncio.run(run_bench())
    Path(args.out).write_text(json.dumps(body, indent=2) + "\n")
    print(json.dumps(body, indent=2))
    if args.smoke and not body["ok"]:
        print("RECOVERY BENCH GATE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
