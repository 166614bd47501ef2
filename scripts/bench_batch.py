#!/usr/bin/env python3
"""Batched-execution-lanes microbench: aggregate throughput for 8 concurrent
one-chip-sized jobs on an 8-chip lane, fused into ONE dispatch vs the serial
pre-batching reality of N sandbox round-trips.

Drives the real local backend + C++ executor with a warm jax runner (the
production shape: the fused /execute-batch staging, per-thread device
pinning, and stdout demux are all exercised end to end). Each job is the
same small matmul chain — known FLOPs, so aggregate GFLOPS is total work
over wall clock and the comparison is apples to apples:

- ``serial``  — APP_BATCHING_ENABLED=0: the 8 jobs run as 8 sequential
  Execute round-trips on one warm recycled sandbox — the pre-this-PR
  reality of the lane's single slice serving its queue one caller at a
  time, which includes the generation turnover (workspace reset) between
  consecutive callers' jobs. The turnover AFTER the last job is excluded
  (symmetric with the batched leg, whose one post-batch turnover is also
  outside the timed window).
- ``batched`` — batching ON, window sized so the 8 concurrent submissions
  always coalesce: one multi-job grant, one fused dispatch, one turnover,
  per-job results demuxed back.

Emits ``BENCH_batch.json``. The headline gate (ROADMAP verbatim, the ISSUE
acceptance criterion): batched aggregate GFLOPS >= 4x the serial baseline,
AND every batched run actually rode the fused path (``batch_jobs`` == 8 in
each job's phases — a silent fallback to serial would otherwise let wall-
clock noise decide the gate). ``--smoke`` (CI) shrinks repeats and
hard-fails on any invariant breakage.

Usage:
    python scripts/bench_batch.py [--repeats 3] [--out BENCH_batch.json]
        [--smoke]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

# The bench stays off the chip by default; on a real
# TPU host run with BENCH_PLATFORM=tpu to measure the 8-chip ICI lane this
# subsystem exists for (there the fused dispatch also parallelizes compute;
# on CPU the win it proves is round-trip coalescing).
os.environ.setdefault("JAX_PLATFORMS", os.environ.get("BENCH_PLATFORM", "cpu"))

from bee_code_interpreter_fs_tpu.config import Config  # noqa: E402
from bee_code_interpreter_fs_tpu.services.backends.local import (  # noqa: E402
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import (  # noqa: E402
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage  # noqa: E402

LANE = 8  # the 8-chip lane of the acceptance criterion
JOBS = 8  # one one-chip-sized job per chip
N = 64  # matmul side: a genuinely SMALL array job (the ISSUE's premise —
ITERS = 4  # round-trip overhead, not FLOPs, dominates its serial cost)
# Dense N×N matmul = 2N³ FLOPs; ITERS of them per job.
FLOPS_PER_JOB = ITERS * 2 * N**3

# The one-chip-sized workload: a chained small matmul via plain jnp ops —
# their compiled executables live in jax's process-wide C++ dispatch cache,
# so after each leg's untimed warm run every job is compile-free (a
# per-job `jax.jit(lambda ...)` would retrace on every request, measuring
# single-threaded trace time instead of dispatch throughput). The fused
# dispatch pins each job's ops to its assigned device.
JOB_SOURCE = f"""
import jax.numpy as jnp
x = jnp.ones(({N}, {N}), dtype=jnp.float32)
y = jnp.eye({N}, dtype=jnp.float32)
for _ in range({ITERS}):
    x = x @ y
x.block_until_ready()
print("job done")
"""


def make_executor(tmp: Path, **overrides) -> CodeExecutor:
    defaults = dict(
        file_storage_path=str(tmp / "storage"),
        local_sandbox_root=str(tmp / "sandboxes"),
        # chips_per_host >= LANE keeps the 8-chip lane single-host (the
        # fused driver runs on one host's runner; multi-host slices stay
        # serial by design).
        tpu_chips_per_host=LANE,
        executor_reuse_sandboxes=True,
        executor_pod_queue_target_length=1,
        default_execution_timeout=600.0,
        compile_cache_prewarm=False,
        batch_max_jobs=JOBS,
        # Generous window so the 8 near-simultaneous submissions always
        # coalesce even on a loaded CI host; a FULL batch fires
        # immediately, so the window never shows up in the timing.
        batch_window_ms=2000.0,
    )
    defaults.update(overrides)
    config = Config(**defaults)
    backend = LocalSandboxBackend(config, warm_import_jax=True)
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


async def settle(executor: CodeExecutor) -> None:
    """Wait out release/turnover/refill tasks so runs don't interleave."""
    for _ in range(400):
        pending = list(executor._dispose_tasks) + list(executor._fill_tasks)
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


def check_result(result, leg: str) -> dict:
    if result.exit_code != 0:
        raise RuntimeError(
            f"{leg} job failed (exit {result.exit_code}): {result.stderr[:500]}"
        )
    return {
        "exit_code": result.exit_code,
        "batch_jobs": int(result.phases.get("batch_jobs", 0.0)),
    }


async def serial_leg(executor: CodeExecutor, repeats: int) -> list[dict]:
    """JOBS sequential round-trips per repeat on one warm recycled sandbox.
    Wall clock spans the first submit to the LAST job's result, including
    the generation turnover between consecutive callers' jobs (the slice
    cannot start job k+1 until it is reset from job k — that reset is part
    of the serial round-trip the fused dispatch eliminates). The turnover
    after the last job is excluded, symmetric with the batched leg."""
    runs = []
    # Warm: spawn + first compile, untimed.
    check_result(await executor.execute(JOB_SOURCE, chip_count=LANE), "serial")
    await settle(executor)
    for _ in range(repeats):
        wall = 0.0
        jobs = []
        for i in range(JOBS):
            start = time.perf_counter()
            result = await executor.execute(JOB_SOURCE, chip_count=LANE)
            wall += time.perf_counter() - start
            jobs.append(check_result(result, "serial"))
            start = time.perf_counter()
            await settle(executor)
            if i < JOBS - 1:
                wall += time.perf_counter() - start
        runs.append(
            {
                "wall_s": round(wall, 4),
                "gflops": round(JOBS * FLOPS_PER_JOB / wall / 1e9, 3),
                "jobs": jobs,
            }
        )
    return runs


async def batched_leg(executor: CodeExecutor, repeats: int) -> list[dict]:
    """JOBS concurrent submissions per repeat: same tenant, same lane, same
    (empty) env/limits — one compatibility key, one fused dispatch."""

    async def burst() -> tuple[float, list[dict]]:
        start = time.perf_counter()
        results = await asyncio.gather(
            *(executor.execute(JOB_SOURCE, chip_count=LANE) for _ in range(JOBS))
        )
        wall = time.perf_counter() - start
        return wall, [check_result(r, "batched") for r in results]

    runs = []
    await burst()  # warm: spawn + first compile, untimed
    await settle(executor)
    for _ in range(repeats):
        wall, jobs = await burst()
        runs.append(
            {
                "wall_s": round(wall, 4),
                "gflops": round(JOBS * FLOPS_PER_JOB / wall / 1e9, 3),
                "jobs": jobs,
            }
        )
        await settle(executor)
    return runs


def p50(runs: list[dict], key: str) -> float:
    return round(statistics.median(r[key] for r in runs), 4)


async def run_bench(repeats: int) -> dict:
    tmp = Path(tempfile.mkdtemp(prefix="bench-batch-"))

    executor = make_executor(tmp / "serial", batching_enabled=False)
    try:
        serial_runs = await serial_leg(executor, repeats)
    finally:
        await executor.close()

    executor = make_executor(tmp / "batched")
    try:
        batched_runs = await batched_leg(executor, repeats)
    finally:
        await executor.close()

    # Collect subprocess transports while the loop is still alive: their
    # __del__ after asyncio.run() closes the loop prints a spurious
    # "Event loop is closed" traceback.
    import gc

    gc.collect()
    await asyncio.sleep(0)

    serial_gflops = p50(serial_runs, "gflops")
    batched_gflops = p50(batched_runs, "gflops")
    checks = {
        # THE acceptance criterion (ROADMAP verbatim): aggregate GFLOPS for
        # 8 concurrent 1-chip-sized jobs on the 8-chip lane, >= 4x serial.
        "batched_4x_serial": batched_gflops >= 4.0 * serial_gflops,
        # Every batched job actually rode a FULL fused dispatch — a silent
        # serial fallback must fail the gate, not hide inside wall-clock.
        "all_jobs_batched": all(
            job["batch_jobs"] == JOBS for run in batched_runs for job in run["jobs"]
        ),
        # The kill-switch leg never touched the batch path.
        "serial_path_untouched": all(
            job["batch_jobs"] == 0 for run in serial_runs for job in run["jobs"]
        ),
    }
    return {
        "metric": (
            "aggregate GFLOPS, 8 concurrent 1-chip-sized matmul jobs on an "
            "8-chip lane: one fused /execute-batch dispatch vs 8 serial "
            "sandbox round-trips"
        ),
        "config": {
            "repeats": repeats,
            "platform": os.environ.get("JAX_PLATFORMS", ""),
            "lane_chips": LANE,
            "jobs": JOBS,
            "kernel": f"{ITERS}x jnp matmul {N}x{N}",
            "flops_per_job": FLOPS_PER_JOB,
        },
        "serial": {
            "p50_gflops": serial_gflops,
            "p50_wall_s": p50(serial_runs, "wall_s"),
            "runs": serial_runs,
        },
        "batched": {
            "p50_gflops": batched_gflops,
            "p50_wall_s": p50(batched_runs, "wall_s"),
            "runs": batched_runs,
        },
        "speedup": round(batched_gflops / serial_gflops, 2)
        if serial_gflops
        else None,
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--out", default=str(REPO_ROOT / "BENCH_batch.json"))
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="two repeats per leg + hard-fail on invariant breakage (CI leg)",
    )
    args = parser.parse_args()
    if args.smoke:
        args.repeats = min(args.repeats, 2)
    blob = asyncio.run(run_bench(max(1, args.repeats)))
    Path(args.out).write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob))
    if not blob["ok"]:
        print("BATCH BENCH INVARIANT FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
