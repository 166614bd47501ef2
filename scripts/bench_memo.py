#!/usr/bin/env python3
"""Result-memo microbench: repeat pure-run Execute latency served from the
content-addressed memo vs the live sandbox path, plus the two overhead
gates the ISSUE demands.

Drives the real local backend + C++ executor (no jax import — the
workload is pure CPython so the numbers isolate the memo plane, not XLA).
Three legs:

- ``disabled``  — ``result_memo_enabled=False`` (the
  ``APP_RESULT_MEMO_ENABLED=0`` kill switch): the pre-this-PR wire path,
  every run live. Baseline for the overhead + parity gates.
- ``miss``      — memo ENABLED, every run a unique source: each run is a
  live execution that also derives keys, verifies the executor's purity
  echo, and records the result. The delta vs ``disabled`` is the memo's
  full uncached overhead.
- ``hit``       — memo ENABLED, one primed source repeated: every run is
  served from the record with no scheduler ticket, no sandbox HTTP, and
  zero chip-seconds.

Emits ``BENCH_memo.json``. Gates (the ISSUE acceptance criteria):

- ``hit_speedup_10x``      — hit wall p50 at least 10x faster than the
  uncached live p50.
- ``uncached_overhead``    — miss p50 within 5% + 5ms of the disabled
  baseline p50.
- ``kill_switch_parity``   — with the kill switch thrown, the same pure
  request byte-for-byte matches the live leg (stdout, stderr, exit code,
  output-file bytes), carries no memo surface, and writes no memo state.
- ``hits_cost_nothing``    — every hit reports state=hit, zero
  chip-seconds, and made zero sandbox HTTP round-trips.

``--smoke`` (CI) shrinks repeats and hard-fails on any gate breakage.

Usage:
    python scripts/bench_memo.py [--repeats 7]
        [--out BENCH_memo.json] [--smoke]
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

from bee_code_interpreter_fs_tpu.config import Config  # noqa: E402
from bee_code_interpreter_fs_tpu.services.backends.local import (  # noqa: E402
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import (  # noqa: E402
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage  # noqa: E402

# A deterministic, CPU-bound workload heavy enough (~100ms+ of CPython)
# that the 5%+5ms overhead gate measures the memo plane, not timer jitter,
# and with an output file so the hit leg proves files ride the record.
WORK = """
total = 0
for i in range(1_200_000):
    total += i * i
print(total)
open('out.bin', 'wb').write(total.to_bytes(16, 'big'))
"""


def make_executor(tmp: Path, **overrides) -> CodeExecutor:
    defaults = dict(
        file_storage_path=str(tmp / "storage"),
        local_sandbox_root=str(tmp / "sandboxes"),
        # One warm, recycled sandbox: the live path is dispatch + exec, not
        # spawn — the honest (hardest) baseline for the 10x hit gate.
        executor_pod_queue_target_length=1,
        executor_reuse_sandboxes=True,
        jax_compilation_cache_dir="",
        compile_cache_enabled=False,
        default_execution_timeout=120.0,
    )
    defaults.update(overrides)
    config = Config(**defaults)
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


async def settle(executor: CodeExecutor) -> None:
    for _ in range(400):
        pending = list(executor._dispose_tasks) + list(executor._fill_tasks)
        if not pending:
            return
        await asyncio.gather(*pending, return_exceptions=True)


def count_sandbox_http(executor: CodeExecutor) -> dict:
    """Arm a request counter on the live sandbox HTTP client — every wire
    round-trip from now on increments it."""
    count = {"n": 0}

    async def tick(request):
        count["n"] += 1

    executor._http_client().event_hooks["request"].append(tick)
    return count


async def timed_run(executor: CodeExecutor, source: str, *, pure: bool):
    start = time.perf_counter()
    result = await executor.execute(source, pure=pure)
    wall = time.perf_counter() - start
    if result.exit_code != 0:
        raise RuntimeError(f"bench execute failed: {result.stderr[:500]}")
    return round(wall, 5), result


async def result_bytes(executor: CodeExecutor, result) -> dict:
    files = {}
    for path, sha in sorted(result.files.items()):
        files[path] = (await executor.storage.read(sha)).hex()
    return {
        "stdout": result.stdout,
        "stderr": result.stderr,
        "exit_code": result.exit_code,
        "files": files,
    }


def p50(walls: list[float]) -> float:
    return round(statistics.median(walls), 5)


async def run_bench(repeats: int) -> dict:
    tmp = Path(tempfile.mkdtemp(prefix="bench-memo-"))

    def unique(n: int) -> str:
        return WORK + f"# variant {n}\n"

    # --- disabled: the kill-switch wire path, every run live.
    disabled_walls: list[float] = []
    executor = make_executor(tmp / "disabled", result_memo_enabled=False)
    try:
        await timed_run(executor, "print('spin-up')", pure=False)
        await settle(executor)
        for n in range(repeats):
            wall, _ = await timed_run(executor, unique(n), pure=True)
            disabled_walls.append(wall)
            await settle(executor)
        _, parity_run = await timed_run(executor, WORK, pure=True)
        disabled_parity = await result_bytes(executor, parity_run)
        disabled_clean = (
            "memo" not in parity_run.phases
            and executor.result_memo.entry_count() == 0
            and not (tmp / "disabled" / "storage" / ".result-memo").exists()
        )
    finally:
        await executor.close()

    # --- enabled: miss leg (unique sources, live + record) then hit leg
    # (one primed source repeated, served from the record).
    executor = make_executor(tmp / "enabled")
    miss_walls: list[float] = []
    hit_walls: list[float] = []
    hit_runs: list[dict] = []
    try:
        await timed_run(executor, "print('spin-up')", pure=False)
        await settle(executor)
        for n in range(repeats):
            wall, result = await timed_run(executor, unique(n), pure=True)
            if result.phases.get("memo", {}).get("state") != "miss":
                raise RuntimeError("unique source unexpectedly hit the memo")
            miss_walls.append(wall)
            await settle(executor)

        _, prime = await timed_run(executor, WORK, pure=True)
        enabled_parity = await result_bytes(executor, prime)
        await settle(executor)
        wire = count_sandbox_http(executor)
        for _ in range(repeats):
            wall, result = await timed_run(executor, WORK, pure=True)
            hit_walls.append(wall)
            hit_runs.append(
                {
                    "wall_s": wall,
                    "state": result.phases.get("memo", {}).get("state"),
                    "chip_seconds": result.phases.get("chip_seconds"),
                }
            )
        hit_bytes = await result_bytes(executor, result)
        hit_sandbox_http = wire["n"]
    finally:
        await executor.close()

    import gc

    gc.collect()
    await asyncio.sleep(0)

    disabled_p50 = p50(disabled_walls)
    miss_p50 = p50(miss_walls)
    hit_p50 = p50(hit_walls)
    speedup = round(miss_p50 / hit_p50, 2) if hit_p50 else float("inf")
    overhead_gate_s = round(disabled_p50 * 1.05 + 0.005, 5)
    checks = {
        # THE acceptance criterion: a memo hit is at least 10x faster at
        # p50 than the uncached live path.
        "hit_speedup_10x": hit_p50 * 10 <= miss_p50,
        # Enabled-but-uncached stays within 5% + 5ms of the kill-switch
        # baseline.
        "uncached_overhead_within_5pct_5ms": miss_p50 <= overhead_gate_s,
        # Kill switch is byte-for-byte: same output bytes, no memo
        # surface, no memo state on disk.
        "kill_switch_parity": (
            disabled_parity == enabled_parity == hit_bytes and disabled_clean
        ),
        # Hits cost nothing: state=hit, zero chip-seconds, zero sandbox
        # HTTP round-trips across the whole hit leg.
        "hits_cost_nothing": (
            all(
                r["state"] == "hit" and r["chip_seconds"] == 0.0
                for r in hit_runs
            )
            and hit_sandbox_http == 0
        ),
    }
    return {
        "metric": (
            "pure-run Execute wall p50: memo hit vs uncached live vs "
            "kill-switch baseline"
        ),
        "config": {
            "repeats": repeats,
            "workload": "CPU-bound CPython sum-of-squares + output file",
        },
        "disabled": {"p50_wall_s": disabled_p50, "walls_s": disabled_walls},
        "miss": {"p50_wall_s": miss_p50, "walls_s": miss_walls},
        "hit": {
            "p50_wall_s": hit_p50,
            "walls_s": hit_walls,
            "runs": hit_runs,
            "sandbox_http_requests": hit_sandbox_http,
        },
        "hit_speedup_p50_x": speedup,
        "uncached_overhead_gate_s": overhead_gate_s,
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--repeats", type=int, default=7)
    parser.add_argument("--out", default=str(REPO_ROOT / "BENCH_memo.json"))
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="three repeats per leg + hard-fail on gate breakage (CI leg)",
    )
    args = parser.parse_args()
    if args.smoke:
        args.repeats = min(args.repeats, 3)
    blob = asyncio.run(run_bench(max(1, args.repeats)))
    Path(args.out).write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob))
    if not blob["ok"]:
        print("RESULT-MEMO BENCH GATE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
