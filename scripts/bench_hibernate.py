#!/usr/bin/env python3
"""Session-durability microbench: hibernate must actually release the chip,
and the lazy restore must bring the session back intact within a bounded
latency tax over a fresh-session turn.

Drives the real local backend + C++ executor (no jax import — the numbers
isolate the durability plane, not XLA). Three legs:

- ``fresh``    — first turn of a brand-new session: sandbox acquire +
  execute. The baseline the restore tax is gated against.
- ``restore``  — a session runs a turn that mutates interpreter state
  (env var) AND the workspace (marker file), idles past the hibernate
  threshold, is checkpointed and its sandbox disposed (chip released),
  then the next turn lazily restores onto a fresh sandbox. The turn must
  see the exact state back, continue ``session_seq`` at 2, and report the
  ``restore`` phase.
- ``disabled`` — ``session_durability_enabled=False`` (the
  ``APP_SESSION_DURABILITY_ENABLED=0`` kill switch): the sweep must
  hibernate NOTHING, the session stays pinned (pre-durability semantics
  byte-for-byte), and no store state touches disk.

Emits ``BENCH_hibernate.json``. Gates:

- ``chip_released_on_hibernate`` — after the hibernate sweep, the
  session's lane capacity is back (``_session_held`` drained) and the
  record is visible in the statusz durability block.
- ``restored_state_intact``      — the restore turn sees the env var and
  the workspace file byte-exact, seq continues at 2, phase reported.
- ``restore_within_budget``      — restore-turn p50 within 1.5x + 500ms
  of the fresh-session-turn p50 (the restore is a sandbox acquire plus a
  state upload; it must never cost a cold re-derivation).
- ``kill_switch_parity``         — with the switch thrown the sweep is a
  no-op, the chip stays held, the session keeps serving live, and no
  session-store directory exists.

``--smoke`` (CI) shrinks repeats and hard-fails on any gate breakage.

Usage:
    python scripts/bench_hibernate.py [--repeats 5]
        [--out BENCH_hibernate.json] [--smoke]
"""

from __future__ import annotations

import argparse
import asyncio
import json
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

MUTATE = """
import os
os.environ['HIBERNATE_PROBE'] = '42'
open('marker.txt', 'w').write('durable bytes')
print('state planted')
"""

OBSERVE = """
import os
print(os.environ.get('HIBERNATE_PROBE'))
print(open('marker.txt').read())
"""

EXPECTED_OBSERVE = "42\ndurable bytes\n"

# The hibernate threshold for the bench: long enough that in-flight turns
# never trip it, short enough that one sleep ages the session past it.
IDLE_S = 0.05


def make_executor(tmp: Path, **overrides) -> CodeExecutor:
    defaults = dict(
        file_storage_path=str(tmp / "storage"),
        local_sandbox_root=str(tmp / "sandboxes"),
        executor_pod_queue_target_length=1,
        executor_reuse_sandboxes=True,
        jax_compilation_cache_dir="",
        compile_cache_enabled=False,
        default_execution_timeout=120.0,
        session_hibernate_idle_seconds=IDLE_S,
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


def held_chips(executor: CodeExecutor) -> int:
    return sum(executor._session_held.values())


async def timed_turn(executor: CodeExecutor, source: str, executor_id: str):
    start = time.perf_counter()
    result = await executor.execute(source, executor_id=executor_id)
    wall = time.perf_counter() - start
    if result.exit_code != 0:
        raise RuntimeError(f"bench execute failed: {result.stderr[:500]}")
    return round(wall, 5), result


def p50(walls: list[float]) -> float:
    return round(statistics.median(walls), 5)


async def run_bench(repeats: int) -> dict:
    tmp = Path(tempfile.mkdtemp(prefix="bench-hibernate-"))

    fresh_walls: list[float] = []
    restore_walls: list[float] = []
    restore_runs: list[dict] = []
    chip_cycle_ok = True

    executor = make_executor(tmp / "enabled")
    try:
        # Spin-up: pay the first sandbox spawn outside every timing window.
        await timed_turn(executor, "print('spin-up')", "warmup")
        await executor.close_session("warmup")
        await settle(executor)

        for n in range(repeats):
            sid = f"bench-{n}"
            wall, first = await timed_turn(executor, MUTATE, sid)
            fresh_walls.append(wall)
            if first.session_seq != 1:
                raise RuntimeError("fresh session did not start at seq 1")

            # Age past the hibernate threshold, sweep, and verify the chip
            # actually came back before the restore is timed.
            await asyncio.sleep(IDLE_S * 3)
            await executor.sweep_sessions()
            await settle(executor)
            status = executor.statusz()["session_durability"]
            chip_cycle_ok = chip_cycle_ok and (
                held_chips(executor) == 0
                and sid not in executor._sessions
                and status["hibernated"] >= 1
            )

            wall, back = await timed_turn(executor, OBSERVE, sid)
            restore_walls.append(wall)
            restore_runs.append(
                {
                    "wall_s": wall,
                    "seq": back.session_seq,
                    "stdout": back.stdout,
                    "restore_phase": "restore" in back.phases,
                }
            )
            await executor.close_session(sid)
            await settle(executor)
        enabled_status = executor.statusz()["session_durability"]
    finally:
        await executor.close()

    # --- kill switch: the sweep must be a no-op, the session stays live.
    executor = make_executor(
        tmp / "disabled", session_durability_enabled=False
    )
    try:
        await timed_turn(executor, MUTATE, "pinned")
        await asyncio.sleep(IDLE_S * 3)
        swept = await executor.sweep_sessions()
        await settle(executor)
        still_pinned = (
            swept == 0
            and "pinned" in executor._sessions
            and held_chips(executor) >= 1
        )
        _, live = await timed_turn(executor, OBSERVE, "pinned")
        disabled_clean = (
            still_pinned
            and live.session_seq == 2
            and live.stdout == EXPECTED_OBSERVE
            and "restore" not in live.phases
            and executor.statusz()["session_durability"]["enabled"] is False
            and not (tmp / "disabled" / "storage" / ".session-store").exists()
        )
    finally:
        await executor.close()

    fresh_p50 = p50(fresh_walls)
    restore_p50 = p50(restore_walls)
    budget_s = round(fresh_p50 * 1.5 + 0.5, 5)
    checks = {
        "chip_released_on_hibernate": chip_cycle_ok,
        "restored_state_intact": all(
            r["seq"] == 2
            and r["stdout"] == EXPECTED_OBSERVE
            and r["restore_phase"]
            for r in restore_runs
        ),
        "restore_within_budget": restore_p50 <= budget_s,
        "kill_switch_parity": disabled_clean,
    }
    return {
        "metric": (
            "session-turn wall p50: lazy restore after hibernate vs fresh "
            "session, chip release + kill-switch parity gates"
        ),
        "config": {
            "repeats": repeats,
            "hibernate_idle_s": IDLE_S,
            "workload": "env var + workspace marker file round trip",
        },
        "fresh": {"p50_wall_s": fresh_p50, "walls_s": fresh_walls},
        "restore": {
            "p50_wall_s": restore_p50,
            "walls_s": restore_walls,
            "runs": restore_runs,
        },
        "restore_budget_s": budget_s,
        "store": enabled_status,
        "checks": checks,
        "ok": all(checks.values()),
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--repeats", type=int, default=5)
    parser.add_argument(
        "--out", default=str(REPO_ROOT / "BENCH_hibernate.json")
    )
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="three repeats + hard-fail on gate breakage (CI leg)",
    )
    args = parser.parse_args()
    if args.smoke:
        args.repeats = min(args.repeats, 3)
    blob = asyncio.run(run_bench(max(1, args.repeats)))
    Path(args.out).write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob))
    if not blob["ok"]:
        print("HIBERNATE BENCH GATE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
