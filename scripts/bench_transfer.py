#!/usr/bin/env python3
"""Workspace-sync microbench: Execute latency and bytes moved for
(a) a cold first session turn, (b) a session turn with unchanged input
files, and (c) a turn with exactly one changed file.

Drives the real local backend + C++ executor (warm JAX import off — this
measures the transfer protocol, not TPU init) and reads the byte movement
straight out of ``Result.phases``, which the delta sync populates. Emits a
``BENCH_transfer.json`` blob::

    {"config": {...}, "cold": {...}, "unchanged": {...}, "one_changed": {...},
     "ok": true}

The headline invariant (the ISSUE acceptance criterion): the unchanged turn
moves ZERO upload bytes regardless of file count or size — O(1) wire cost,
not O(total bytes x hosts) — and its skipped-bytes counters are nonzero
while the cold turn's are zero. ``--smoke`` (CI) shrinks the file set and
exits nonzero when any invariant breaks.

A second gate (ISSUE 4) measures TRACING overhead on the same unchanged-turn
path: the p50 with tracing enabled at 0% sampling must stay within 5% of the
tracing-disabled p50 (the no-op fast path really is a no-op); the 100%
number is recorded for reference.

A third gate (ISSUE 8) measures the DEVICE-HEALTH PROBE daemon's overhead on
the same path: with the probe running at a 500ms cadence (30x the
production default) against the live sandbox host, the unchanged-turn p50
must stay
within 5% + 5ms of the probe-off p50, and one full probe cycle (real
/device-stats HTTP + classification) must finish under 250ms — background
telemetry must not tax the serving path, and the probe itself must stay
cheap enough that any cadence an operator picks stays negligible.

Usage:
    python scripts/bench_transfer.py [--files 16] [--bytes 65536]
        [--repeats 3] [--out BENCH_transfer.json] [--smoke]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import secrets
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT))

# The transfer bench stays off the chip; everything here is control-plane +
# wire mechanics.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bee_code_interpreter_fs_tpu.config import Config  # noqa: E402
from bee_code_interpreter_fs_tpu.services.backends.local import (  # noqa: E402
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import (  # noqa: E402
    CodeExecutor,
)
from bee_code_interpreter_fs_tpu.services.storage import Storage  # noqa: E402


def _phase_blob(result, wall_s: float) -> dict:
    phases = result.phases
    return {
        "wall_s": round(wall_s, 4),
        "upload_s": round(phases.get("upload", 0.0), 4),
        "download_s": round(phases.get("download", 0.0), 4),
        "upload_bytes": int(phases.get("upload_bytes", 0.0)),
        "upload_skipped_bytes": int(phases.get("upload_skipped_bytes", 0.0)),
        "download_bytes": int(phases.get("download_bytes", 0.0)),
        "download_skipped_bytes": int(
            phases.get("download_skipped_bytes", 0.0)
        ),
    }


async def _timed_execute(executor, source, files, session) -> dict:
    start = time.perf_counter()
    result = await executor.execute(source, files=files, executor_id=session)
    wall = time.perf_counter() - start
    if result.exit_code != 0:
        raise RuntimeError(f"bench execute failed: {result.stderr[:500]}")
    return _phase_blob(result, wall)


def _make_executor(tmp: str, **config_overrides) -> CodeExecutor:
    config = Config(
        file_storage_path=f"{tmp}/storage",
        local_sandbox_root=f"{tmp}/sandboxes",
        executor_pod_queue_target_length=1,
        jax_compilation_cache_dir="",
        default_execution_timeout=120.0,
        **config_overrides,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=False)
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


def _trimmed_p50(samples: list[float]) -> float:
    """Median of the fastest two-thirds of samples. Applied to BOTH sides
    of an overhead comparison (symmetric, so it cannot bias the delta): a
    CI machine's load bursts land multi-x spikes on a ~50ms path, and a
    plain small-sample median flakes when a burst covers one side's slow
    half. Real per-turn overhead shifts the FAST samples too, so the
    trimmed median still detects it."""
    fast = sorted(samples)[: max(1, (2 * len(samples) + 2) // 3)]
    return statistics.median(fast)


class _OverheadStack:
    """One executor stack for the overhead benches (tracing, device-health
    probe): its own session and input set, a `turn` that wraps every
    execute in a root span (without one, the pipeline's child spans no-op
    regardless of sampling and the comparison would measure nothing), and
    a recorded-sample list the A/B loops slice per mode."""

    def __init__(self, label: str, **config_overrides) -> None:
        self.label = label
        self.config_overrides = config_overrides
        self.samples: list[float] = []
        self.executor: CodeExecutor | None = None
        self.files: dict[str, str] = {}

    async def start(self, num_files: int, file_bytes: int) -> None:
        tmp = tempfile.mkdtemp(prefix=f"bench-overhead-{self.label}-")
        self.executor = _make_executor(tmp, **self.config_overrides)
        for i in range(num_files):
            object_id = await self.executor.storage.write(
                secrets.token_bytes(file_bytes)
            )
            self.files[f"/workspace/input-{i:03d}.bin"] = object_id

    async def close(self) -> None:
        if self.executor is not None:
            await self.executor.close()

    async def turn(self, record: bool) -> None:
        with self.executor.tracer.start_trace("bench unchanged-turn"):
            start = time.perf_counter()
            result = await self.executor.execute(
                "import glob; print(len(glob.glob('input-*.bin')))",
                files=self.files,
                executor_id="bench-tracing",
            )
            wall = time.perf_counter() - start
        if result.exit_code != 0:
            raise RuntimeError(f"bench execute failed: {result.stderr[:500]}")
        if record:
            self.samples.append(wall)


async def tracing_overhead_bench(
    num_files: int, file_bytes: int, repeats: int
) -> dict:
    """ISSUE 4 satellite: unchanged-turn p50 with tracing disabled vs
    enabled@0% vs enabled@100%. The gate: 0% sampling must be free — within
    5% of disabled (plus a 5ms epsilon so sub-ms scheduler jitter on a
    ~50ms path cannot flake CI).

    ONE stack, three tracer modes toggled turn by turn (`Tracer.enabled` /
    `sample_ratio` are plain attributes, and no span is live between
    turns): the original three-parallel-stacks design compared three
    separate executor/sandbox PROCESSES, whose scheduling placement on a
    loaded CI machine differs by more than the 5% being measured — the
    dominant flake source. Same process, same sandbox, interleaved turns,
    trimmed medians: only the tracer config varies.

    Tail sampling is off in the 0% mode: since PR 7 a head-REJECTED trace
    records tentatively anyway (the tail flight recorder) — a deliberate,
    separately kill-switched feature whose cost is ~that of 100% sampling.
    This gate measures the head-sampling no-op path, which is what "0%
    sampling is free" has always meant; the 100% leg stands in as the
    recording-cost reference."""
    stack = _OverheadStack("tracing-ab", tracing_sample_ratio=1.0)
    modes = {"off": [], "s0": [], "s100": []}
    try:
        await stack.start(num_files, file_bytes)
        await stack.turn(record=False)  # the cold upload turn
        tracer = stack.executor.tracer
        # Deep sampling: a loaded CI box jitters a ~50ms path by +/-50%,
        # and a 5% gate needs the trimmed median to converge through that.
        for _ in range(max(24, 8 * repeats)):
            for mode, samples in modes.items():
                tracer.enabled = mode != "off"
                tracer.sample_ratio = 1.0 if mode == "s100" else 0.0
                tracer.tail_enabled = mode == "s100"
                stack.samples = []
                await stack.turn(record=True)
                samples.extend(stack.samples)
    finally:
        await stack.close()

    # Trimmed medians for the GATE comparison: CI load bursts land multi-x
    # spikes on a ~50ms path, and a plain median flakes when a burst covers
    # one leg's slow half (the trim is symmetric, so it cannot bias the
    # delta; real overhead shifts the fast samples too).
    off, sampled_0, sampled_100 = (
        _trimmed_p50(modes["off"]),
        _trimmed_p50(modes["s0"]),
        _trimmed_p50(modes["s100"]),
    )
    gate = off * 1.05 + 0.005
    return {
        "metric": "tracing overhead on the unchanged-turn path (p50 seconds)",
        "disabled_p50_s": round(off, 4),
        "sampling_0_p50_s": round(sampled_0, 4),
        "sampling_100_p50_s": round(sampled_100, 4),
        "gate_p50_s": round(gate, 4),
        "checks": {"sampling_0_within_5pct_of_disabled": sampled_0 <= gate},
    }


async def probe_overhead_bench(
    num_files: int, file_bytes: int, repeats: int
) -> dict:
    """ISSUE 8 satellite: unchanged-turn p50 with the device-health probe
    daemon OFF vs ON at a 500ms cadence (30x the production default), with
    ON blocks long enough (~1s of turns) that daemon cycles genuinely land
    INSIDE the measured turns — not just at block boundaries — plus a
    direct bound on the probe cycle's own latency. The cadence is chosen
    against the gate's own arithmetic: expected per-turn overhead is
    cycle_cost/interval, and a contended CI box prices one cycle at up to
    ~25ms, so 500ms keeps even the contended expectation (~5%) inside the
    5% + 5ms budget while any *regression* in the probe (a blocking loop, a
    cycle that stops being async) still blows straight through it. Two
    gates:

    - p50 gate (the ISSUE criterion): probe-on stays within 5% + 5ms of
      probe-off. At any sane cadence the daemon's per-turn p50 impact is
      (cycle cost x cadence) — sub-millisecond — so this catches the
      failure mode that matters: a probe loop that starts blocking or
      hogging the shared event loop.
    - cycle gate: one full probe cycle (real /device-stats HTTP against
      the live host + classification) stays under 250ms. This is the
      regression detector for the probe itself — per-turn p50 at a
      realistic cadence cannot see a ~5ms cycle becoming seconds (a probe
      that blocks, serializes on a lock, or stops being async), this can.
      The bound is generous because a loaded CI box prices one local HTTP
      round-trip at tens of milliseconds.

    Single-stack A/B block design: the daemon starts and stops on ONE live
    executor (same process, same sandbox, same session), eliminating the
    per-process scheduling-placement bias that dominates a 5% gate on a
    loaded CI machine; alternating blocks handle load drift and trimmed
    medians handle burst noise."""
    interval = 0.5
    stack = _OverheadStack(
        "probe-ab",
        device_probe_interval=interval,
        device_probe_timeout=2.0,
    )
    off_samples: list[float] = []
    on_samples: list[float] = []
    cycle_samples: list[float] = []
    probe = None
    try:
        await stack.start(num_files, file_bytes)
        await stack.turn(record=False)  # the cold upload turn
        from bee_code_interpreter_fs_tpu.services.device_health import (
            DeviceHealthProbe,
        )

        probe = DeviceHealthProbe(stack.executor)
        blocks = max(6, 2 * repeats)
        turns_per_block = 24
        for _ in range(blocks):
            # One unrecorded settle turn after each toggle (symmetric on
            # both sides): start() fires its first probe cycle immediately,
            # and that one-off start transient is a bench artifact — the
            # production daemon starts once per process, so steady state is
            # what the gate must measure.
            await stack.turn(record=False)
            stack.samples = []
            for _ in range(turns_per_block):
                await stack.turn(record=True)
            off_samples.extend(stack.samples)
            probe.start()  # probes immediately, then every `interval`
            await stack.turn(record=False)
            stack.samples = []
            for _ in range(turns_per_block):
                await stack.turn(record=True)
            on_samples.extend(stack.samples)
            await probe.stop()  # restart-safe: next block start()s again
        # Snapshot BEFORE the direct cycle-latency section below: the
        # probe_actually_ran check must count only cycles the DAEMON ran
        # during the measured ON blocks — the standalone probe_once calls
        # would otherwise satisfy it even if start() never probed at all.
        # And it must exceed ONE PER BLOCK: each start() fires exactly one
        # immediate cycle during the unrecorded settle turn, so equality
        # with `blocks` would mean no cycle ever overlapped a RECORDED
        # turn and the p50 gate measured two probe-off legs.
        leg_cycles = probe._cycles
        # Direct cycle-latency samples (the probe-regression detector).
        await probe.probe_once()  # warm the client path
        for _ in range(10):
            t0 = time.perf_counter()
            await probe.probe_once()
            cycle_samples.append(time.perf_counter() - t0)
    finally:
        if probe is not None:
            await probe.stop()
        await stack.close()

    off, on = _trimmed_p50(off_samples), _trimmed_p50(on_samples)
    cycle = _trimmed_p50(cycle_samples)
    gate = off * 1.05 + 0.005
    return {
        "metric": (
            "device-health probe overhead on the unchanged-turn path "
            "(p50 seconds)"
        ),
        "probe_off_p50_s": round(off, 4),
        "probe_on_p50_s": round(on, 4),
        "probe_interval_s": interval,
        "probe_cycles_during_leg": leg_cycles,
        "probe_cycle_p50_s": round(cycle, 4),
        "gate_p50_s": round(gate, 4),
        "checks": {
            "probe_on_within_5pct_plus_5ms_of_off": on <= gate,
            "probe_cycle_under_250ms": cycle <= 0.25,
            # The DAEMON must have probed INSIDE the measured ON turns —
            # strictly more cycles than the one-per-block start transient
            # — or the p50 gate trivially measures two probe-off legs.
            "probe_actually_ran": leg_cycles > blocks,
        },
    }


async def run_bench(num_files: int, file_bytes: int, repeats: int) -> dict:
    tmp = tempfile.mkdtemp(prefix="bench-transfer-")
    executor = _make_executor(tmp)
    try:
        files = {}
        for i in range(num_files):
            # Distinct random content per file: dedup must come from the
            # manifest protocol, not accidentally-identical payloads.
            object_id = await executor.storage.write(
                secrets.token_bytes(file_bytes)
            )
            files[f"/workspace/input-{i:03d}.bin"] = object_id
        changed_id = await executor.storage.write(secrets.token_bytes(file_bytes))
        session = "bench-transfer"
        source = "import glob; print(len(glob.glob('input-*.bin')))"

        cold = await _timed_execute(executor, source, files, session)
        unchanged_runs = [
            await _timed_execute(executor, source, files, session)
            for _ in range(max(1, repeats))
        ]
        one_changed_files = dict(files)
        one_changed_files[f"/workspace/input-000.bin"] = changed_id
        one_changed = await _timed_execute(
            executor, source, one_changed_files, session
        )

        unchanged = min(unchanged_runs, key=lambda r: r["wall_s"])
        tracing = await tracing_overhead_bench(num_files, file_bytes, repeats)
        device_probe = await probe_overhead_bench(
            num_files, file_bytes, repeats
        )
        total_bytes = num_files * file_bytes
        checks = {
            "cold_moves_all_bytes": cold["upload_bytes"] == total_bytes,
            "cold_skips_nothing": cold["upload_skipped_bytes"] == 0,
            "unchanged_moves_zero_bytes": unchanged["upload_bytes"] == 0,
            "unchanged_skips_all_bytes": (
                unchanged["upload_skipped_bytes"] == total_bytes
            ),
            "one_changed_moves_one_file": (
                one_changed["upload_bytes"] == file_bytes
                and one_changed["upload_skipped_bytes"]
                == total_bytes - file_bytes
            ),
        }
        return {
            "metric": "workspace-sync bytes moved per session turn",
            "config": {
                "files": num_files,
                "file_bytes": file_bytes,
                "total_bytes": total_bytes,
                "repeats": repeats,
            },
            "cold": cold,
            "unchanged": unchanged,
            "one_changed": one_changed,
            "tracing": tracing,
            "device_probe": device_probe,
            "checks": checks,
            "ok": (
                all(checks.values())
                and all(tracing["checks"].values())
                and all(device_probe["checks"].values())
            ),
        }
    finally:
        await executor.close()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--files", type=int, default=16)
    parser.add_argument("--bytes", type=int, default=65536)
    parser.add_argument("--repeats", type=int, default=3)
    parser.add_argument("--out", default=str(REPO_ROOT / "BENCH_transfer.json"))
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="tiny file set + hard-fail on invariant breakage (CI leg)",
    )
    args = parser.parse_args()
    if args.smoke:
        args.files = min(args.files, 4)
        args.bytes = min(args.bytes, 8192)
        args.repeats = 1
    blob = asyncio.run(run_bench(args.files, args.bytes, args.repeats))
    Path(args.out).write_text(json.dumps(blob, indent=2) + "\n")
    print(json.dumps(blob))
    if not blob["ok"]:
        print("TRANSFER BENCH INVARIANT FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
