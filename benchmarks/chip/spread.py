#!/usr/bin/env python3
"""Sets of runs of one cell in one call, and each metric's spread as the
driver reckons it.

    python3 benchmarks/chip/spread.py --workload <cell> --seeds 11,12,13 \
        [--sets 2] [--seconds <run_seconds>] [--trace 0] [--control] [--out DIR]

Runs `run.py` once per seed and set, each a process of its own, the sets one
after the other with the same seeds, and prints per end-to-end metric: each
set's values, its spread (quartile distance over the median) and the spread
with the run farthest from the median left out where that narrows it; over
the sets, the mean of those against half the bound (over: too tight) and
the bound against eight times the widest spread of all runs (over: too
loose). `--stats FILE...` reckons the same from result lines kept earlier.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from lib import stats  # noqa: E402
from lib.manifest import ROOT, Manifest  # noqa: E402


def report(sets: list[list[dict]], bounds: dict[str, float]) -> None:
    names = sorted({name for runs in sets for r in runs for name in r["metrics"]})
    for name in names:
        values = [[r["metrics"][name]["value"] for r in runs if name in r["metrics"]] for runs in sets]
        values = [v for v in values if len(v) >= 2 and any(v)]  # a counter that reads 0 has no spread
        if not values:
            continue
        for i, v in enumerate(values):
            print(f"{name} set {i}: " + " ".join(f"{x:.4f}" for x in v)
                  + f" | spread {stats.spread(v):.4f} trimmed {stats.trimmed_spread(v):.4f}")
        bound = bounds.get(name)
        if bound is None:
            print(f"{name}: widest spread {max(stats.spread(v) for v in values):.4f} (no bound: per-layer)")
            continue
        verdict = stats.verdict(values, bound)
        print(f"{name}: medians {[round(m, 4) for m in verdict['medians']]} mean trimmed spread "
              f"{verdict['mean_trimmed']:.4f} vs bound/2 {bound / 2:.4f}"
              f"{' TOO TIGHT' if verdict['too_tight'] else ''}; bound {bound} vs 8 x widest "
              f"{8 * verdict['widest']:.4f}{' TOO LOOSE' if verdict['too_loose'] else ''}; "
              f"five times the widest: {5 * verdict['widest']:.4f}")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload")
    parser.add_argument("--seeds", default="")
    parser.add_argument("--sets", type=int, default=1)
    parser.add_argument("--seconds", type=float)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--control", action="store_true")
    parser.add_argument("--out", default=None)
    parser.add_argument("--stats", nargs="+", help="result-line files, one per set, in place of running")
    args = parser.parse_args()
    doc = Manifest().doc
    bounds = {m["name"]: m["bound"] for m in doc["end_to_end"]}
    if args.stats:
        sets = [[json.loads(line) for line in Path(p).read_text().splitlines() if line.strip()] for p in args.stats]
        report([[r for r in runs if "metrics" in r] for runs in sets], bounds)
        return 0
    seconds = args.seconds or doc["run_seconds"]
    out = Path(args.out) if args.out else None
    if out:
        out.mkdir(parents=True, exist_ok=True)
    sets, bad = [], 0
    for s in range(args.sets):
        runs = []
        for seed in (int(x) for x in args.seeds.split(",")):
            tag = f"{args.workload}.trace{args.trace}{'.control' if args.control else ''}.set{s}"
            command = [sys.executable, str(BENCH / "run.py"), "--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(args.trace)]
            if args.control:
                command.append("--control")
            if out:
                command += ["--turns-out", str(out / f"{tag}.seed{seed}.turns.jsonl")]
            t0 = time.perf_counter()
            proc = subprocess.run(command, cwd=ROOT, capture_output=True, text=True)
            took = time.perf_counter() - t0
            last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
            print(f"--- {tag} seed {seed}: rc={proc.returncode} in {took:.0f}s", flush=True)
            print("\n".join(line[:400] for line in proc.stderr.strip().splitlines()[-(30 if proc.returncode == 0 else 60):]), flush=True)
            print(last[:3000], flush=True)
            if proc.returncode != 0 or not last.startswith("{"):
                bad += 1
                continue
            line = json.loads(last)
            line.update(set=s, seed=seed, took_s=took)
            runs.append(line)
            if out:
                with open(out / f"{tag}.jsonl", "a") as f:
                    f.write(json.dumps(line) + "\n")
        sets.append(runs)
    report(sets, bounds)
    wrong = sum(1 for runs in sets for r in runs if r["correct"] == (args.control))
    print(f"runs: {sum(len(r) for r in sets)}, failed to run: {bad}, correct != expected: {wrong}")
    return 1 if bad or wrong else 0


if __name__ == "__main__":
    sys.exit(main())
