#!/usr/bin/env python3
"""Where a turn's time goes, stage by stage, from what the program itself
records (PERF.md section 5's tables are made with this).

    python3 scripts/stage_table.py prints [--count 25] [--cpu]
        Start the service as benchmarks/chip/run.py does, send `--count`
        print-sized turns back to back with one client, read each turn's
        trace and each turnover's from GET /traces/{trace_id}, and print the
        mean length of every span name in ms.
    python3 scripts/stage_table.py turns TURNS.jsonl [SPANS.jsonl]
        From a `run.py --turns-out` file: per payload the mean of every stage
        key of `phases`, the remainder of `exec` over its parts, and the chip
        holder's cycle summed over all served turns of the window. With the
        service's span file (APP_TRACING_JSONL_PATH) also the mean length of
        every span name per payload.

This process never imports jax."""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

EXEC_PARTS = (
    "exec_wire", "sandbox_before_run", "runner_pickup", "runner_before_user",
    "runner_user_code", "runner_after_user", "sandbox_after_run",
)
CYCLE = ("upload", "exec", "download", "turnover_before", "pool_idle_before")
STAGE_KEYS = ("edge_before", "edge_after", "turnover_before", "pool_idle_before") + EXEC_PARTS


def mean_ms(values) -> float:
    values = list(values)
    return round(1000.0 * sum(values) / len(values), 3) if values else 0.0


def span_means(traces: list[list[dict]]) -> dict[str, float]:
    """Mean length in ms of every span name over `traces` (a name that a
    trace lacks counts as absent there, not as 0)."""
    lengths = defaultdict(list)
    for spans in traces:
        for span in spans:
            lengths[span["name"]].append(span["duration_s"])
    return {name: mean_ms(found) for name, found in sorted(lengths.items())}


def prints(args) -> int:
    from lib.service import Service, build_executor

    build_executor()
    workdir = ROOT / "benchmarks" / "chip" / ".work" / "stage_table"
    shutil.rmtree(workdir, ignore_errors=True)
    extra = {"JAX_PLATFORMS": "cpu", "APP_EXECUTOR_POD_QUEUE_TARGET_LENGTH": "1"} if args.cpu else {}
    service = Service(workdir / "service", {}, extra)
    try:
        warm_s = service.wait_warm()
        client = service.client()
        turns = []
        for _ in range(args.count + args.skip):
            t0 = time.perf_counter()
            reply = client.json("POST", "/v1/execute", {"source_code": "print(21 * 2)", "timeout": 60})
            turns.append({"client_s": time.perf_counter() - t0, "phases": reply["phases"]})
            if args.pause:
                time.sleep(args.pause)
        time.sleep(0.5)  # the last turnover ends off the request's path
        turns = turns[args.skip:]
        requests = [client.json("GET", f"/traces/{t['phases']['trace_id']}")["spans"] for t in turns]
        listing = client.json("GET", "/traces?limit=200")["traces"]
        turnovers = [client.json("GET", f"/traces/{row['trace_id']}")["spans"]
                     for row in listing if row["root"] == "pool.turnover"][: len(turns)]
        client.close()
        service.stop()
    except BaseException:
        print(service.log_tails(), file=sys.stderr)
        service.kill()
        raise
    phases = {k: mean_ms(t["phases"][k] for t in turns)
              for k in ("queue_wait", "upload", "exec", "download") + STAGE_KEYS}
    remainder = mean_ms(t["phases"]["exec"] - sum(t["phases"][k] for k in EXEC_PARTS) for t in turns)
    print(json.dumps({
        "warm_s": round(warm_s, 2), "turns": len(turns), "client_ms": mean_ms(t["client_s"] for t in turns),
        "phases_ms": phases, "exec_remainder_ms": remainder,
        "request_spans_ms": span_means(requests), "turnover_spans_ms": span_means(turnovers),
    }))
    return 0


def turns_table(args) -> int:
    turns = [json.loads(line) for line in Path(args.turns).read_text().splitlines()]
    window = [t for t in turns if t.get("in_window") and t["status"] == 200]
    if not window:
        print("no served turn in the window", file=sys.stderr)
        return 1
    opened = min(t["answered"] for t in turns if t.get("in_window"))
    by_trace = defaultdict(list)
    if args.spans:
        for line in Path(args.spans).read_text().splitlines():
            span = json.loads(line)
            by_trace[span["trace_id"]].append(span)
    table = {}
    for payload in sorted({t["payload"] for t in window}):
        plain = [t for t in window if t["payload"] == payload and not t["profiled"]]
        if not plain:
            continue
        row = {"turns": len(plain), "client_ms": mean_ms(t["client_s"] for t in plain)}
        for key in ("queue_wait", "upload", "exec", "download") + STAGE_KEYS:
            row[key] = mean_ms(t["phases"][key] for t in plain)
        row["exec_remainder"] = mean_ms(
            t["phases"]["exec"] - sum(t["phases"][k] for k in EXEC_PARTS) for t in plain)
        if by_trace:
            row["spans_ms"] = span_means([by_trace[t["phases"]["trace_id"]] for t in plain])
        table[payload] = row
    cycle = sum(t["phases"][k] for t in window for k in CYCLE)
    print(json.dumps({
        "served_in_window": len(window),
        "holder_cycle_s": round(cycle, 4),
        "first_answer_at_s": round(opened, 3),
        "per_payload": table,
        "turnover_spans_ms": span_means(
            [spans for spans in by_trace.values() if any(s["name"] == "pool.turnover" for s in spans)]),
    }))
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="mode", required=True)
    p = sub.add_parser("prints")
    p.add_argument("--count", type=int, default=25)
    p.add_argument("--skip", type=int, default=2, help="turns sent first and left out (the fresh spawn's)")
    p.add_argument("--pause", type=float, default=0.0, help="seconds between turns")
    p.add_argument("--cpu", action="store_true", help="a stated CPU, as the rehearsal: no chip reading")
    t = sub.add_parser("turns")
    t.add_argument("turns")
    t.add_argument("spans", nargs="?")
    args = parser.parse_args()
    return prints(args) if args.mode == "prints" else turns_table(args)


if __name__ == "__main__":
    sys.exit(main())
