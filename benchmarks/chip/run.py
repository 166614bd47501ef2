#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the served Execute path.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the executor where it is missing or older than its sources, starts
`python -m bee_code_interpreter_fs_tpu` (local backend, as shipped) as a
child, waits for the one warm sandbox, asks it through Execute what it
attached (no TPU: exit 1, no result line), uploads the cell's input files,
sends every distinct turn of the cell once, starts the clients LEAD_IN_S
before the window opens, measures for `--seconds`, lets the turns in flight
end, reads the sandbox's /device-stats, stops the service and sees that no
process of it is left; then runs the configuration's plain reference (stock
python, no shim, JAX_PLATFORMS=cpu) over every turn answered in the window,
compares, and prints the result as the last line of stdout.

Everything that belongs to one cell, payload, metric or reader is data under
this directory, found by the names in BENCHMARK.json (lib/manifest.py); this
file names none of them. This process never imports jax: the chip belongs to
the sandbox's warm runner.

`--rehearse` runs the same flow at each payload's tiny sizes with the stated
platform JAX_PLATFORMS=cpu and reports no device metric. `--control` sends
each array payload's lower-precision variant in place of the payload, while
the reference keeps the sound source: `correct` must come out false.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from lib import compare, profile_reduce, stats  # noqa: E402
from lib.manifest import ROOT, Manifest, UnknownName  # noqa: E402
from lib.peaks import peaks_of  # noqa: E402
from lib.service import Client, HarnessError, Service, build_executor, log  # noqa: E402
from lib.traffic import Plan, evaluate  # noqa: E402

WORK = BENCH / ".work"
LEAD_IN_S = 4.0
# The sandbox says what it attached through the served path itself, before
# any load is sent; /device-stats says it again after the window.
PROBE = (
    "import jax\nd = jax.devices()\n"
    "print('attached', d[0].platform, '|', d[0].device_kind, '|', len(d))\n"
)
# The peak on the fullest chip, asked the same way once the window has closed:
# the allocator's own high-water mark of the warm runner, which by then has
# run this cell's turns and no others (warm-up, lead-in, window). The
# program's per-turn `phases.peak_hbm_bytes` cannot say it: where the
# process's peak does not move, it gives what was live before and after the
# turn, and an array that the turn made and dropped is in neither.
PEAK_PROBE = (
    "import jax\n"
    "print('peak', max((d.memory_stats() or {}).get('peak_bytes_in_use', 0) for d in jax.local_devices()))\n"
)


def execute(client: Client, turn: dict, hashes: dict, executor_id: str | None) -> dict:
    """One POST /v1/execute, timed by the client from send to last byte; `sent`
    and `answered` are on T_START's clock."""
    body = {
        "source_code": turn["source"],
        "timeout": 600,
        "files": {f"/workspace/{name}": hashes[key] for name, key in turn["input_keys"].items()},
    }
    if executor_id is not None:
        body["executor_id"] = executor_id
    if turn["profile"]:
        body["profile"] = True
    record = {k: turn[k] for k in ("payload", "params", "chain", "place")}
    record["profiled"] = turn["profile"]
    t0 = time.perf_counter()
    try:
        status, raw = client.call("POST", "/v1/execute", body)
        error = None
    except Exception as e:  # noqa: BLE001 — a refused or broken turn is a failed turn, counted
        status, raw, error = 0, b"", repr(e)
    t1 = time.perf_counter()
    record.update(sent=t0 - T_START, answered=t1 - T_START, client_s=t1 - t0, status=status)
    if status == 200:
        reply = json.loads(raw)
        record.update(
            stdout=reply["stdout"], exit_code=reply["exit_code"], files=reply["files"],
            phases=reply["phases"], warm=reply["warm"], stderr_tail=reply["stderr"][-300:],
        )
        if executor_id is not None:
            record["session_seq"] = reply.get("session_seq")
    else:
        record["error"] = error or raw[:300].decode("utf-8", "replace")
    return record


class Run:
    def __init__(self, args, manifest: Manifest) -> None:
        self.args, self.manifest = args, manifest
        self.cell = manifest.cell(args.workload)
        self.payloads = manifest.payloads_of(self.cell["traffic"])
        self.plan = Plan(self.cell["traffic"], self.payloads, args.seed, rehearse=args.rehearse,
                         control=args.control, trace=bool(args.trace))
        self.hashes: dict = {}
        self.records: list[dict] = []
        self.lock = threading.Lock()
        self.next_index = itertools.count()
        self.close_at = float("inf")

    # -- set-up
    def upload(self, client: Client, turns) -> None:
        """PUT each distinct input file once; Execute then names its hash."""
        for turn in turns:
            for name, key in turn["input_keys"].items():
                if key not in self.hashes:
                    self.hashes[key] = client.json("PUT", "/v1/files", turn["inputs"][name])["hash"]

    def ask(self, client: Client, source: str, starts: str) -> dict:
        """One turn of the harness's own through the served path."""
        turn = {"source": source, "inputs": {}, "input_keys": {}, "profile": False, "payload": "probe",
                "params": {}, "chain": "", "place": 0}
        got = execute(client, turn, {}, None)
        if got["status"] != 200 or got["exit_code"] != 0 or not got["stdout"].startswith(starts):
            raise HarnessError(f"the probe turn failed: {got}")
        return got

    def memory_peak(self, client: Client) -> int:
        return int(self.ask(client, PEAK_PROBE, "peak ")["stdout"].split()[1])

    def probe(self, client: Client) -> dict:
        got = self.ask(client, PROBE, "attached ")
        platform, kind, count = (s.strip() for s in got["stdout"][len("attached "):].split("|"))
        wanted = "cpu" if self.args.rehearse else "tpu"
        if platform != wanted:
            raise HarnessError(f"no TPU: the sandbox attached {platform!r} ({kind}); a measured run has no other mode")
        if int(count) < self.cell["entry"]["chips"]:
            raise HarnessError(f"the cell asks for {self.cell['entry']['chips']} chips, the sandbox holds {count}")
        if not got["warm"]:
            raise HarnessError("the probe turn did not run in the warm runner")
        return {"platform": platform, "kind": kind, "count": int(count)}

    def warm_up(self, client: Client) -> None:
        for executor_id, turns in self.plan.warmup():
            self.upload(client, turns)
            for turn in turns:
                got = execute(client, turn, self.hashes, executor_id)
                if got["status"] != 200:
                    raise HarnessError(f"warm-up turn {turn['payload']} failed: {got}")
                log(f"warm-up {turn['payload']}{' profiled' if turn['profile'] else ''}: "
                    f"{got['client_s']:.3f}s exit={got['exit_code']} "
                    f"misses={got['phases'].get('compile_cache_misses')}")
            if executor_id is not None:
                client.call("DELETE", f"/v1/executors/{executor_id}")

    # -- the clients
    def stateless_client(self, client: Client, number: int) -> None:
        while time.perf_counter() < self.close_at:
            with self.lock:
                index = next(self.next_index)
                turn = self.plan.stateless(index)
            record = execute(client, turn, self.hashes, None)
            record.update(client=number, index=index)
            with self.lock:
                self.records.append(record)

    def session_client(self, client: Client, number: int) -> None:
        while time.perf_counter() < self.close_at:
            with self.lock:
                n = next(self.next_index)
            executor_id, turns = self.plan.session_turns(n)
            for turn in turns:
                record = execute(client, turn, self.hashes, executor_id)
                record.update(client=number, index=n)
                with self.lock:
                    self.records.append(record)
                if record["status"] != 200 or time.perf_counter() >= self.close_at:
                    break
            client.call("DELETE", f"/v1/executors/{executor_id}")

    def drive(self, service: Service) -> tuple[float, float]:
        """Clients start now; the window opens LEAD_IN_S later on a full
        queue. Returns the window's opening and close on T_START's clock."""
        if self.plan.order == "sessions":
            # the inputs of every variant, before the clock matters
            admin = service.client()
            for n in range(len(self.plan.session["variants"]) * 2):
                self.upload(admin, self.plan.session_turns(n)[1])
            target = self.session_client
        else:
            admin = service.client()
            self.upload(admin, [self.plan.stateless(i) for i in range(len(self.plan.deck))])
            target = self.stateless_client
        admin.close()
        opened = time.perf_counter() + LEAD_IN_S
        self.close_at = opened + self.args.seconds
        threads = [
            threading.Thread(target=target, args=(service.client(), i), daemon=True)
            for i in range(self.plan.clients)
        ]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=LEAD_IN_S + self.args.seconds + 120)
            if thread.is_alive():
                raise HarnessError("a client did not end within two minutes of the window's close")
        return opened - T_START, self.close_at - T_START


def device_block(service: Service, probe: dict, rehearse: bool) -> dict:
    """What the sandbox's /device-stats says it attached, which has to be
    what the probe turn saw: one sandbox, one attach. (On the rehearsal's
    CPU nothing caps the pool at one holder: the probe's word stands.)"""
    if rehearse:
        return dict(probe)
    found = service.sandbox_stats()
    if len(found) != 1:
        raise HarnessError(f"the pool should hold ONE chip holder, /statusz lists {len(found)}")
    stats_ = found[0]
    block = {"platform": stats_["backend"], "kind": stats_["device_kind"], "count": int(stats_["device_count"])}
    if block != probe:
        raise HarnessError(f"/device-stats says {block}, the probe turn saw {probe}")
    return block


def fetch_traces(service: Service, window: list[dict], others: list[dict], rehearse: bool) -> None:
    """Reduce each profiled turn's profile.zip to `busy_s` and `ops`."""
    client = service.client()
    for turn in window + others:
        if not turn["profiled"] or turn["status"] != 200:
            continue
        ref = turn["files"].get(f"/workspace/{compare.PROFILE_ARTIFACT}")
        if ref is None:
            raise HarnessError(f"a profiled {turn['payload']} turn came back without profile.zip")
        status, data = client.call("GET", f"/v1/files/{ref}")
        if status != 200:
            raise HarnessError(f"GET /v1/files/{ref} -> {status}")
        try:
            reduced = profile_reduce.reduce_profile_zip(data)
        except profile_reduce.NoDevicePlane:
            if rehearse:  # the CPU's trace has no device plane: no device metric
                continue
            raise HarnessError(f"the trace of a {turn['payload']} turn holds no device plane") from None
        turn["busy_s"], turn["ops"] = reduced["busy_s"], reduced["ops"]
    client.close()


def device_time(window: list[dict], others: list[dict], payloads: dict) -> dict | None:
    """Device-busy seconds of the window: per payload that states a floor,
    the mean busy time of its profiled turns times its turns in the window
    (a turn's device time does not depend on whether it was profiled; the
    other payloads run no device program). None where nothing was traced."""
    per_payload, turn_busy, ops = {}, {}, {}
    for name, spec in payloads.items():
        if "floor" not in spec:
            continue
        count = sum(1 for t in window if t["payload"] == name and t["status"] == 200)
        if not count:
            continue
        traced = [t for t in window if t["payload"] == name and "busy_s" in t] or \
                 [t for t in others if t["payload"] == name and "busy_s" in t]
        if not traced:
            return None
        turn_busy[name] = [t["busy_s"] for t in traced]
        per_payload[name] = count * sum(turn_busy[name]) / len(traced)
        for t in traced:
            for op, seconds in t["ops"].items():
                ops[op] = ops.get(op, 0.0) + seconds * count / len(traced)
    if not per_payload:
        return None
    return {"busy_s": sum(per_payload.values()), "per_payload": per_payload,
            "turn_busy": turn_busy, "ops": ops}


def breakdown(window: list[dict], busy: dict, window_s: float) -> dict:
    """Top device operations by name, and the idle time by what the host was
    doing: inside a payload's exec phase, or outside any exec."""
    served = [t for t in window if t["status"] == 200]
    gaps = {}
    for name in sorted({t["payload"] for t in served}):
        inside = sum(t["phases"]["exec"] for t in served if t["payload"] == name)
        gaps[f"exec:{name}"] = inside - busy["per_payload"].get(name, 0.0)
    gaps["outside_exec"] = window_s - sum(t["phases"]["exec"] for t in served)
    top = sorted(busy["ops"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "device_ops": [[name[:160], seconds] for name, seconds in top],
        "idle_gaps": [[k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1]) if v > 0][:10],
    }


def log_stalls(service: Service, window: list[dict]) -> None:
    """For whoever reads a run that came out slow: the window's turns whose
    exec took three times their payload's median, and what the service's log
    says of its own auto-profiler (`perf_profile_auto`, on as shipped: one
    slow request arms a capture of the next, which costs that one a second)."""
    served = [t for t in window if t["status"] == 200 and not t["profiled"]]
    for name in sorted({t["payload"] for t in served}):
        execs = sorted(t["phases"]["exec"] for t in served if t["payload"] == name)
        slow = [round(e, 3) for e in execs if e > 3 * execs[len(execs) // 2]]
        if slow:
            log(f"stalls: {name} exec {slow} s against a median of {execs[len(execs) // 2]:.3f}")
    lines = service.log_path.read_text(errors="replace").splitlines()
    auto = [line.split("auto-profile ", 1)[1][:120] for line in lines if "auto-profile armed" in line or "auto-profile captured" in line]
    log(f"the service's auto-profiler in the whole run: {auto}")


def end_to_end(window: list[dict], seconds: float, setup_s: float) -> dict:
    """The arithmetic of the judged numbers: all the work over all the time,
    and the tail of ALL turns (a failed turn counts as the worst)."""
    good = sum(1 for t in window if t.get("equal"))
    worst = max((t["client_s"] for t in window), default=0.0)
    times = [t["client_s"] if t["status"] == 200 else worst for t in window]
    return {
        "turns_per_s": stats.rate(good, seconds),
        "turn_p90_ms": stats.percentile(times, 90) * 1000 if times else None,
        "setup_s": setup_s,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true", help="tiny sizes on a stated CPU; no device metric")
    parser.add_argument("--control", action="store_true", help="send the lower-precision control; correct must read false")
    parser.add_argument("--turns-out", help="also write every turn of the run to this .jsonl")
    parser.add_argument("--benchmark-json", help="another BENCHMARK.json than the repository's (a test's own cells)")
    args = parser.parse_args(argv)
    try:
        return measure(args)
    except (HarnessError, UnknownName) as e:
        print(f"[chipbench] FAILED: {e}", file=sys.stderr, flush=True)
        return 1


def measure(args) -> int:
    if not (ROOT / "executor" / "server.cpp").is_file() or not (ROOT / "bee_code_interpreter_fs_tpu").is_dir():
        raise HarnessError("the program is not in this checkout: nothing to measure")
    manifest = Manifest(args.benchmark_json)
    run = Run(args, manifest)
    cell = args.workload
    limits = {name: spec["rel_limit"] for name, spec in run.payloads.items()}
    built = build_executor()
    log(f"cell {cell} seed {args.seed}: executor ready in {built:.1f}s")

    workdir = WORK / "run"
    shutil.rmtree(workdir, ignore_errors=True)
    extra = {"JAX_PLATFORMS": "cpu", "APP_EXECUTOR_POD_QUEUE_TARGET_LENGTH": "1"} if args.rehearse else {}
    service = Service(workdir / "service", run.cell["config"]["service_env"], extra)
    try:
        warm_s = service.wait_warm()
        log(f"one warm sandbox {warm_s:.1f}s after service start")
        client = service.client()
        probe = run.probe(client)
        log(f"attached: {probe}")
        run.warm_up(client)
        client.close()
        opened, closed = run.drive(service)
        log(f"window {opened:.3f}..{closed:.3f}s, {len(run.records)} turns sent in the run")
        window = [t for t in run.records if opened <= t["answered"] <= closed]
        if not window:
            raise HarnessError("no turn was answered inside the window")
        others = [t for t in run.records if not opened <= t["answered"] <= closed]
        device = device_block(service, probe, args.rehearse)
        client = service.client()
        device["memory_peak_bytes"] = run.memory_peak(client)
        client.close()
        if args.trace:
            fetch_traces(service, window, others, args.rehearse)
        stopped = service.stop()
        log(f"service stopped in {stopped:.1f}s, no process left")
        log_stalls(service, window)
    except BaseException:
        print(service.log_tails(), file=sys.stderr, flush=True)
        service.kill()
        raise

    # The plain reference, after the chip is given back: every turn answered
    # in the window, each distinct chain once (kept under .work/refs/).
    t0 = time.perf_counter()
    chains: dict[str, list[dict]] = {}
    if run.plan.order == "sessions":
        for n in sorted({t["index"] for t in window}):
            turns = run.plan.session_turns(n)[1]
            chains.setdefault(turns[0]["chain"], turns)
    else:
        for t in window:
            chains.setdefault(t["chain"], [run.plan.stateless(t["index"])])
    expected = compare.expected_for(
        run.cell["reference"],
        {cid: [{"source": t["reference_source"], "files": t["inputs"]} for t in turns]
         for cid, turns in chains.items()},
        WORK / "refs", WORK / "run" / "reference",
    )
    verdict = compare.judge(window, expected, limits)
    log(f"reference and comparison of {len(window)} turns over {len(chains)} chains: {time.perf_counter() - t0:.1f}s")

    seconds = closed - opened
    window_ctx = {
        "window_s": seconds, "payloads": run.payloads, "device_kind": device["kind"],
        "busy": None, "evaluate": evaluate,
    }
    if args.trace:
        busy = device_time(window, others, run.payloads)
        if busy is None and not args.rehearse:
            raise HarnessError("a traced run in which no operation ran on the device")
        if busy is not None:
            window_ctx["busy"] = busy
            window_ctx["peaks"] = peaks_of(device["kind"])
            device["busy_s"], device["window_s"] = busy["busy_s"], seconds
        metrics = {}
        for entry in manifest.metrics("per_layer", cell):
            spec, read = manifest.layer_metric(entry["name"])
            value = read(window, spec.get("args", {}), window_ctx)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        values = end_to_end(window, seconds, opened)
        metrics = {
            entry["name"]: {"value": values[entry["name"]], "unit": entry["unit"]}
            for entry in manifest.metrics("end_to_end", cell)
        }

    if args.turns_out:
        Path(args.turns_out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.turns_out, "w") as out:
            for t in run.records:
                slim = {k: v for k, v in t.items() if k not in ("ops", "stdout", "files")}
                slim["in_window"] = opened <= t["answered"] <= closed
                out.write(json.dumps(slim) + "\n")

    result = {
        "correct": verdict["correct"],
        "attempted": len(window),
        "failed": sum(1 for t in window if t["status"] != 200),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and window_ctx["busy"] is not None:
        result["breakdown"] = breakdown(window, window_ctx["busy"], seconds)
    result["checks"] = verdict["checks"]
    for note in verdict["notes"]:
        print(f"[chipbench] differs: {note}", file=sys.stderr)
    print("[chipbench] compared (value, limit): " + json.dumps(verdict["checks"]), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
