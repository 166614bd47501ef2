"""The comparison that decides `correct`: every turn answered in the window
against the plain reference's run of the same turn.

`compare_text` is chip_smoke.py's, made to return the widest relative gap
between numeric tokens instead of judging it, so that the caller can print
each number beside its limit. The reference itself is the configuration's
own file (`configs/<name>.reference.py`); this module runs it, keeps what it
returned under `.work/refs/` keyed by the reference's text, the sources and
the inputs, and sets the served turns beside it.
"""

from __future__ import annotations

import concurrent.futures
import hashlib
import importlib.util
import json
import re
from pathlib import Path

_NUMBER = re.compile(r"[-+]?(?:\d[\d_]*\.\d*|\.\d+|\d[\d_]*)(?:[eE][-+]?\d+)?")
# Left by the harness's own `profile: true` in a traced run, not by the turn.
PROFILE_ARTIFACT = "profile.zip"
WORKSPACE = "/workspace/"  # changed files are named by their path under it


def compare_text(got: str, want: str) -> tuple[str | None, float]:
    """(what differs apart from numbers, or None; the widest relative gap
    between numeric tokens that stand at the same place)."""
    got_lines, want_lines = got.splitlines(), want.splitlines()
    if len(got_lines) != len(want_lines):
        return f"{len(got_lines)} lines, reference has {len(want_lines)}", 0.0
    gap = 0.0
    for g, w in zip(got_lines, want_lines):
        if _NUMBER.split(g) != _NUMBER.split(w):
            return f"{g[:200]!r} vs reference {w[:200]!r}", gap
        for gn, wn in zip(_NUMBER.findall(g), _NUMBER.findall(w)):
            a, b = float(gn.replace("_", "")), float(wn.replace("_", ""))
            if a != b:
                gap = max(gap, abs(a - b) / max(abs(b), 1e-300))
    return None, gap


def load_reference(path: Path):
    spec = importlib.util.spec_from_file_location("chipbench_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def chain_key(reference_text: str, chain: list[dict]) -> str:
    digest = hashlib.sha256(reference_text.encode())
    for turn in chain:
        digest.update(b"\0turn\0" + turn["source"].encode())
        for name in sorted(turn["files"]):
            digest.update(b"\0file\0" + name.encode() + b"\0")
            digest.update(hashlib.sha256(turn["files"][name]).digest())
    return digest.hexdigest()


def expected_for(reference_path: Path, chains: dict[str, list[dict]], cache: Path,
                 scratch: Path, workers: int = 2) -> dict[str, list[dict]]:
    """{chain id: [{"stdout", "exit_code", "files": {name: sha256}} per
    turn]}. A chain is what the reference runs in one go: one stateless turn,
    or the turns of one session in their order. Chains not yet under `cache`
    run now, `workers` at a time (stock numpy over 1.2e9 elements holds
    about 10 GB a process)."""
    text = reference_path.read_text()
    reference = load_reference(reference_path)
    cache.mkdir(parents=True, exist_ok=True)
    out, todo = {}, {}
    for cid, chain in chains.items():
        key = chain_key(text, chain)
        path = cache / f"{key}.json"
        if path.is_file():
            out[cid] = json.loads(path.read_text())
        else:
            todo.setdefault(key, (path, chain, []))[2].append(cid)

    def run(item):
        path, chain, _ = item
        result = reference.run(chain, scratch)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(result))
        tmp.replace(path)
        return result

    with concurrent.futures.ThreadPoolExecutor(max_workers=workers) as pool:
        for item, result in zip(todo.values(), pool.map(run, todo.values())):
            for cid in item[2]:
                out[cid] = result
    return out


def judge(turns: list[dict], expected: dict[str, list[dict]], limits: dict[str, float]) -> dict:
    """Each number compared beside its limit, `[value, limit]` by a short
    plain name, and `equal`/`gap` written into every turn. A turn is a dict
    with `payload`, `chain`, `place` (its index in the chain), `status`, and
    where served `stdout`, `exit_code`, `files` ({path: hash}; the service names a
    file by the sha256 of its content), and in a session `session_seq`.
    `limits` is {payload: widest relative gap allowed}."""
    counts = {"unanswered": 0, "exit_code": 0, "text": 0, "files": 0, "order": 0}
    gaps: dict[str, float] = {}
    notes = []
    for turn in turns:
        turn["equal"], turn["gap"] = False, None
        if turn["status"] != 200:
            counts["unanswered"] += 1
            notes.append(f"{turn['payload']}: status {turn['status']}: {turn.get('error', '')[:200]}")
            continue
        want = expected[turn["chain"]][turn["place"]]
        faults = []
        if turn["exit_code"] != want["exit_code"]:
            counts["exit_code"] += 1
            faults.append(f"exit {turn['exit_code']}, reference {want['exit_code']}: "
                          f"{turn.get('stderr_tail', '')[-200:]!r}")
        diff, gap = compare_text(turn["stdout"], want["stdout"])
        if diff is not None:
            counts["text"] += 1
            faults.append(f"stdout: {diff}")
        got_files = {
            p.removeprefix(WORKSPACE): h for p, h in turn["files"].items()
            if p.removeprefix(WORKSPACE) != PROFILE_ARTIFACT
        }
        if got_files != want["files"]:
            counts["files"] += 1
            faults.append(f"changed files {sorted(got_files)} vs reference {sorted(want['files'])}"
                          if set(got_files) != set(want["files"]) else "changed files differ in content")
        if "session_seq" in turn and turn["session_seq"] != turn["place"] + 1:
            counts["order"] += 1
            faults.append(f"session_seq {turn['session_seq']} at place {turn['place']}")
        turn["gap"] = gap
        gaps[turn["payload"]] = max(gaps.get(turn["payload"], 0.0), gap)
        turn["equal"] = not faults and gap <= limits[turn["payload"]]
        if faults:
            notes.append(f"{turn['payload']}: " + "; ".join(faults))
    checks = {name: [count, 0] for name, count in counts.items()}
    for payload, gap in sorted(gaps.items()):
        checks[f"rel_gap.{payload}"] = [gap, limits[payload]]
    checks["compared"] = len(turns)
    return {
        "checks": checks,
        "correct": bool(turns) and all(
            value <= limit for value, limit in
            (v for v in checks.values() if isinstance(v, list))
        ),
        "notes": notes[:20],
    }
