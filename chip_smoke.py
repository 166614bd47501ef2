#!/usr/bin/env python3
"""The quickest proof that the served Execute path still starts on the chip.

Builds the executor from the committed sources, starts the service itself
(`python -m bee_code_interpreter_fs_tpu`, local backend) as a child process,
waits for the pool's one warm sandbox, requires that sandbox to report a TPU,
and sends a few requests through `POST /v1/execute` (and one through gRPC
`Execute`): print, the workspace-file round trip, examples/benchmark-numpy.py
unchanged at its published N = 1e8, examples/benchmark-matmul.py, examples/
benchmark-attention.py (the Pallas kernel through Mosaic), a flash-vs-dense
check, two dependent turns of one session, the session's close, and a plain
request after it all. Each is compared with a plain reference: the same
source under stock `python` in a subprocess, no shim, JAX_PLATFORMS=cpu — on
stdout, exit code and changed files, numeric tokens within the shim's
documented bound (rtol 1e-5). Then the service is stopped and started once
more, and the same compiled payload must hit the compile cache.

With `--chips 4` it runs ONLY the path that exists across chips and what that
is compared with: one sandbox process driving four chips, psum over the mesh
against plain sums, and one fused batch of four small jobs (one per device)
against the same jobs dispatched serially.

It has no CPU mode: where the sandbox attaches no TPU it exits non-zero with
the log tails and prints no result line. This process never imports jax — a
chip belongs to one process, and that process is the sandbox's warm runner.
Snippets that need the network (examples/using_imports.py, cowsay.py, tcp.py,
anything that makes executor/deps.py pip-install) are left out: the machine
with the chip has none. Timings printed here are smoke readings, not
measurements. The last line of stdout is the result:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / "chip_smoke_out"
EXAMPLES = ROOT / "examples"
RTOL = 1e-5  # the shim's documented bound, ops/npdispatch/shim.py
PLATFORM = "tpu"  # what the sandbox must have attached; there is no other mode
NUMPY_N = 100_000_000  # examples/benchmark-numpy.py's published size
WARM_TIMEOUT_S = 600.0
SESSION_ID = "chip-smoke-session"


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def result_line(stats: dict) -> str:
    """The one line the driver reads, from what the sandbox's /device-stats
    reported: exactly these keys, nothing more."""
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": stats["backend"],
                "kind": stats["device_kind"],
                "count": stats["device_count"],
            },
        }
    )


# -- comparison with the plain reference --------------------------------------

_NUMBER = re.compile(r"[-+]?(?:\d[\d_]*\.\d*|\.\d+|\d[\d_]*)(?:[eE][-+]?\d+)?")


def compare_text(got: str, want: str, *, rtol: float = RTOL, ignore=()) -> str | None:
    """None when `got` equals `want` line by line with numeric tokens within
    `rtol`; else what differs. Lines matching an `ignore` pattern (timings)
    are dropped from both sides first."""

    def kept(text: str) -> list[str]:
        return [
            line
            for line in text.splitlines()
            if not any(re.search(p, line) for p in ignore)
        ]

    got_lines, want_lines = kept(got), kept(want)
    if len(got_lines) != len(want_lines):
        return f"{len(got_lines)} lines, reference has {len(want_lines)}: {got!r} vs {want!r}"
    for g, w in zip(got_lines, want_lines):
        if _NUMBER.split(g) != _NUMBER.split(w):
            return f"{g!r} vs reference {w!r}"
        for gn, wn in zip(_NUMBER.findall(g), _NUMBER.findall(w)):
            a, b = float(gn.replace("_", "")), float(wn.replace("_", ""))
            if not math.isclose(a, b, rel_tol=rtol, abs_tol=rtol):
                return f"{gn} vs reference {wn} (rtol {rtol}) in {g!r}"
    return None


def reference_run(*sources: str, files: dict[str, bytes] | None = None):
    """The plain reference: the source under stock python in a fresh
    directory, no sitecustomize, no shim, JAX_PLATFORMS=cpu. Returns (stdout,
    exit_code, {changed file: sha256}). Several sources are the turns of one
    session in one directory; the last turn's result is returned."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("PYTHONPATH", "APP_NUMPY_DISPATCH", "XLA_FLAGS")
    }
    env["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory(prefix="ref-", dir=WORK) as tmp:
        workspace = Path(tmp) / "workspace"
        workspace.mkdir()

        def hashes() -> dict[str, str]:
            return {
                p.name: hashlib.sha256(p.read_bytes()).hexdigest()
                for p in workspace.iterdir()
            }

        for name, data in (files or {}).items():
            (workspace / name).write_bytes(data)
        for i, turn in enumerate(sources):
            before = hashes()
            script = Path(tmp) / f"turn{i}.py"
            script.write_text(turn)
            proc = subprocess.run(
                [sys.executable, str(script)],
                cwd=workspace,
                env=env,
                capture_output=True,
                text=True,
                timeout=600,
            )
        changed = {n: h for n, h in hashes().items() if before.get(n) != h}
        return proc.stdout, proc.returncode, changed


# -- the service as a child process -------------------------------------------


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def build_executor() -> None:
    """From the committed sources, every time: `executor/build/` is ignored
    by git, and a stale binary on disk would be what runs."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        ["make", "-B", "-C", str(ROOT / "executor")],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        raise SmokeFailure(f"executor build failed:\n{proc.stdout}\n{proc.stderr}")
    say(f"built executor/build/executor-server in {time.perf_counter() - t0:.1f}s")


class Service:
    def __init__(self, tag: str, chips: int) -> None:
        self.tag, self.chips = tag, chips
        self.dir = WORK / tag
        self.dir.mkdir(parents=True)
        self.http = f"http://127.0.0.1:{free_port()}"
        self.grpc_target = f"127.0.0.1:{free_port()}"
        self.log_path = self.dir / "service.log"
        self.lane = str(chips if chips > 1 else 0)
        env = dict(os.environ)
        env.update(
            APP_EXECUTOR_BACKEND="local",
            APP_HTTP_LISTEN_ADDR=self.http.removeprefix("http://"),
            APP_GRPC_LISTEN_ADDR=self.grpc_target,
            APP_FILE_STORAGE_PATH=str(self.dir / "storage"),
            APP_LOCAL_SANDBOX_ROOT=str(self.dir / "sandboxes"),
            # Quick /statusz host rows (the probe is a trivial stats read).
            APP_DEVICE_PROBE_INTERVAL="2",
        )
        if chips > 1:
            env.update(
                APP_DEFAULT_CHIP_COUNT=str(chips),
                # One full batch fires at once; the window only has to
                # outlast four HTTP requests sent from four threads.
                APP_BATCH_MAX_JOBS=str(chips),
                APP_BATCH_WINDOW_MS="500",
            )
        self.started = time.perf_counter()
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "bee_code_interpreter_fs_tpu"],
                cwd=ROOT,
                env=env,
                stdout=log,
                stderr=subprocess.STDOUT,
            )

    # -- plumbing
    def get(self, path: str, base: str | None = None, timeout: float = 10.0):
        with urllib.request.urlopen((base or self.http) + path, timeout=timeout) as r:
            return json.load(r)

    def call(self, method: str, path: str, body=None, timeout: float = 660.0):
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
        req = urllib.request.Request(
            self.http + path,
            data=None if body is None else data,
            method=method,
            headers={"content-type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def execute(self, source: str, **fields) -> dict:
        t0 = time.perf_counter()
        status, raw = self.call(
            "POST", "/v1/execute", {"source_code": source, "timeout": 600, **fields}
        )
        if status != 200:
            raise SmokeFailure(f"POST /v1/execute -> {status}: {raw[:800]!r}")
        body = json.loads(raw)
        body["wall_s"] = time.perf_counter() - t0
        return body

    def log_tails(self) -> str:
        parts = []
        logs = [self.log_path, *sorted(self.dir.glob("sandboxes/*/server.log"))]
        for path in logs:
            try:
                tail = path.read_bytes()[-6000:].decode("utf-8", "replace")
            except OSError:
                continue
            parts.append(f"--- tail of {path.relative_to(ROOT)} ---\n{tail}")
        return "\n".join(parts)

    # -- lifecycle
    def wait_warm(self) -> dict:
        """Until the pool reports its one warm sandbox; then what THAT
        sandbox says it attached (its /device-stats, found via /statusz)."""
        deadline = time.perf_counter() + WARM_TIMEOUT_S
        while True:
            if self.proc.poll() is not None:
                raise SmokeFailure(f"service exited rc={self.proc.returncode}")
            if time.perf_counter() > deadline:
                raise SmokeFailure(f"no warm sandbox within {WARM_TIMEOUT_S:.0f}s")
            try:
                status = self.get("/statusz")
            except (OSError, ValueError):
                time.sleep(0.5)
                continue
            lane = status["lanes"].get(self.lane, {})
            if lane.get("breaker", "closed") != "closed":
                raise SmokeFailure(f"spawn circuit {lane['breaker']}: sandboxes fail to warm")
            hosts = [
                h for h in status["device_health"]["hosts"] if str(h["lane"]) == self.lane
            ]
            # in_use counts too: the compile-cache pre-warm may hold the
            # (warm) sandbox for a moment right after the fill.
            if lane.get("pooled", 0) + lane.get("in_use", 0) == 1 and hosts:
                break
            time.sleep(0.5)
        warm_s = time.perf_counter() - self.started
        self.sandbox_url = hosts[0]["host"]
        stats = self.device_stats()
        say(
            f"{self.tag}: warm sandbox after {warm_s:.1f}s from service start "
            f"(sandbox attach+warm {stats['attach_seconds']:.1f}s): "
            f"backend={stats['backend']} kind={stats['device_kind']!r} "
            f"count={stats['device_count']} runner_pid={stats['runner_pid']:.0f}"
        )
        if stats["backend"] != PLATFORM:
            raise SmokeFailure(
                f"no TPU: the sandbox attached {stats['backend']!r}; "
                "chip_smoke.py has no CPU mode"
            )
        if lane["pool_target"] != 1 or len(hosts) != 1:
            raise SmokeFailure(
                f"the pool should settle at ONE chip holder: target "
                f"{lane['pool_target']}, hosts {[h['host'] for h in hosts]}"
            )
        return stats

    def device_stats(self) -> dict:
        return self.get("/device-stats", base=self.sandbox_url)

    def stop(self) -> None:
        """SIGTERM, then every process of this service must be gone — a
        runner left behind would still hold the chip."""
        t0 = time.perf_counter()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                raise SmokeFailure("service ignored SIGTERM for 90s") from None
        deadline = time.perf_counter() + 15.0
        while (left := processes_of(self.dir)) and time.perf_counter() < deadline:
            time.sleep(0.2)
        if left:
            for pid in left:
                os.kill(pid, signal.SIGKILL)
            raise SmokeFailure(f"processes outlived the service: {left}")
        say(f"{self.tag}: service stopped in {time.perf_counter() - t0:.1f}s, no process left")
        # One warm sandbox, one attach: a pool spinning on the chip's slot, or
        # any sandbox that failed to spawn, shows in the service's own log.
        log = self.log_path.read_text(errors="replace")
        for line in [l for l in log.splitlines() if l.startswith(("WARNING", "ERROR"))][:10]:
            say(f"  {self.tag} log: {line[:300]}")
        if "no TPU slot freed" in log or "SandboxSpawnError" in log:
            raise SmokeFailure(f"{self.tag}: a sandbox spawn failed, see the log lines above")

    def kill(self) -> None:
        """Failure path: leave nothing running."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        for pid in processes_of(self.dir):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


@contextlib.contextmanager
def serving(tag: str, chips: int):
    """The service for one phase: stopped cleanly after it, and on any
    failure its log tails printed and nothing left running."""
    service = Service(tag, chips)
    try:
        yield service
        service.stop()
    except BaseException:
        print(service.log_tails(), flush=True)
        service.kill()
        raise


def processes_of(directory: Path) -> list[int]:
    """Pids whose environment names `directory` (every sandbox server and
    runner carries its workspace path there)."""
    needle = str(directory).encode()
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            if needle in Path(f"/proc/{entry}/environ").read_bytes():
                found.append(int(entry))
        except OSError:
            continue
    return found


def chip_holders() -> list[int]:
    """Pids that have the TPU runtime mapped: at most one, the warm runner."""
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            if "libtpu" in Path(f"/proc/{entry}/maps").read_text():
                found.append(int(entry))
        except OSError:
            continue
    return found


# -- phases --------------------------------------------------------------------

FLASH_CHECK = """
import jax, jax.numpy as jnp

B, T, H, D = 1, 2048, 4, 128
q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
           for kk in jax.random.split(jax.random.PRNGKey(21), 3))

@jax.jit
def dense(q, k, v):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

want = dense(q, k, v)
print(f"dense_mean_abs={float(jnp.mean(jnp.abs(want))):.6f}")
if jax.devices()[0].platform == "tpu":
    from bee_code_interpreter_fs_tpu.ops.flash_attention import flash_attention
    got = jax.jit(flash_attention)(q, k, v)  # Mosaic: interpret is off
    print(f"flash_max_abs_err={float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))):.6f}")
"""

SESSION_TURN_1 = """
import numpy as np
x = np.linspace(0.0, 1.0, 2**22, dtype=np.float32)
np.save("state.npy", np.asarray(x))
print("saved", x.shape[0])
"""

SESSION_TURN_2 = """
import numpy as np
x = np.array(np.load("state.npy"))
print("array:", type(x).__name__)
print(f"sum_sq={float((x * x).sum()):.4f} mean={float(x.mean()):.6f}")
"""

HBM_PROBE = """
import jax
stats = jax.devices()[0].memory_stats() or {}
print(f"hbm_bytes_in_use={stats.get('bytes_in_use')} peak={stats.get('peak_bytes_in_use')}")
"""


PHASE_KEYS = (
    "queue_wait", "upload", "exec", "download", "device_op_seconds",
    "compile_cache_hits", "compile_cache_misses", "peak_hbm_bytes",
    "batch_jobs", "batch_index",
)


def check(name: str, got: dict, *, want=None) -> None:
    """One request's verdict: it ran warm (in the chip holder), and its exit
    code, stdout and changed files equal the reference run's."""
    phases = {k: round(got["phases"][k], 4) for k in PHASE_KEYS if k in got["phases"]}
    say(f"{name}: wall {got['wall_s']:.3f}s warm={got['warm']} phases={json.dumps(phases)}")
    if not got["warm"]:
        raise SmokeFailure(f"{name}: did not run in the warm runner: {got}")
    if want is None:
        if got["exit_code"] != 0:
            raise SmokeFailure(f"{name}: exit {got['exit_code']}: {got['stderr'][-1500:]}")
        return
    ref_stdout, ref_code, ref_files = want
    if got["exit_code"] != ref_code:
        raise SmokeFailure(
            f"{name}: exit {got['exit_code']}, reference {ref_code}: {got['stderr'][-1500:]}"
        )
    if (diff := compare_text(got["stdout"], ref_stdout)):
        raise SmokeFailure(f"{name}: stdout differs from the reference: {diff}")
    got_files = {Path(p).name: h for p, h in got["files"].items()}
    if got_files != ref_files:
        raise SmokeFailure(f"{name}: changed files {got_files}, reference {ref_files}")


def grpc_execute(service: Service, source: str) -> dict:
    import grpc

    from bee_code_interpreter_fs_tpu.proto import SERVICE_NAME
    from bee_code_interpreter_fs_tpu.proto import code_interpreter_pb2 as pb2

    t0 = time.perf_counter()
    with grpc.insecure_channel(service.grpc_target) as channel:
        call = channel.unary_unary(
            f"/{SERVICE_NAME}/Execute",
            request_serializer=pb2.ExecuteRequest.SerializeToString,
            response_deserializer=pb2.ExecuteResponse.FromString,
        )
        resp = call(pb2.ExecuteRequest(source_code=source), timeout=300.0)
    return {
        "stdout": resp.stdout,
        "stderr": resp.stderr,
        "exit_code": resp.exit_code,
        "files": dict(resp.files),
        "phases": {},
        "warm": True,  # the gRPC response carries no such field
        "wall_s": time.perf_counter() - t0,
    }


def labelled(stdout: str, pattern: str) -> list[str]:
    return [line for line in stdout.splitlines() if re.search(pattern, line)]


def one_chip_requests(service: Service, stats: dict) -> None:
    # print, over HTTP and over gRPC
    source = "print(21*2)"
    want = reference_run(source)
    check("print(21*2) [http]", service.execute(source), want=want)
    check("print(21*2) [grpc]", grpc_execute(service, source), want=want)

    # upload -> execute -> download round trip through the files API
    source = (EXAMPLES / "hello_world_write_file.py").read_text()
    want = reference_run(source)
    got = service.execute(source)
    check("hello_world_write_file.py", got, want=want)
    status, data = service.call("GET", f"/v1/files/{got['files']['/workspace/hello.txt']}")
    if status != 200 or hashlib.sha256(data).hexdigest() != want[2]["hello.txt"]:
        raise SmokeFailure(f"download of hello.txt -> {status}, {data[:80]!r}")
    payload = b"Hello from chip_smoke.py\n"
    status, raw = service.call("PUT", "/v1/files", payload)
    uploaded = json.loads(raw)["hash"]
    source = (EXAMPLES / "hello_world_read_file.py").read_text()
    check(
        "hello_world_read_file.py (uploaded file)",
        service.execute(source, files={"/workspace/hello.txt": uploaded}),
        want=reference_run(source, files={"hello.txt": payload}),
    )

    # the headline payload, unchanged, at its published size
    source = (EXAMPLES / "benchmark-numpy.py").read_text()
    got = service.execute(source)
    ref_stdout, ref_code, _ = reference_run(source)
    check("benchmark-numpy.py N=1e8", got)
    if "backend: TpuArray" not in got["stdout"] or "backend: ndarray" not in ref_stdout:
        raise SmokeFailure(f"benchmark-numpy.py backends: {got['stdout']!r} / {ref_stdout!r}")
    # np.random.rand is unseeded and the shim's generator is jax's, not
    # MT19937 (ops/npdispatch/random.py: the contract is distributional), so
    # run and reference are held to the same law instead of to each other:
    # sum of squares of N uniforms is N/3 with sigma sqrt(4N/45).
    for label, text in (("service", got["stdout"]), ("reference", ref_stdout)):
        n, value = re.search(r"over ([0-9_]+) doubles = ([0-9.]+)", text).groups()
        if int(n) != NUMPY_N:
            raise SmokeFailure(f"benchmark-numpy.py ({label}) ran at N={n}")
        bound = 6 * math.sqrt(4 * NUMPY_N / 45) + RTOL * NUMPY_N / 3
        if abs(float(value) - NUMPY_N / 3) > bound:
            raise SmokeFailure(f"benchmark-numpy.py {label} sum {value} is not ~N/3")
    for line in labelled(got["stdout"], r"GFLOPS|_s="):
        say(f"  smoke reading on {stats['device_kind']}, not a measurement: {line}")

    # pure-JAX user code and the Pallas kernel: TPU payloads, no CPU reference
    for example, marker in (("benchmark-matmul.py", "TFLOPS|MFU"), ("benchmark-attention.py", "TFLOPS")):
        got = service.execute((EXAMPLES / example).read_text())
        check(example, got)
        if not got["stdout"].startswith(f"backend: {PLATFORM}"):
            raise SmokeFailure(f"{example} did not print backend: {PLATFORM}: {got['stdout']!r}")
        for line in labelled(got["stdout"], marker + "|^backend"):
            say(f"  smoke reading on {stats['device_kind']}, not a measurement: {line}")
    flash_check(service)

    # two dependent turns of one session; the workspace file is the state
    want = reference_run(SESSION_TURN_1, SESSION_TURN_2)
    got = service.execute(SESSION_TURN_1, executor_id=SESSION_ID)
    check("session turn 1 (np.save)", got)
    if got["session_seq"] != 1:
        raise SmokeFailure(f"session turn 1 has session_seq {got['session_seq']}")
    got = service.execute(SESSION_TURN_2, executor_id=SESSION_ID)
    check(
        "session turn 2 (np.load + reduce)", got,
        want=(want[0].replace("ndarray", "TpuArray"), want[1], {}),
    )
    if got["session_seq"] != 2:
        raise SmokeFailure(f"session turn 2 has session_seq {got['session_seq']}")
    same_holder(service, stats, "after the session's second turn")
    status, raw = service.call("DELETE", f"/v1/executors/{SESSION_ID}")
    if status != 200:
        raise SmokeFailure(f"DELETE /v1/executors/{SESSION_ID} -> {status}: {raw!r}")

    # the chip holder must come back to the pool, the same process
    check("print(21*2) after the session", service.execute("print(21*2)"), want=reference_run("print(21*2)"))
    got = service.execute(HBM_PROBE)
    check("HBM left by earlier tenants", got)
    say(f"  smoke reading: {got['stdout'].strip()}")
    same_holder(service, stats, "after every request")


def flash_check(service: Service) -> dict:
    """The kernel compiled for the chip against dense attention on the chip,
    and that dense result against the CPU reference of the same source."""
    got = service.execute(FLASH_CHECK)
    check("flash attention vs dense (t=2048)", got)
    ref_stdout, _, _ = reference_run(FLASH_CHECK)
    err = re.search(r"flash_max_abs_err=([0-9.]+)", got["stdout"])
    if err is None or float(err.group(1)) > 0.05:
        raise SmokeFailure(f"flash kernel disagrees with dense attention: {got['stdout']!r}")
    # bf16 inputs and the MXU's default f32 precision: 1e-2, not the shim's bound
    if (diff := compare_text(got["stdout"], ref_stdout, rtol=1e-2, ignore=("flash_",))):
        raise SmokeFailure(f"dense attention on the chip vs the CPU reference: {diff}")
    say(f"  {got['stdout'].strip().replace(chr(10), ' ')} (reference {ref_stdout.strip()})")
    return got


def same_holder(service: Service, stats: dict, when: str) -> None:
    now = service.device_stats()
    holders = chip_holders()
    say(f"chip holder {when}: runner_pid={now['runner_pid']:.0f} holders={holders}")
    if (now["runner_pid"], now["attach_seconds"]) != (stats["runner_pid"], stats["attach_seconds"]):
        raise SmokeFailure(f"a second attach happened {when}: {stats} -> {now}")
    if holders != [int(stats["runner_pid"])]:
        raise SmokeFailure(f"processes with the TPU runtime mapped {when}: {holders}")
    if "jax" in sys.modules:
        raise SmokeFailure("chip_smoke.py itself imported jax")


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def run_one_chip(cache_dir: str) -> dict:
    say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries before)")
    with serving("start1", chips=1) as service:
        stats = service.wait_warm()
        if stats["device_count"] != 1:
            raise SmokeFailure(f"one chip expected, sandbox reports {stats['device_count']}")
        one_chip_requests(service, stats)
    after_first = cache_entries(cache_dir)
    say(f"compile cache: {after_first} entries after the first start")

    # Second start in the same run: the attach right after a close, and the
    # cache found again by a new service and a new runner.
    with serving("start2", chips=1) as service:
        service.wait_warm()
        check("print(21*2) [second start]", service.execute("print(21*2)"), want=reference_run("print(21*2)"))
        got = flash_check(service)
        hits = got["phases"].get("compile_cache_hits", 0)
        misses = got["phases"].get("compile_cache_misses", 0)
        say(f"second start: compile_cache_hits={hits:.0f} misses={misses:.0f}")
        if hits < 1 or hits <= misses:
            raise SmokeFailure("the second start did not find the first start's compiles")
    say(f"compile cache: {cache_entries(cache_dir)} entries after the second start")
    if cache_entries(cache_dir) < after_first or after_first == 0:
        raise SmokeFailure("the compile cache lost entries or never got any")
    return stats


PSUM_VALUES = """
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

devices = jax.devices()
n = len(devices)
mesh = Mesh(np.array(devices), ("chips",))
x = jnp.arange(n * 8, dtype=jnp.float32)
total = jax.jit(shard_map(lambda b: jax.lax.psum(b, "chips"), mesh=mesh,
                          in_specs=P("chips"), out_specs=P()))(x)
print("psum:", " ".join(f"{v:.1f}" for v in np.asarray(total)))
"""

PLAIN_SUMS = """
import numpy as np
x = np.arange(4 * 8, dtype=np.float32)
print("psum:", " ".join(f"{v:.1f}" for v in x.reshape(4, -1).sum(axis=0)))
"""

BATCH_JOB = """
import jax, jax.numpy as jnp
a = jax.random.normal(jax.random.PRNGKey({seed}), (512, 512), jnp.float32)
r = jnp.tanh(a @ a.T / 512.0)
print("device:", sorted(d.id for d in r.devices()))
print(f"job {seed}: sum={{float(jnp.sum(r)):.4f}} trace={{float(jnp.trace(r)):.4f}}")
"""


def run_four_chips() -> dict:
    with serving("four", chips=4) as service:
        stats = service.wait_warm()
        if stats["device_count"] != 4:
            raise SmokeFailure(f"four chips expected, sandbox reports {stats['device_count']}")

        got = service.execute((EXAMPLES / "pmap_allreduce.py").read_text())
        check("pmap_allreduce.py", got)
        say(f"  {got['stdout'].strip()}")
        if got["stdout"].strip() != "chips=4 psum_ok=True":
            raise SmokeFailure(f"pmap_allreduce.py printed {got['stdout']!r}")
        # the same sums computed without the mesh, by plain numpy
        plain = reference_run(PLAIN_SUMS)
        check("psum over the 4-chip mesh vs plain sums", service.execute(PSUM_VALUES), want=plain)

        sources = [BATCH_JOB.format(seed=seed) for seed in range(4)]
        fused: list = [None] * 4

        def submit(i: int) -> None:
            try:
                fused[i] = service.execute(sources[i])
            except BaseException as e:  # noqa: BLE001 — read back below
                fused[i] = e

        threads = [threading.Thread(target=submit, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=700)
        devices = []
        for i, got in enumerate(fused):
            if not isinstance(got, dict):
                raise SmokeFailure(f"fused job {i} failed: {got!r}")
            check(f"fused job {i}", got)
            if got["phases"].get("batch_jobs") != 4.0:
                raise SmokeFailure(f"job {i} did not ride a fused batch of four: {got['phases']}")
            devices.append(labelled(got["stdout"], "^device:")[0])
            say(f"  {got['stdout'].strip().replace(chr(10), ' | ')}")
        if len(set(devices)) != 4:
            raise SmokeFailure(f"four fused jobs on devices {devices}, not four distinct ones")
        for i, source in enumerate(sources):
            serial = service.execute(source)  # alone in its window: serial path
            check(f"serial job {i}", serial)
            if "batch_jobs" in serial["phases"]:
                raise SmokeFailure(f"serial job {i} rode a batch: {serial['phases']}")
            if (diff := compare_text(fused[i]["stdout"], serial["stdout"], ignore=("^device:",))):
                raise SmokeFailure(f"fused job {i} differs from its serial dispatch: {diff}")
        say("four fused jobs on four distinct devices equal their serial dispatch")
        same_holder(service, stats, "after the four-chip phase")
    return stats


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 = only the path across four chips and what it is compared with",
    )
    args = parser.parse_args()
    if not (ROOT / "executor" / "server.cpp").is_file():
        print("chip_smoke.py runs from the root of a checkout of this repo", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT))
    from bee_code_interpreter_fs_tpu.config import jax_cache_dir

    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    t0 = time.perf_counter()
    try:
        build_executor()
        stats = run_one_chip(jax_cache_dir()) if args.chips == 1 else run_four_chips()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(result_line(stats), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
