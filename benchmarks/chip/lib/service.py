"""The served process as a child of the benchmark: build, start, wait for the
one warm sandbox, talk HTTP to it, stop it and see that nothing is left.

Copied from chip_smoke.py (`Service`, `build_executor`, `processes_of`) and
cut to what a benchmark run needs. This module never imports jax: the chip
belongs to the sandbox's warm runner.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]  # the checkout
WARM_TIMEOUT_S = 600.0
T0 = time.perf_counter()  # log lines count from the import of this module


class HarnessError(Exception):
    """The run cannot be measured: no result line, exit code 1."""


def log(msg: str) -> None:
    print(f"[chipbench {time.perf_counter() - T0:8.3f}] {msg}", file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def build_executor() -> float:
    """`make` the sandbox's server where it is missing or older than its
    sources (make's own rule); the seconds it took."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        ["make", "-C", str(ROOT / "executor")], capture_output=True, text=True
    )
    if proc.returncode != 0:
        raise HarnessError(f"executor build failed:\n{proc.stdout}\n{proc.stderr}")
    return time.perf_counter() - t0


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


class Client:
    """One keep-alive HTTP connection: one per client thread."""

    def __init__(self, address: str) -> None:
        self.host, port = address.rsplit(":", 1)
        self.port = int(port)
        self.conn: http.client.HTTPConnection | None = None

    def call(self, method: str, path: str, body=None, timeout: float = 660.0):
        """(status, bytes). One reconnect when the kept connection had been
        closed under us before the request went out."""
        if body is None or isinstance(body, bytes):
            data, ctype = body, "application/octet-stream"
        else:
            data, ctype = json.dumps(body).encode(), "application/json"
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, self.port, timeout=timeout)
            try:
                self.conn.request(method, path, body=data, headers={"content-type": ctype})
            except (ConnectionError, http.client.HTTPException, OSError):
                self.close()
                if attempt:
                    raise
                continue
            try:
                resp = self.conn.getresponse()
                return resp.status, resp.read()
            except (ConnectionError, http.client.HTTPException, OSError):
                self.close()
                raise
        raise AssertionError("unreachable")

    def json(self, method: str, path: str, body=None, timeout: float = 30.0):
        status, raw = self.call(method, path, body, timeout)
        if status != 200:
            raise HarnessError(f"{method} {path} -> {status}: {raw[:400]!r}")
        return json.loads(raw)

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Service:
    """`python -m bee_code_interpreter_fs_tpu`, local backend, as shipped:
    only addresses and paths are set, then what the configuration's
    `service_env` states, then `extra` (the rehearsal's stated CPU)."""

    def __init__(self, workdir: Path, service_env: dict, extra: dict) -> None:
        self.dir = workdir
        self.dir.mkdir(parents=True)
        self.address = f"127.0.0.1:{free_port()}"
        self.log_path = self.dir / "service.log"
        env = dict(os.environ)
        env.update(
            APP_EXECUTOR_BACKEND="local",
            APP_HTTP_LISTEN_ADDR=self.address,
            APP_GRPC_LISTEN_ADDR=f"127.0.0.1:{free_port()}",
            APP_FILE_STORAGE_PATH=str(self.dir / "storage"),
            APP_LOCAL_SANDBOX_ROOT=str(self.dir / "sandboxes"),
        )
        env.update({k: str(v) for k, v in service_env.items()})
        env.update(extra)
        self.started = time.perf_counter()
        with open(self.log_path, "wb") as out:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "bee_code_interpreter_fs_tpu"],
                cwd=ROOT, env=env, stdout=out, stderr=subprocess.STDOUT,
            )
        self.admin = Client(self.address)

    def client(self) -> Client:
        return Client(self.address)

    def lane(self) -> dict:
        return self.admin.json("GET", "/statusz")["lanes"].get("0", {})

    def wait_warm(self) -> float:
        """Until the pool counts its one warm sandbox. /statusz lists the
        sandbox's host only after the device-health daemon's next tick
        (device_probe_interval, 15 s as shipped), so that row is not waited
        for here; `sandbox_stats` reads it after the window."""
        deadline = time.perf_counter() + WARM_TIMEOUT_S
        while True:
            if self.proc.poll() is not None:
                raise HarnessError(f"service exited rc={self.proc.returncode}")
            if time.perf_counter() > deadline:
                raise HarnessError(f"no warm sandbox within {WARM_TIMEOUT_S:.0f}s")
            try:
                lane = self.lane()
            except (OSError, ValueError, HarnessError, http.client.HTTPException):
                self.admin.close()
                time.sleep(0.1)
                continue
            if lane.get("breaker", "closed") != "closed":
                raise HarnessError(f"spawn circuit {lane['breaker']}: sandboxes fail to warm")
            # in_use counts too: the compile-cache pre-warm may hold the
            # (warm) sandbox for a moment right after the fill.
            if lane.get("pooled", 0) + lane.get("in_use", 0) >= 1:
                return time.perf_counter() - self.started
            time.sleep(0.1)

    def sandbox_stats(self) -> list[dict]:
        """/device-stats of every sandbox the device-health daemon lists; its
        first tick may be up to device_probe_interval after the start."""
        deadline = time.perf_counter() + 30.0
        while not (hosts := self.admin.json("GET", "/statusz")["device_health"]["hosts"]):
            if time.perf_counter() > deadline:
                raise HarnessError("/statusz lists no sandbox host 30 s after the window")
            time.sleep(0.5)
        found = []
        for row in hosts:
            host = row["host"].removeprefix("http://")
            found.append(Client(host).json("GET", "/device-stats"))
        return found

    def log_tails(self, lines: int = 25) -> str:
        """The last lines of the service's log and of up to three sandboxes',
        each cut to 300 characters."""
        parts = []
        for path in [self.log_path, *sorted(self.dir.glob("sandboxes/*/server.log"))[-3:]]:
            try:
                text = path.read_bytes()[-20000:].decode("utf-8", "replace")
            except OSError:
                continue
            tail = "\n".join(line[:300] for line in text.splitlines()[-lines:])
            parts.append(f"--- tail of {path} ---\n{tail}")
        return "\n".join(parts)

    def stop(self) -> float:
        """SIGTERM, then every process of this service must be gone: a
        runner left behind would still hold the chip."""
        t0 = time.perf_counter()
        self.admin.close()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.kill()
                raise HarnessError("service ignored SIGTERM for 90s") from None
        deadline = time.perf_counter() + 15.0
        while (left := processes_of(self.dir)) and time.perf_counter() < deadline:
            time.sleep(0.1)
        if left:
            self.kill()
            raise HarnessError(f"processes outlived the service: {left}")
        return time.perf_counter() - t0

    def kill(self) -> None:
        """Failure path: leave nothing running."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        for pid in processes_of(self.dir):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
