"""Local subprocess sandbox backend.

Spawns the C++ executor server (executor/server.cpp) as a local process with a
fresh workspace directory per sandbox. Serves three roles:

1. The fake-executor test backend the reference lacked (SURVEY.md §4) — full
   e2e coverage of the orchestrator/API stack without Kubernetes.
2. Single-host TPU serving: the sandbox's warm runner attaches the local
   TPU and user code runs on it directly (chip_smoke.py drives this path
   through `python -m bee_code_interpreter_fs_tpu`).

All sandboxes share one JAX persistent compilation cache directory
(config.jax_cache_dir), so XLA compiles survive across sandbox generations
(SURVEY.md §7 hard part #2 — single-use sandboxes must not mean recompiling
every request).
"""

from __future__ import annotations

import asyncio
import logging
import os
import re
import shutil
import sys
import uuid
from pathlib import Path

import httpx

from ...config import REPO_ROOT, Config
from ..limits import sandbox_limit_env
from .base import (
    ResetClient,
    Sandbox,
    SandboxBackend,
    SandboxSpawnError,
    num_hosts_for,
    reset_sandbox_over_http,
)

logger = logging.getLogger(__name__)


def _httpx_client() -> httpx.AsyncClient:
    # Control-plane↔sandbox calls are localhost; 10s covers a loaded machine.
    return httpx.AsyncClient(timeout=httpx.Timeout(10.0))

DEFAULT_BINARY = REPO_ROOT / "executor" / "build" / "executor-server"


def _kill_group(proc: asyncio.subprocess.Process) -> None:
    """SIGKILL the sandbox's whole process group (the server was spawned with
    start_new_session=True, so pgid == its pid). Killing only the server
    would orphan the warm runner and any user-code subprocesses — which keep
    the server's stdout pipe open, making asyncio's Process.wait() (which
    waits for pipe EOF, not just exit) hang until they die on their own."""
    import signal

    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    try:
        proc.kill()
    except ProcessLookupError:
        pass


async def _terminate_sandbox(proc: asyncio.subprocess.Process, grace: float) -> None:
    """SIGTERM first: the server's handler reaps the warm runner's whole
    SESSION (which killpg cannot reach, and which may be wedged in
    GIL-holding TPU init where its own pipe-EOF watchdog can't run) before
    exiting. Escalate to a group SIGKILL if the server doesn't die in time."""
    try:
        proc.terminate()
    except ProcessLookupError:
        pass
    try:
        await asyncio.wait_for(asyncio.shield(proc.wait()), timeout=grace)
    except asyncio.TimeoutError:
        pass
    _kill_group(proc)


def _free_port() -> int:
    """An OS-assigned free TCP port for the group's jax.distributed
    coordinator. Racy in principle, but the window is the group spawn and
    local dev/test is the only user of this path."""
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class LocalSandboxBackend(SandboxBackend):
    def __init__(
        self,
        config: Config | None = None,
        *,
        warm_import_jax: bool | None = None,
    ) -> None:
        self.config = config or Config()
        binary = self.config.executor_binary or str(DEFAULT_BINARY)
        self.binary = Path(binary)
        if not self.binary.is_absolute():
            self.binary = REPO_ROOT / self.binary
        self.root = Path(self.config.local_sandbox_root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.warm_import_jax = (
            self.config.executor_warm_runner
            if warm_import_jax is None
            else warm_import_jax
        )
        self._procs: dict[str, tuple[asyncio.subprocess.Process, str]] = {}
        self._reset_client = ResetClient()
        # libtpu is exclusive-access: only `local_tpu_slots` warm-JAX
        # sandboxes may hold the local TPU at once. Spawns acquire a slot
        # BEFORE triggering the runner's jax import (POST /warmup) and
        # release it only when the sandbox's process group is confirmed
        # dead — so a pool refill can never race the in-flight execution
        # for the chip (the round-1 bench wedge).
        self._tpu_slots = asyncio.Semaphore(max(1, self.config.local_tpu_slots))
        self._build_lock = asyncio.Lock()
        self._build_failed = False  # memo: never re-run a failed auto-build
        self._slot_holders: set[str] = set()  # sandbox/host ids holding a slot
        # Entries already in the shared cache dir when this control plane
        # started were written by parties it never saw (a previous lifetime's
        # tenants, or whoever handed the directory in).
        cache_dir = self.config.jax_compilation_cache_dir
        self._cache_dir_preexisting = bool(
            cache_dir
            and not self.config.compile_cache_per_sandbox
            and os.path.isdir(cache_dir)
            and os.listdir(cache_dir)
        )

    @property
    def compile_cache_dir_scope(self) -> str:
        """Shared-dir mode (the default: one host dir, zero-copy across
        sandboxes — and the fleet-constant path jax's key hashing demands
        for cross-sandbox hits) is writable by every sandbox on this
        control plane; per-sandbox mode gives each its own dir. Harvest only
        vouches for entries written in this control plane's trusted-only
        epoch (before its first tenant execute, see
        CodeExecutor._harvest_compile_cache), so a shared dir that was not
        empty at start is "external": sandboxes still hit it, the fleet
        store never harvests from it. The dir itself is never wiped — it
        may be the caller's JAX_COMPILATION_CACHE_DIR."""
        if self.config.compile_cache_per_sandbox:
            return "private"
        return "external" if self._cache_dir_preexisting else "shared"

    def _tpu_exclusive(self) -> bool:
        """Would a warm-JAX runner grab a real (exclusive-access) TPU?

        JAX_PLATFORMS=cpu (tests, CI's virtual mesh) means jax init is
        concurrency-safe and spawns need no serialization."""
        if not self.warm_import_jax:
            return False
        return not os.environ.get("JAX_PLATFORMS", "").strip().lower().startswith(
            "cpu"
        )

    def pool_capacity(self, chip_count: int) -> int | None:
        """Max warm sandboxes a pool lane should hold on this backend
        (None = unbounded). Every warm-JAX sandbox on this host holds the
        same local TPU regardless of lane, so the cap is the slot count."""
        del chip_count
        return max(1, self.config.local_tpu_slots) if self._tpu_exclusive() else None

    async def _build_binary(self) -> None:
        """Build the executor server on first use if the checkout is fresh.

        `executor/build/` is gitignored, so a clean clone has sources but no
        binary — which would fail every spawn. Only attempted for the
        default in-repo path; a custom `executor_binary` is the operator's
        to provide."""
        if self.binary != DEFAULT_BINARY:
            return
        async with self._build_lock:
            if self.binary.exists() or self._build_failed:
                return
            makedir = self.binary.parent.parent
            logger.info("executor binary missing; building via make -C %s", makedir)
            try:
                proc = await asyncio.create_subprocess_exec(
                    "make",
                    "-C",
                    str(makedir),
                    stdout=asyncio.subprocess.PIPE,
                    stderr=asyncio.subprocess.STDOUT,
                )
            except OSError as e:  # no `make` on PATH → fall to the message
                logger.error("executor auto-build unavailable: %s", e)
                self._build_failed = True
                return
            try:
                out, _ = await asyncio.wait_for(proc.communicate(), timeout=300.0)
            except asyncio.TimeoutError:
                proc.kill()
                await proc.wait()
                logger.error("executor build timed out after 300s; killed")
                self._build_failed = True
                return
            if proc.returncode != 0:
                self._build_failed = True
                logger.error(
                    "executor build failed rc=%s:\n%s",
                    proc.returncode,
                    out.decode("utf-8", "replace")[-1500:],
                )
            elif not self.binary.exists():
                # rc=0 but no binary at the expected path (e.g. the Makefile's
                # output target moved) — memoize, or every spawn re-runs a
                # full no-op make before failing.
                self._build_failed = True
                logger.error(
                    "executor build succeeded but %s does not exist; "
                    "not retrying", self.binary,
                )

    def _stderr_tail(self, host_ids: list[str], limit: int = 1500) -> str:
        """Tail of the sandbox server's stderr log(s) — the only place a
        wedged `import jax` leaves its traceback (round-1's bench failure
        was undiagnosable because this went to DEVNULL)."""
        parts = []
        for host_id in host_ids:
            try:
                data = (self.root / host_id / "server.log").read_bytes()
            except OSError:
                continue
            if data:
                tail = data[-limit:].decode("utf-8", "replace").strip()
                parts.append(f"--- {host_id} stderr tail ---\n{tail}")
        return "\n".join(parts)

    async def spawn(self, chip_count: int = 0) -> Sandbox:
        if not self.binary.exists():
            await self._build_binary()
        if not self.binary.exists():
            raise SandboxSpawnError(
                f"executor binary not found at {self.binary}; run `make -C executor`"
            )
        sandbox_id = self.config.executor_pod_name_prefix + uuid.uuid4().hex[:6]
        num_hosts = num_hosts_for(chip_count, self.config.tpu_chips_per_host)
        if num_hosts == 1:
            port = await self._spawn_host(sandbox_id)
            urls = [f"http://127.0.0.1:{port}"]
            await self._warm_sandbox(sandbox_id, [sandbox_id], urls)
            logger.info("spawned local sandbox %s on port %d", sandbox_id, port)
            return Sandbox(
                id=sandbox_id,
                url=urls[0],
                chip_count=chip_count,
                meta={"dir": str(self.root / sandbox_id), "shares_storage": True},
            )

        # Multi-host slice group: one executor process per "host", all joined
        # into a single jax.distributed cluster via a localhost coordinator.
        # Servers come up instantly (warm-up is deferred to /warmup), then
        # every host's runner starts concurrently — they block in distributed
        # init until the whole group has joined.
        coord_port = _free_port()
        host_ids = [f"{sandbox_id}-h{i}" for i in range(num_hosts)]
        chips_per_host = max(1, self.config.tpu_chips_per_host)
        results = await asyncio.gather(
            *(
                self._spawn_host(
                    host_id,
                    env_extra={
                        "APP_NUM_HOSTS": str(num_hosts),
                        "APP_HOST_ID": str(i),
                        "APP_COORDINATOR_ADDR": f"127.0.0.1:{coord_port}",
                        # Local "hosts" share one machine: partition its chips
                        # so peers don't all grab the whole TPU and wedge each
                        # other out of libtpu's exclusive access (inert when
                        # JAX_PLATFORMS=cpu). Real multi-host TPU slices are
                        # the kubernetes backend's job.
                        "TPU_VISIBLE_CHIPS": ",".join(
                            str(c)
                            for c in range(
                                i * chips_per_host, (i + 1) * chips_per_host
                            )
                        ),
                        "TPU_PROCESS_BOUNDS": f"1,1,{num_hosts}",
                        "TPU_CHIPS_PER_PROCESS_BOUNDS": f"1,1,{chips_per_host}",
                    },
                )
                for i, host_id in enumerate(host_ids)
            ),
            return_exceptions=True,
        )
        failure = next((r for r in results if isinstance(r, BaseException)), None)
        if failure is not None:
            for host_id in host_ids:  # no partial groups
                await self._kill_host(host_id)
            if isinstance(failure, SandboxSpawnError):
                raise failure
            raise SandboxSpawnError(f"group {sandbox_id} spawn failed: {failure!r}")
        ports = list(results)
        urls = [f"http://127.0.0.1:{p}" for p in ports]
        await self._warm_sandbox(sandbox_id, host_ids, urls)
        logger.info(
            "spawned local multi-host sandbox %s (%d hosts, ports %s)",
            sandbox_id,
            num_hosts,
            ports,
        )
        return Sandbox(
            id=sandbox_id,
            url=urls[0],
            chip_count=chip_count,
            host_urls=urls,
            meta={
                "hosts": host_ids,
                "dirs": [str(self.root / h) for h in host_ids],
                "shares_storage": True,
            },
        )

    async def _warm_sandbox(
        self, sandbox_id: str, host_ids: list[str], urls: list[str]
    ) -> None:
        """Drive the sandbox from reachable to warm: acquire a TPU slot if the
        runner will grab the chip, POST /warmup to every host, poll /healthz
        until all report warm. Kills the sandbox (and releases the slot) on
        failure/cancellation, with the server's stderr tail in the error."""
        if not self.config.executor_warm_runner:
            return
        try:
            if self._tpu_exclusive():
                # One slot per sandbox (a local group partitions the same
                # chips), held until _kill_host confirms the process group is
                # dead. Bounded wait: an idle warm sandbox of ANOTHER lane
                # holding the slot must surface as an error the pool can act
                # on (evict + retry), never an unbounded hang.
                try:
                    await asyncio.wait_for(
                        self._tpu_slots.acquire(),
                        timeout=self.config.executor_warm_ready_timeout,
                    )
                except asyncio.TimeoutError:
                    raise SandboxSpawnError(
                        f"sandbox {sandbox_id}: no TPU slot freed within "
                        f"{self.config.executor_warm_ready_timeout:.0f}s "
                        "(held by another warm sandbox)"
                    ) from None
                self._slot_holders.add(sandbox_id)
            await self._await_warm(urls, host_ids)
        except BaseException as e:
            # Tail BEFORE the kill: _kill_host's rmtree deletes server.log,
            # and generic failures (server died mid-warm-up) need the tail
            # just as much as the explicit timeout paths.
            tail = self._stderr_tail(host_ids)
            for host_id in host_ids:
                await self._kill_host(host_id)
            self._release_slot(sandbox_id)
            if isinstance(e, (SandboxSpawnError, asyncio.CancelledError)):
                raise
            raise SandboxSpawnError(
                f"sandbox {sandbox_id} warm-up failed: {e!r}"
                + (f"\n{tail}" if tail else "")
            ) from e

    async def _await_warm(self, urls: list[str], host_ids: list[str]) -> None:
        deadline = (
            asyncio.get_running_loop().time() + self.config.executor_warm_ready_timeout
        )
        # This control plane holds a chip slot for the sandbox: one that
        # warmed on anything else would serve every request on the host CPU,
        # green. (A no-JAX plumbing runner attaches nothing to check.)
        expects_tpu = self.warm_import_jax and self._tpu_exclusive()
        async with _httpx_client() as client:
            for url in urls:
                resp = await client.post(f"{url}/warmup")
                resp.raise_for_status()
            pending = dict(zip(host_ids, urls))
            while pending:
                for host_id, url in list(pending.items()):
                    health = (await client.get(f"{url}/healthz")).json()
                    state = health.get("warm_state")
                    if health.get("warm"):
                        if expects_tpu and health.get("backend") != "tpu":
                            tail = self._stderr_tail([host_id])
                            raise SandboxSpawnError(
                                f"sandbox {host_id} warmed on backend "
                                f"{health.get('backend')!r}, not the TPU this "
                                f"host is expected to hold\n{tail}"
                            )
                        del pending[host_id]
                    elif state == "failed":
                        tail = self._stderr_tail([host_id])
                        raise SandboxSpawnError(
                            f"sandbox {host_id} warm-up failed (jax/TPU init "
                            f"died)\n{tail}"
                        )
                if not pending:
                    return
                if asyncio.get_running_loop().time() > deadline:
                    tail = self._stderr_tail(sorted(pending))
                    raise SandboxSpawnError(
                        f"sandbox hosts {sorted(pending)} not warm within "
                        f"{self.config.executor_warm_ready_timeout:.0f}s\n{tail}"
                    )
                await asyncio.sleep(0.25)

    def _release_slot(self, sandbox_id: str) -> None:
        if sandbox_id in self._slot_holders:
            self._slot_holders.discard(sandbox_id)
            self._tpu_slots.release()

    async def _spawn_host(
        self, host_id: str, env_extra: dict[str, str] | None = None
    ) -> int:
        sandbox_dir = self.root / host_id
        workspace = sandbox_dir / "workspace"
        runtime_packages = sandbox_dir / "runtime-packages"
        # Per-sandbox TMPDIR: tempfile writes from user code must not land in
        # the shared host /tmp (which /reset could never wipe) — they go to a
        # sandbox-private dir that IS wiped at generation turnover.
        scratch_tmp = sandbox_dir / "tmp"
        workspace.mkdir(parents=True)
        runtime_packages.mkdir(parents=True)
        scratch_tmp.mkdir(parents=True)

        # All local sandboxes share one host cache dir by default (zero-copy
        # cross-sandbox XLA cache); per-sandbox mode gives each its own dir
        # under the sandbox root — the pod-local reality the fleet
        # compile-cache store exists for (tests/bench exercise that mode).
        cache_dir = self.config.jax_compilation_cache_dir
        if cache_dir and self.config.compile_cache_per_sandbox:
            cache_dir = str(sandbox_dir / "jax-cache")
        if cache_dir:
            Path(cache_dir).mkdir(parents=True, exist_ok=True)

        env = dict(os.environ)
        env.update(
            {
                "APP_LISTEN_ADDR": "127.0.0.1:0",
                "APP_WORKSPACE": str(workspace),
                "APP_RUNTIME_PACKAGES": str(runtime_packages),
                # This host holds the control plane's storage directory
                # too: the server copies a turn's input files out of it
                # itself (spawn()'s `shares_storage` says so to the
                # control plane).
                "APP_STORAGE_OBJECTS_DIR": str(
                    Path(self.config.file_storage_path).resolve()
                ),
                "APP_WARM_RUNNER": "1" if self.config.executor_warm_runner else "0",
                # Warm-up waits for our POST /warmup — issued only after the
                # per-chip TPU slot is acquired, so concurrent spawns never
                # fight over libtpu's exclusive access.
                "APP_WARM_EAGER": "0",
                "APP_WARM_IMPORT_JAX": "1" if self.warm_import_jax else "0",
                "APP_RUNNER_READY_TIMEOUT": str(
                    self.config.executor_warm_ready_timeout
                ),
                "APP_PARENT_DEATH_EXIT": "1",  # die with the control plane
                "APP_PYTHON": sys.executable,
                # Local sandboxes share the host's RAM — bound user-code
                # allocations (runner.py applies the soft-rlimit window).
                "APP_MAX_USER_MEMORY_BYTES": str(
                    self.config.sandbox_max_user_memory_bytes
                ),
                "APP_MAX_OPEN_FILES": str(self.config.sandbox_max_open_files),
                "APP_DEFAULT_TIMEOUT": str(self.config.default_execution_timeout),
                "TMPDIR": str(scratch_tmp),
                "APP_RESET_EXTRA_WIPE_DIRS": str(scratch_tmp),
            }
        )
        # Resource-governance caps (APP_LIMIT_* + the output cap): the
        # executor re-clamps every request against these, so sandbox-side
        # policy holds even if the control plane stops clamping.
        env.update(sandbox_limit_env(self.config))
        if cache_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
            # The executor's compile-cache endpoints (manifest + entry
            # PUT/GET) serve this dir; the kill switch reaches the sandbox
            # so a disabled fleet cache leaves NO new surface behind.
            env["APP_COMPILE_CACHE"] = (
                "1" if self.config.compile_cache_enabled else "0"
            )
        # sitecustomize (media/json patches + the gated numpy shim) is always
        # on the path — in the sandbox image it lives in site-packages
        # unconditionally; only the dispatch shim inside it is env-gated.
        # The shim rides with the JAX runner, as in the sandbox image
        # (executor/Dockerfile) and the kubernetes backend: a sandbox that
        # attaches the device serves numpy code on it; the no-JAX plumbing
        # mode (warm_import_jax=False) keeps stock numpy. REPO_ROOT (which
        # exposes the npdispatch package, and with it the whole control-plane
        # tree) is added only when the shim is on.
        path_entries = [str(REPO_ROOT / "executor")]
        if self.warm_import_jax:
            env["APP_NUMPY_DISPATCH"] = "1"
            path_entries.append(str(REPO_ROOT))
        env["PYTHONPATH"] = os.pathsep.join(
            path_entries + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        if env_extra:
            env.update(env_extra)

        # Server stderr (including the warm runner's `import jax` traceback —
        # the one clue when TPU init wedges) goes to a per-sandbox log file;
        # its tail is included in every SandboxSpawnError.
        log_file = open(sandbox_dir / "server.log", "wb")
        try:
            proc = await asyncio.create_subprocess_exec(
                str(self.binary),
                env=env,
                stdout=asyncio.subprocess.PIPE,
                stderr=log_file,
                start_new_session=True,
            )
        finally:
            log_file.close()
        # Register BEFORE waiting for readiness: a close() racing this spawn
        # (service shutdown mid-prefill) must be able to kill the process.
        self._procs[host_id] = (proc, str(sandbox_dir))

        async def abort_spawn(reason: str):
            tail = self._stderr_tail([host_id])
            await self._kill_host(host_id)
            raise SandboxSpawnError(
                f"sandbox {host_id} {reason}" + (f"\n{tail}" if tail else "")
            )

        try:
            line = await asyncio.wait_for(
                proc.stdout.readline(), timeout=self.config.executor_pod_ready_timeout
            )
        except asyncio.TimeoutError:
            await abort_spawn("did not become ready")
        except asyncio.CancelledError:
            await self._kill_host(host_id)
            raise
        match = re.search(rb"port=(\d+)", line)
        if not match:
            await abort_spawn(f"spoke garbage at startup: {line!r}")
        return int(match.group(1))

    async def _kill_host(self, host_id: str) -> None:
        entry = self._procs.pop(host_id, None)
        if entry is None:
            self._release_slot(host_id)
            return
        proc, sandbox_dir = entry
        await _terminate_sandbox(proc, grace=2.0)
        try:
            # wait() resolves only after the server's pipes fully close; the
            # runner's server-watchdog makes that prompt, but never let a
            # straggler (e.g. a user-code subprocess holding the pipe) hang
            # service shutdown.
            await asyncio.wait_for(proc.wait(), timeout=10.0)
        except asyncio.TimeoutError:
            logger.warning("sandbox %s did not reap within 10s; abandoning", host_id)
        # Only now — with the process group dead and its libtpu handle gone —
        # may the next warm spawn take the chip.
        self._release_slot(host_id)
        await asyncio.to_thread(shutil.rmtree, sandbox_dir, True)

    async def reset(self, sandbox: Sandbox) -> Sandbox | None:
        """Generation turnover without losing the TPU lease: POST /reset to
        every host (server scrubs the warm runner and wipes workspace +
        runtime-packages in place). All hosts must succeed; any refusal
        (runner cold / mid-rewarm after a timeout kill / wipe failure) makes
        the whole sandbox non-reusable and the caller disposes it. The TPU
        slot stays held by the sandbox across generations — it is released
        only by _kill_host when the process actually dies."""
        if not self.config.executor_reuse_sandboxes:
            return None
        host_ids = sandbox.meta.get("hosts", [sandbox.id])
        for host_id in host_ids:
            entry = self._procs.get(host_id)
            if entry is None or entry[0].returncode is not None:
                return None  # process gone or already dying
        return await reset_sandbox_over_http(
            sandbox, self._reset_client, timeout=10.0
        )

    async def delete(self, sandbox: Sandbox) -> None:
        # Concurrent per-host teardown: the TERM grace + reap timeout would
        # otherwise stack serially across a slice group's hosts.
        await asyncio.gather(
            *(
                self._kill_host(host_id)
                for host_id in sandbox.meta.get("hosts", [sandbox.id])
            )
        )
        # A slice group's TPU slot is keyed by the group id, not a host id.
        self._release_slot(sandbox.id)
        logger.info("deleted local sandbox %s", sandbox.id)

    async def close(self) -> None:
        await self._reset_client.aclose()
        await asyncio.gather(
            *(self._kill_host(host_id) for host_id in list(self._procs))
        )
