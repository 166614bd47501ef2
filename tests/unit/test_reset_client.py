"""The backend's kept HTTP client for POST /reset.

A turnover used to build an `httpx.AsyncClient` (its TLS context: 26 ms of
synchronous work on the event loop), POST once and throw it away, on the chip
holder's cycle. Now each backend keeps one, with its keep-alive connections,
from its first turnover to its `close()`. These tests pin the results, not
timings, against both real backends with in-process stand-ins for a sandbox's
hosts: which client and which connection a turnover used, what a failed
`/reset` still means (`None`, the caller disposes), and that the chaos
wrapper's seeded draws never see `/reset`.
"""

import asyncio
import json
import random
from pathlib import Path

import pytest

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.base import Sandbox
from bee_code_interpreter_fs_tpu.services.backends.faults import (
    EXEC_DROP,
    FaultInjectingBackend,
    FaultSpec,
)
from bee_code_interpreter_fs_tpu.services.backends.kubernetes import (
    KubernetesSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.backends.local import (
    LocalSandboxBackend,
)
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.storage import Storage

OK = json.dumps({"ok": True, "trace": None}).encode()


class FakeHost:
    """One sandbox host as far as /reset goes: an HTTP/1.1 keep-alive loop
    like the C++ server's, counting connections and requests. With
    `hangs_up`, it closes the connection after each reply WITHOUT announcing
    it (`Connection: close`), so the client pools a connection that is dead
    by the time it is wanted again."""

    def __init__(self, *, status: int = 200, body: bytes = OK, hangs_up=False):
        self.status = status
        self.body = body
        self.hangs_up = hangs_up
        self.connections = 0
        self.requests: list[str] = []
        self._writers: set[asyncio.StreamWriter] = set()

    async def start(self) -> "FakeHost":
        self._server = await asyncio.start_server(self._serve, "127.0.0.1", 0)
        port = self._server.sockets[0].getsockname()[1]
        self.url = f"http://127.0.0.1:{port}"
        return self

    async def _serve(self, reader, writer) -> None:
        self.connections += 1
        self._writers.add(writer)
        try:
            while True:
                head = (await reader.readuntil(b"\r\n\r\n")).decode()
                lines = head.split("\r\n")
                length = next(
                    (
                        int(line.split(":", 1)[1])
                        for line in lines
                        if line.lower().startswith("content-length:")
                    ),
                    0,
                )
                await reader.readexactly(length)
                self.requests.append(lines[0])
                writer.write(
                    b"HTTP/1.1 %d X\r\nContent-Type: application/json\r\n"
                    b"Content-Length: %d\r\n\r\n%s"
                    % (self.status, len(self.body), self.body)
                )
                await writer.drain()
                if self.hangs_up:
                    return
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            self._writers.discard(writer)
            writer.close()

    async def stop(self) -> None:
        self._server.close()
        for writer in list(self._writers):
            writer.close()
        await self._server.wait_closed()


async def dead_url() -> str:
    """The URL of a host that was there and is gone: nothing listens."""
    host = await FakeHost().start()
    await host.stop()
    return host.url


class RecordingKubectl:
    def __init__(self) -> None:
        self.deleted: list[str] = []

    async def delete(self, kind: str, name: str, **_) -> None:
        self.deleted.append(name)


class Harness:
    """A real backend of either kind, with sandboxes registered the way its
    own `spawn` would leave them but with `FakeHost`s for hosts."""

    def __init__(self, kind: str, tmp_path: Path) -> None:
        self.kind = kind
        self.tmp_path = tmp_path
        self.serial = 0
        self.backend = self.new_backend()

    def new_backend(self):
        if self.kind == "local":
            return LocalSandboxBackend(
                Config(local_sandbox_root=str(self.tmp_path / "sandboxes")),
                warm_import_jax=False,
            )
        return KubernetesSandboxBackend(Config(), kubectl=RecordingKubectl())

    async def adopt(self, urls: list[str], backend=None) -> Sandbox:
        backend = backend or self.backend
        self.serial += 1
        sandbox_id = f"sb-{self.serial}"
        sandbox = Sandbox(id=sandbox_id, url=urls[0], host_urls=list(urls))
        if self.kind == "local":
            # A live process per host is the local backend's precheck.
            host_ids = [f"{sandbox_id}-h{i}" for i in range(len(urls))]
            for host_id in host_ids:
                proc = await asyncio.create_subprocess_exec(
                    "sleep", "600", start_new_session=True
                )
                backend._procs[host_id] = (proc, str(self.tmp_path / host_id))
            sandbox.meta["hosts"] = host_ids
        else:
            backend._live[sandbox_id] = sandbox
        return sandbox

    def disposed(self, sandbox: Sandbox, backend=None) -> bool:
        backend = backend or self.backend
        if self.kind == "local":
            return not set(sandbox.meta["hosts"]) & set(backend._procs)
        return (
            sandbox.id not in backend._live
            and sandbox.id in backend.kubectl.deleted
        )


@pytest.fixture(params=["local", "kubernetes"])
async def harness(request, tmp_path):
    harness = Harness(request.param, tmp_path)
    hosts: list[FakeHost] = []

    async def host(**kwargs) -> FakeHost:
        hosts.append(await FakeHost(**kwargs).start())
        return hosts[-1]

    harness.host = host
    try:
        yield harness
    finally:
        await harness.backend.close()
        for fake in hosts:
            await fake.stop()


def make_executor(backend, tmp_path) -> CodeExecutor:
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        executor_pod_queue_target_length=1,
    )
    return CodeExecutor(backend, Storage(config.file_storage_path), config)


def turnover_outcomes(executor: CodeExecutor) -> list[tuple[str, list[str]]]:
    """(`outcome`, names of the trace's other spans) of every `pool.turnover`
    in the tracer's ring, oldest first."""
    found = []
    for line in executor.tracer.ring.export_jsonl().splitlines():
        root = json.loads(line)
        if root["name"] != "pool.turnover":
            continue
        spans = executor.tracer.ring.trace(root["trace_id"])
        found.append(
            (
                root["attributes"]["outcome"],
                sorted(s["name"] for s in spans if s["name"] != "pool.turnover"),
            )
        )
    return found


async def test_two_turnovers_use_one_client_and_one_connection(harness):
    host = await harness.host()
    sandbox = await harness.adopt([host.url])
    kept = harness.backend._reset_client
    assert kept._client is None  # nothing is built before the first turnover

    assert await harness.backend.reset(sandbox) is sandbox
    first = kept.get()
    assert await harness.backend.reset(sandbox) is sandbox

    assert kept.get() is first and not first.is_closed
    assert sandbox.meta["generation"] == 2
    assert host.requests == ["POST /reset HTTP/1.1"] * 2
    assert host.connections == 1  # the second rode the first's connection
    assert sandbox.meta["reset_client_s"] >= 0.0


async def test_a_host_that_closed_the_idle_connection_still_recycles(harness):
    host = await harness.host(hangs_up=True)
    sandbox = await harness.adopt([host.url])
    for generation in (1, 2, 3):
        assert await harness.backend.reset(sandbox) is sandbox
        assert sandbox.meta["generation"] == generation
        # the hang-up reaches the pooled connection before the next turnover
        await asyncio.sleep(0.05)
    assert host.connections == 3
    assert len(host.requests) == 3


@pytest.mark.parametrize(
    "refusal",
    [
        pytest.param({"status": 500}, id="status-500"),
        pytest.param({"body": b'{"ok": false}'}, id="ok-false"),
        pytest.param({"body": b"not json"}, id="not-json"),
        pytest.param(None, id="dead-host"),
    ],
)
async def test_a_failed_reset_is_none_and_a_dispose(harness, tmp_path, refusal):
    """What a failed `/reset` meant before the client was kept: `None` from
    the backend, `disposed` from the turnover, the sandbox deleted. And the
    kept client is none the worse: the next sandbox recycles over it."""
    if refusal is None:
        url = await dead_url()
    else:
        url = (await harness.host(**refusal)).url
    executor = make_executor(harness.backend, tmp_path)
    try:
        refused = await harness.adopt([url])
        assert await harness.backend.reset(refused) is None
        assert "generation" not in refused.meta

        await executor._turnover(refused, 0, True)
        assert harness.disposed(refused)

        healthy = await harness.adopt([(await harness.host()).url])
        await executor._turnover(healthy, 0, True)
        assert not harness.disposed(healthy)
        assert list(executor._pool(0)) == [healthy]

        outcomes = turnover_outcomes(executor)
        assert [outcome for outcome, _ in outcomes] == ["disposed", "recycled"]
        for _, children in outcomes:
            assert {"sandbox.reset", "sandbox.reset_client"} <= set(children)
    finally:
        await executor.close()


async def test_a_two_host_sandbox_resets_both_hosts_over_the_one_client(harness):
    hosts = [await harness.host(), await harness.host()]
    sandbox = await harness.adopt([h.url for h in hosts])
    for _ in range(2):
        assert await harness.backend.reset(sandbox) is sandbox
    client = harness.backend._reset_client.get()
    for host in hosts:
        assert host.requests == ["POST /reset HTTP/1.1"] * 2
        assert host.connections == 1
    assert sandbox.meta["reset_trace"] == [None, None]  # one block per host

    # every host must answer: one of the two gone is no recycle
    lame = await harness.adopt([hosts[0].url, await dead_url()])
    assert await harness.backend.reset(lame) is None
    assert harness.backend._reset_client.get() is client


async def test_close_closes_the_client_and_a_new_backend_builds_its_own(harness):
    host = await harness.host()
    sandbox = await harness.adopt([host.url])
    assert await harness.backend.reset(sandbox) is sandbox
    client = harness.backend._reset_client.get()

    await harness.backend.close()
    assert client.is_closed

    successor = harness.new_backend()
    try:
        await successor.close()  # a backend that never reset has built none
        assert successor._reset_client._client is None

        adopted = await harness.adopt([host.url], backend=successor)
        assert await successor.reset(adopted) is adopted
        own = successor._reset_client.get()
        assert own is not client and not own.is_closed
        assert host.connections == 2
    finally:
        await successor.close()
    assert own.is_closed


async def test_the_chaos_wrapper_never_draws_for_reset(harness):
    """`exec_drop` rides the executor's client (`http_transport`) and draws
    from its seeded stream for every request it sees. `/reset` goes over the
    backend's own plain client: with every request on the executor's wire
    dropped, a recycle still succeeds and the stream has not moved."""
    host = await harness.host()
    chaos = FaultInjectingBackend(
        harness.backend, FaultSpec(seed=7, exec_drop=1.0)
    )
    assert chaos.http_transport() is not None
    before = chaos._rngs[EXEC_DROP].getstate()
    sandbox = await harness.adopt([host.url])
    for _ in range(3):
        assert await chaos.reset(sandbox) is sandbox
    assert chaos._rngs[EXEC_DROP].getstate() == before
    assert before == random.Random(f"7:{EXEC_DROP}").getstate()
    assert host.connections == 1
