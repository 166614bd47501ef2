"""Session-durability plane tests at the orchestrator level: hibernate
releases the chip, restore continues the session byte-identically
(session_seq continuous), a fence migrates instead of destroying state,
the restore-in-flight interleave gets the typed refusal, and the kill
switch restores pin-forever semantics byte-for-byte.

The sandbox wire is faked at the same seams the session tests use
(`_post_execute`) plus the two durability seams (`_post_snapshot_op`,
`_capture_workspace`) — everything between them (store, sweep, fence,
session table, capacity accounting) is real.
"""

import asyncio

import pytest
from fakes import FakeBackend

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.code_executor import (
    CodeExecutor,
    ExecutorError,
    SessionRestoringError,
)
from bee_code_interpreter_fs_tpu.services.session_store import SESSION_NS
from bee_code_interpreter_fs_tpu.services.storage import Storage


class FakeSandboxServer:
    def __init__(self, executor: CodeExecutor):
        self.served_by: list[str] = []

        async def fake_post_execute(client, base, payload, timeout, sandbox):
            self.served_by.append(sandbox.id)
            return {
                "stdout": "ok\n",
                "stderr": "",
                "exit_code": 0,
                "files": [],
                "warm": True,
            }

        executor._post_execute = fake_post_execute


class FakeSnapshotPlane:
    """Fakes the runner's snapshot/restore ops and the workspace capture.
    Knobs: `restore_gate` parks restores until set (interleave tests),
    `restore_reply` forces one clean refusal, `restore_error` /
    `snapshot_error` force one wire failure."""

    STATE = {
        "version": 1,
        "env_set": {"SESSION_VAR": "42"},
        "env_del": [],
        "cwd": "",
        "modules": [],
        "packages": [],
        "skipped": [],
    }

    def __init__(self, executor: CodeExecutor):
        self.snapshots = 0
        self.restored: list[dict] = []
        self.restore_gate: asyncio.Event | None = None
        self.restore_reply: dict | None = None
        self.restore_error: Exception | None = None
        self.snapshot_error: Exception | None = None

        async def fake_post_snapshot_op(client, base, op, payload, sandbox):
            if op == "snapshot":
                if self.snapshot_error is not None:
                    err, self.snapshot_error = self.snapshot_error, None
                    raise err
                self.snapshots += 1
                return {"ok": True, "state": dict(self.STATE)}
            if self.restore_gate is not None:
                await self.restore_gate.wait()
            if self.restore_error is not None:
                err, self.restore_error = self.restore_error, None
                raise err
            if self.restore_reply is not None:
                reply, self.restore_reply = self.restore_reply, None
                return reply
            self.restored.append(payload["state"])
            return {"ok": True, "skipped": []}

        async def fake_capture_workspace(sandbox):
            return {}

        executor._post_snapshot_op = fake_post_snapshot_op
        executor._capture_workspace = fake_capture_workspace


def make_executor(backend, tmp_path, **config_kwargs):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        executor_pod_queue_target_length=1,
        **config_kwargs,
    )
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    return executor, FakeSandboxServer(executor), FakeSnapshotPlane(executor)


async def settle(executor):
    for _ in range(3):
        await asyncio.sleep(0)
    tasks = list(executor._dispose_tasks) + list(executor._fill_tasks)
    if tasks:
        await asyncio.gather(*tasks, return_exceptions=True)


def age_session(executor, executor_id, seconds):
    session = executor._sessions[executor_id]
    session.last_used -= seconds
    session.idle_accounted = 0.0


def counter(executor, name, **labels):
    fam = getattr(executor.metrics, name)
    for sample_labels, value in fam.samples():
        if all(sample_labels.get(k) == v for k, v in labels.items()):
            return value
    return 0.0


@pytest.mark.parametrize("sessions", [1, 3, 5])
async def test_hibernate_releases_chip_then_restore_continues_seq(
    tmp_path, sessions
):
    """One session after another on a one-slot backend: each hibernates,
    gives the chip back, restores and closes; the counts add up."""
    backend = FakeBackend(capacity=1)
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        for n in range(1, sessions + 1):
            sid = f"sess-d{n}"
            first = await executor.execute("x", executor_id=sid)
            assert first.session_seq == 1
            assert executor._session_held.get(0) == 1

            # Idle past the hibernate threshold but NOT past the hard idle
            # timeout: the durability leg must fire first.
            age_session(
                executor,
                sid,
                executor.config.session_hibernate_idle_seconds + 1.0,
            )
            assert await executor.sweep_sessions() == 1
            await settle(executor)
            # The chip is back: session_held drained, the session is a
            # record.
            assert executor._session_held.get(0) == 0
            assert sid not in executor._sessions
            assert plane.snapshots == n
            assert executor.session_store.entry_count() == 1
            assert (
                counter(executor, "session_hibernates", outcome="hibernate")
                == n
            )
            snap = executor.statusz()["session_durability"]
            assert snap["enabled"] is True and snap["hibernated"] == 1

            # Next turn restores lazily: interpreter state shipped back,
            # session_seq CONTINUOUS (2, not a reset to 1), restore phase
            # reported.
            second = await executor.execute("x", executor_id=sid)
            assert second.session_seq == 2
            assert second.session_ended is False
            assert plane.restored == [dict(plane.STATE)] * n
            assert "restore" in second.phases
            assert (
                counter(executor, "session_restores", outcome="restored") == n
            )
            # The record stays until close/expiry (it is superseded on the
            # next hibernate via first-write-wins on a newer seq).
            assert await executor.close_session(sid) is True
            await settle(executor)
            assert executor.session_store.entry_count() == 0
    finally:
        await executor.close()


async def test_restore_in_flight_turn_gets_typed_refusal(tmp_path):
    """THE concurrent-turn interleave regression (satellite 2): a second
    turn arriving mid-restore is refused typed-and-retryable, the restore
    finishes unharmed, and the retry rides the restored session."""
    backend = FakeBackend()
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-r")
        age_session(
            executor,
            "sess-r",
            executor.config.session_hibernate_idle_seconds + 1.0,
        )
        assert await executor.sweep_sessions() == 1
        await settle(executor)

        plane.restore_gate = asyncio.Event()
        turn_a = asyncio.ensure_future(
            executor.execute("x", executor_id="sess-r")
        )
        # The restore parks at the gate, so the state below holds until the
        # gate opens; getting there takes real time (the record is loaded and
        # a sandbox acquired first), so wait for the state, not for a count
        # of loop turns: 200 of them ran out under six loaded workers.
        loop = asyncio.get_running_loop()
        deadline = loop.time() + 60.0
        session = None
        while loop.time() < deadline:
            session = executor._sessions.get("sess-r")
            if session is not None and session.restoring:
                break
            await asyncio.sleep(0.005)
        assert session is not None and session.restoring is True

        with pytest.raises(SessionRestoringError) as exc_info:
            await executor.execute("x", executor_id="sess-r")
        assert exc_info.value.retry_after > 0
        # The loser did NOT end the session or disturb the restore.
        assert executor._sessions.get("sess-r") is session
        plane.restore_gate.set()
        result = await turn_a
        assert result.session_seq == 2
        # The retry (post-restore) is an ordinary session turn.
        retry = await executor.execute("x", executor_id="sess-r")
        assert retry.session_seq == 3
    finally:
        await executor.close()


async def test_fence_migrates_parked_session_with_state(tmp_path):
    backend = FakeBackend(distinct_urls=True)
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-m")
        await executor.execute("x", executor_id="sess-m")
        sandbox = executor._sessions["sess-m"].sandbox
        assert await executor.fence_host(sandbox.id, reason="wedged") == "fenced"
        await settle(executor)
        # Migrated, not destroyed: checkpoint admitted with the session's
        # seq, session table entry gone, chip released.
        assert counter(executor, "session_migrations", outcome="saved") == 1
        assert counter(executor, "session_hibernates", outcome="migrate") == 1
        assert executor.session_store.entry_count() == 1
        assert "sess-m" not in executor._sessions
        assert executor._session_held.get(0) == 0

        # Next turn restores on a HEALTHY host with zero state loss:
        # session_seq continues at 3.
        result = await executor.execute("x", executor_id="sess-m")
        assert result.session_seq == 3
        assert plane.restored == [dict(plane.STATE)]
        assert server.served_by[-1] != sandbox.id
    finally:
        await executor.close()


async def test_fence_falls_back_to_force_close_when_snapshot_fails(tmp_path):
    backend = FakeBackend(distinct_urls=True)
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-f")
        plane.snapshot_error = ExecutorError("device wedged mid-snapshot")
        sandbox = executor._sessions["sess-f"].sandbox
        assert await executor.fence_host(sandbox.id, reason="wedged") == "fenced"
        await settle(executor)
        # Pre-durability semantics: force-closed, no record, next turn is
        # an honest fresh session.
        assert counter(executor, "session_migrations", outcome="forced") == 1
        assert executor.session_store.entry_count() == 0
        result = await executor.execute("x", executor_id="sess-f")
        assert result.session_seq == 1
    finally:
        await executor.close()


async def test_clean_refusal_recreates_fresh_with_honest_seq(tmp_path):
    backend = FakeBackend()
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-c")
        age_session(
            executor,
            "sess-c",
            executor.config.session_hibernate_idle_seconds + 1.0,
        )
        await executor.sweep_sessions()
        await settle(executor)
        plane.restore_reply = {"ok": False, "reason": "corrupt_state"}
        # The turn still SUCCEEDS — on a genuinely fresh session whose
        # seq=1 reports the state loss honestly; the bad record is gone.
        result = await executor.execute("x", executor_id="sess-c")
        assert result.session_seq == 1
        assert executor.session_store.entry_count() == 0
        assert counter(executor, "session_restores", outcome="fresh") == 1
    finally:
        await executor.close()


async def test_wire_failure_mid_restore_keeps_record_for_retry(tmp_path):
    backend = FakeBackend()
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-w")
        age_session(
            executor,
            "sess-w",
            executor.config.session_hibernate_idle_seconds + 1.0,
        )
        await executor.sweep_sessions()
        await settle(executor)
        plane.restore_error = ExecutorError("connection dropped mid-restore")
        with pytest.raises(ExecutorError):
            await executor.execute("x", executor_id="sess-w")
        await settle(executor)
        # The record SURVIVES a wire drop (blob intact) — never a
        # half-restored session: the failed sandbox was closed, and the
        # retry restores byte-exact with seq continuity.
        assert executor.session_store.entry_count() == 1
        result = await executor.execute("x", executor_id="sess-w")
        assert result.session_seq == 2
    finally:
        await executor.close()


async def test_kill_switch_restores_pin_forever_semantics(tmp_path):
    backend = FakeBackend()
    executor, server, plane = make_executor(
        backend, tmp_path, session_durability_enabled=False
    )
    try:
        await executor.execute("x", executor_id="sess-k")
        # Idle far past the hibernate threshold, short of the hard
        # timeout: pre-durability behavior is "stay parked".
        age_session(
            executor,
            "sess-k",
            executor.config.session_hibernate_idle_seconds + 1.0,
        )
        assert await executor.sweep_sessions() == 0
        assert executor._session_held.get(0) == 1
        assert plane.snapshots == 0
        assert executor.session_store.entry_count() == 0
        assert executor.statusz()["session_durability"] == {
            "enabled": False,
            "idle_chip_seconds_total": executor.statusz()[
                "session_durability"
            ]["idle_chip_seconds_total"],
        }
        # No store directory was ever created (no-IO posture).
        assert not (
            tmp_path / "storage" / ".session-store"
        ).exists()
        # The session is still live where it was: the next turn continues
        # it with nothing to restore.
        live = await executor.execute("x", executor_id="sess-k")
        assert live.session_seq == 2
        assert "restore" not in live.phases
        assert "sess-k" in executor._sessions
        # A fence force-closes, exactly as before the plane existed.
        sandbox = executor._sessions["sess-k"].sandbox
        await executor.fence_host(sandbox.id, reason="wedged")
        await settle(executor)
        assert executor.session_store.entry_count() == 0
        result = await executor.execute("x", executor_id="sess-k")
        assert result.session_seq == 1
    finally:
        await executor.close()


async def test_idle_chip_seconds_accounting(tmp_path):
    backend = FakeBackend()
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-i", chip_count=4)
        age_session(executor, "sess-i", 10.0)
        # Under the hibernate threshold: the sweep only accounts idle.
        assert await executor.sweep_sessions() == 0
        total = executor.statusz()["session_durability"][
            "idle_chip_seconds_total"
        ]
        # ~10 idle seconds x 4 chips.
        assert 35.0 <= total <= 60.0
        assert counter(executor, "session_idle_chip_seconds") == pytest.approx(
            total, abs=0.01
        )
    finally:
        await executor.close()


async def test_close_session_evicts_hibernated_record(tmp_path):
    backend = FakeBackend()
    executor, server, plane = make_executor(backend, tmp_path)
    try:
        await executor.execute("x", executor_id="sess-x")
        age_session(
            executor,
            "sess-x",
            executor.config.session_hibernate_idle_seconds + 1.0,
        )
        await executor.sweep_sessions()
        await settle(executor)
        assert executor.session_store.entry_count() == 1
        # No LIVE session — but DELETE must still kill the checkpoint, or
        # the id resurrects with stale state on reuse.
        assert await executor.close_session("sess-x") is True
        assert executor.session_store.entry_count() == 0
        assert await executor.close_session("sess-x") is False
        fresh = await executor.execute("x", executor_id="sess-x")
        assert fresh.session_seq == 1
    finally:
        await executor.close()


async def test_hibernated_record_is_replica_coherent(tmp_path):
    """A session hibernated by replica A restores behind replica B: the
    record index rides the shared StateStore, the interp blob rides the
    store path both replicas mount."""
    backend_a, backend_b = FakeBackend(), FakeBackend()
    exec_a, _, plane_a = make_executor(backend_a, tmp_path)
    exec_b, _, plane_b = make_executor(backend_b, tmp_path)
    # Splice B onto A's index (the InMemory default is per-process; a
    # shared SQLite store does this for real deployments).
    exec_b.session_store.state = exec_a.session_store.state
    try:
        await exec_a.execute("x", executor_id="sess-ab")
        age_session(
            exec_a, "sess-ab", exec_a.config.session_hibernate_idle_seconds + 1
        )
        await exec_a.sweep_sessions()
        await settle(exec_a)
        assert exec_a.session_store.entry_count() == 1
        result = await exec_b.execute("x", executor_id="sess-ab")
        assert result.session_seq == 2
        assert plane_b.restored == [dict(plane_b.STATE)]
    finally:
        await exec_a.close()
        await exec_b.close()
