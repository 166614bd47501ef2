"""Async adapter over the ``kubectl`` CLI.

Same architectural choice as the reference (services/kubectl.py:25-28): the
CLI rather than the kubernetes Python client, because the CLI gives us
battle-tested auth/exec/wait behavior and composes with asyncio via
subprocesses. The reference exposed every subcommand through ``__getattr__``
magic with typing overloads (kubectl.py:99-178); here the surface is explicit
— the orchestrator uses exactly five verbs, and explicit methods are greppable
and typo-safe. kwargs become ``--key=value`` flags; dict stdin is sent as
JSON (kubectl.py:84-91); non-zero exit raises KubectlError with stderr
(kubectl.py:93-96).
"""

from __future__ import annotations

import asyncio
import json
import logging
import subprocess
from concurrent.futures import ThreadPoolExecutor
from typing import Any

logger = logging.getLogger(__name__)


class KubectlError(RuntimeError):
    def __init__(self, argv: list[str], returncode: int, stderr: str) -> None:
        super().__init__(
            f"kubectl {' '.join(argv)} failed with exit code {returncode}: {stderr.strip()}"
        )
        self.argv = argv
        self.returncode = returncode
        self.stderr = stderr


def _flags(kwargs: dict[str, Any]) -> list[str]:
    out = []
    for key, value in kwargs.items():
        flag = "--" + key.replace("_", "-")
        if value is True:
            out.append(flag)
        elif value is False:
            out.append(f"{flag}=false")
        elif value is not None:
            out.append(f"{flag}={value}")
    return out


class Kubectl:
    """Thin async kubectl runner; ctor kwargs (e.g. namespace) apply to every
    call, mirroring the reference's default-kwargs ctor (kubectl.py:40-46)."""

    def __init__(self, binary: str = "kubectl", **defaults: Any) -> None:
        self.binary = binary
        self.defaults = defaults
        # One blocked thread per kubectl in flight (a `wait` can sit for the
        # whole pod-ready budget), kept off the loop's shared default pool.
        self._threads = ThreadPoolExecutor(
            max_workers=64, thread_name_prefix="kubectl"
        )

    async def _run(
        self,
        *argv: str,
        stdin: bytes | str | dict | list | None = None,
        **kwargs: Any,
    ) -> str:
        full = [*argv, *_flags({**self.defaults, **kwargs})]
        if isinstance(stdin, (dict, list)):
            stdin = json.dumps(stdin)
        if isinstance(stdin, str):
            stdin = stdin.encode()
        # Popen plus a worker thread, not asyncio's subprocess transport: a
        # task cancelled while that transport is still connecting its pipes
        # waits for an exit notification CPython 3.12 never delivers, so the
        # fire-and-tracked deletes of a failed group spawn hung the loop's
        # teardown for good. Here a cancelled caller returns at once. A
        # create or delete still runs to its end in the thread (a pod half
        # created or never deleted is the leak); any other verb has nothing
        # to finish and is killed.
        proc = subprocess.Popen(
            [self.binary, *full],
            stdin=subprocess.PIPE if stdin is not None else None,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )
        try:
            stdout, stderr = await asyncio.get_running_loop().run_in_executor(
                self._threads, proc.communicate, stdin
            )
        except asyncio.CancelledError:
            if argv[0] not in ("create", "delete"):
                proc.kill()
            raise
        if proc.returncode != 0:
            raise KubectlError(full, proc.returncode, stderr.decode())
        return stdout.decode()

    async def _run_json(self, *argv: str, **kwargs: Any) -> Any:
        out = await self._run(*argv, output="json", **kwargs)
        return json.loads(out)

    # ------------------------------------------------------------- verbs

    async def get(self, kind: str, name: str | None = None, **kwargs: Any) -> Any:
        argv = ["get", kind] + ([name] if name else [])
        return await self._run_json(*argv, **kwargs)

    async def create(self, manifest: dict, **kwargs: Any) -> Any:
        return await self._run_json("create", "-f", "-", stdin=manifest, **kwargs)

    async def wait(self, kind: str, name: str, **kwargs: Any) -> str:
        return await self._run("wait", f"{kind}/{name}", **kwargs)

    async def delete(self, kind: str, name: str, **kwargs: Any) -> str:
        return await self._run(
            "delete", kind, name, ignore_not_found=True, **kwargs
        )

    async def logs(self, name: str, **kwargs: Any) -> str:
        return await self._run("logs", name, **kwargs)
