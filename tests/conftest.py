"""Test harness config.

- Forces JAX onto a virtual 8-device CPU mesh so all sharding/collective logic
  is exercised without TPU hardware (the driver separately dry-runs the
  multi-chip path via __graft_entry__.dryrun_multichip).
- Provides a minimal async-test runner (pytest-asyncio is not available in
  this environment): any ``async def test_*`` is run via asyncio.run().
"""

import asyncio
import inspect
import os
import sys
from pathlib import Path

# Must happen before anything imports jax. Force (not default) CPU: the
# machine may hold a TPU, but tests need the virtual 8-device CPU mesh — and
# sandbox subprocesses spawned by e2e tests inherit this stated platform.
os.environ["JAX_PLATFORMS"] = "cpu"
# Sandboxes inherit this process's env: keep the executor's cooperative-
# cancellation grace short so forced-kill timeout tests don't idle for the
# 20 s production default.
os.environ.setdefault("APP_RUNNER_INTERRUPT_GRACE_S", "2")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests via asyncio.run, driving async-generator
    fixtures (which plugin-less pytest passes through unresolved) in the same
    event loop as the test."""
    fn = pyfuncitem.obj
    if not inspect.iscoroutinefunction(fn):
        return None

    async def run():
        import contextlib

        kwargs = {}
        cleanups = []
        for name in pyfuncitem._fixtureinfo.argnames:
            value = pyfuncitem.funcargs[name]
            if inspect.isasyncgen(value):
                kwargs[name] = await value.__anext__()
                cleanups.append(value)
            elif inspect.iscoroutine(value):
                kwargs[name] = await value
            else:
                kwargs[name] = value
        try:
            await fn(**kwargs)
        finally:
            for gen in reversed(cleanups):
                with contextlib.suppress(StopAsyncIteration):
                    await gen.__anext__()

    asyncio.run(run())
    return True


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Drop compiled-program state at every module boundary.

    XLA's CPU backend segfaults INSIDE backend_compile after the suite
    accumulates several hundred live compiled programs (observed
    deterministically at tests/unit scale in round 5, same class as the
    round-4 note in test_serving.py: fine standalone, crashes at suite
    position — an upstream compiler fragility, not a model bug). Modules
    share almost no compiled programs (each has its own tiny-config
    fixtures), so clearing between modules costs little and keeps the
    accumulation bounded."""
    yield
    jax.clear_caches()


@pytest.fixture
def tmp_storage(tmp_path):
    from bee_code_interpreter_fs_tpu.services.storage import Storage

    return Storage(tmp_path / "storage")


def pytest_sessionfinish(session, exitstatus):
    """CI post-mortem for seeded chaos legs: when CHAOS_TRACE_EXPORT names a
    path and the run FAILED, dump the tracing flight recorder (every span
    any tracer exported this process, bounded ring) as JSONL so the workflow
    can upload it as an artifact — a red seed is then diagnosable without
    re-running locally."""
    path = os.environ.get("CHAOS_TRACE_EXPORT")
    if not path or exitstatus == 0:
        return
    try:
        from bee_code_interpreter_fs_tpu.utils.tracing import GLOBAL_RING

        Path(path).write_text(GLOBAL_RING.export_jsonl())
        print(f"\n[chaos] exported {len(GLOBAL_RING)} trace spans to {path}")
    except Exception as error:  # noqa: BLE001 — diagnostics must not mask the failure
        print(f"\n[chaos] trace export failed: {error}")
