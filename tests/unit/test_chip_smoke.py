"""The pieces chip_smoke.py's verdict rests on that need no chip: the result
line's shape, the comparison with the plain reference, the one rule for the
compile-cache directory, and the local backend refusing a sandbox that
warmed on the CPU when it believes a chip is there."""

import json
import sys
from pathlib import Path

import pytest
from aiohttp import web
from aiohttp.test_utils import TestServer

import chip_smoke
from bee_code_interpreter_fs_tpu.config import REPO_ROOT, Config, jax_cache_dir
from bee_code_interpreter_fs_tpu.services.backends.base import SandboxSpawnError
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend


def test_result_line_has_exactly_the_three_device_keys():
    line = chip_smoke.result_line(
        {"backend": "tpu", "device_kind": "TPU v5 lite", "device_count": 1, "runner_pid": 7}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_chip_smoke_never_imports_jax():
    """A chip belongs to one process; the smoke's own must stay off it."""
    import subprocess

    probe = "import sys, chip_smoke; sys.exit('jax' in sys.modules)"
    assert subprocess.run([sys.executable, "-c", probe], cwd=REPO_ROOT).returncode == 0


@pytest.mark.parametrize(
    "got,want,kwargs,same",
    [
        ("42\n", "42\n", {}, True),
        ("sum=33333334.0 ok\n", "sum=33333333.9 ok\n", {}, True),  # within 1e-5
        ("sum=33334.0\n", "sum=33333.0\n", {}, False),
        ("wrote hello.txt\n", "wrote hullo.txt\n", {}, False),
        ("a\nb\n", "a\n", {}, False),
        ("t_s=0.5\nx=1\n", "t_s=9.9\nx=1\n", {"ignore": (r"_s=",)}, True),
        ("x=1.004\n", "x=1.0\n", {"rtol": 1e-2}, True),
    ],
)
def test_compare_text(got, want, kwargs, same):
    assert (chip_smoke.compare_text(got, want, **kwargs) is None) == same


def test_reference_run_reports_stdout_exit_code_and_changed_files(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "WORK", tmp_path)
    stdout, code, changed = chip_smoke.reference_run(
        "open('new.txt', 'w').write('x'); print(open('old.txt').read()); raise SystemExit(3)",
        files={"old.txt": b"kept"},
    )
    assert (stdout, code, sorted(changed)) == ("kept\n", 3, ["new.txt"])


def test_cache_dir_rule():
    """The one function that decides where the compile cache goes: the
    caller's JAX_COMPILATION_CACHE_DIR and no other path when it is set,
    else one fixed path inside the checkout. (That the backend never wipes
    it: tests/unit/test_compile_cache.py, the trusted-epoch test.)"""
    assert jax_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/handed/in"}) == "/handed/in"
    assert jax_cache_dir({}) == str(REPO_ROOT / ".jax_cache")
    assert jax_cache_dir({}) == jax_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""})
    assert Path(jax_cache_dir({})).parent == Path(chip_smoke.ROOT)
    # the control plane follows it, and every sandbox gets config's value
    given = Config.from_env({"JAX_COMPILATION_CACHE_DIR": "/handed/in"})
    assert given.jax_compilation_cache_dir == "/handed/in"
    assert Config.from_env({}).jax_compilation_cache_dir == str(REPO_ROOT / ".jax_cache")
    # still a deployment setting: "" turns the cache off
    off = Config.from_env({"APP_JAX_COMPILATION_CACHE_DIR": ""})
    assert off.jax_compilation_cache_dir == ""


@pytest.mark.parametrize("reported,admitted", [("cpu", False), ("tpu", True)])
async def test_await_warm_refuses_a_sandbox_that_warmed_off_the_chip(
    tmp_path, monkeypatch, reported, admitted
):
    """The control plane believes a chip is there (it serialised the spawn
    on a TPU slot); a sandbox whose /healthz says it warmed on anything else
    is a spawn error, never an admitted CPU sandbox."""

    async def healthz(request):
        return web.json_response(
            {"warm": True, "warm_state": "ready", "backend": reported, "device_count": 1}
        )

    async def warmup(request):
        return web.json_response({"ok": True})

    app = web.Application()
    app.add_routes([web.get("/healthz", healthz), web.post("/warmup", warmup)])
    server = TestServer(app)
    await server.start_server()
    try:
        backend = LocalSandboxBackend(
            Config(
                jax_compilation_cache_dir="",
                local_sandbox_root=str(tmp_path / "sb"),
                executor_warm_ready_timeout=5.0,
            ),
            warm_import_jax=True,
        )
        monkeypatch.setattr(backend, "_tpu_exclusive", lambda: True)
        url = str(server.make_url("")).rstrip("/")
        if admitted:
            await backend._await_warm([url], ["host-0"])
        else:
            with pytest.raises(SandboxSpawnError, match="warmed on backend 'cpu'"):
                await backend._await_warm([url], ["host-0"])
    finally:
        await server.close()
