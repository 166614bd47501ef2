"""Subprocess tests of the sandbox sitecustomize import patches.

Each test runs a fresh interpreter with executor/ on PYTHONPATH (how the
local backend and the sandbox image deploy sitecustomize.py) and checks the
patch behavior from inside user-style code.
"""

import os
import subprocess
import sys
import zipfile
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent.parent
EXECUTOR_DIR = REPO_ROOT / "executor"


def run_sandboxed(source: str, cwd, extra_env=None, timeout=240):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(EXECUTOR_DIR), str(REPO_ROOT)])
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-c", source],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_json_datetime_patch(tmp_path):
    proc = run_sandboxed(
        "import json, datetime\n"
        "print(json.dumps({'t': datetime.date(2026, 7, 29)}))\n",
        tmp_path,
    )
    assert proc.returncode == 0, proc.stderr
    assert '"2026-07-29"' in proc.stdout


def test_partial_init_does_not_poison_patch(tmp_path):
    """A module imported *inside* another module's __init__ must still get
    patched once the import completes (regression: the hook used to mark
    modules patched while they were mid-initialization)."""
    proc = run_sandboxed(
        "import json\n"  # json may already be mid-patch from interpreter boot
        "import datetime\n"
        "print(json.dumps(datetime.time(1, 2, 3)))\n",
        tmp_path,
    )
    assert proc.returncode == 0, proc.stderr
    assert "01:02:03" in proc.stdout


def test_cold_path_jax_profile(tmp_path):
    """APP_JAX_PROFILE=1 in a plain subprocess (no warm runner) must produce
    ./profile.zip via the sitecustomize jax patch — this exercises the
    deferred-patch path, since jax exists in sys.modules but has no
    `profiler` attribute while its own __init__ is still running."""
    proc = run_sandboxed(
        "import jax.numpy as jnp\n"
        "print(float(jnp.dot(jnp.ones(8), jnp.ones(8))))\n",
        tmp_path,
        extra_env={"APP_JAX_PROFILE": "1"},
    )
    assert proc.returncode == 0, proc.stderr
    assert "8.0" in proc.stdout
    zip_path = tmp_path / "profile.zip"
    assert zip_path.exists(), (proc.stdout, proc.stderr)
    with zipfile.ZipFile(zip_path) as zf:
        assert zf.namelist(), "profile.zip must contain trace files"


def test_matplotlib_show_saves_png(tmp_path):
    proc = run_sandboxed(
        "try:\n"
        "    import matplotlib\n"
        "except ImportError:\n"
        "    print('SKIP')\n"
        "    raise SystemExit(0)\n"
        "matplotlib.use('Agg')\n"
        "import matplotlib.pyplot as plt\n"
        "plt.plot([1, 2, 3])\n"
        "plt.show()\n"
        "print('shown')\n",
        tmp_path,
    )
    assert proc.returncode == 0, proc.stderr
    if "SKIP" not in proc.stdout:
        assert (tmp_path / "plot.png").exists()


def test_moviepy_write_videofile_forced_quiet(tmp_path):
    """moviepy isn't installed in this environment, so emulate its module
    shape: the patch must wrap VideoClip.write_videofile to force
    verbose=False, logger=None (progress bars otherwise flood the stdout
    Execute returns)."""
    fake_pkg = tmp_path / "pkgs"
    (fake_pkg / "moviepy").mkdir(parents=True)
    (fake_pkg / "moviepy" / "__init__.py").write_text("")
    (fake_pkg / "moviepy" / "editor.py").write_text(
        # moviepy 1.x shape: write_videofile accepts a verbose kwarg
        "class VideoClip:\n"
        "    def write_videofile(self, path, verbose=True, logger='bar', **kw):\n"
        "        return {'verbose': verbose, 'logger': logger, **kw}\n"
    )
    proc = run_sandboxed(
        "import moviepy.editor as e\n"
        "kwargs = e.VideoClip().write_videofile('out.mp4', verbose=True)\n"
        "assert kwargs == {'verbose': False, 'logger': None}, kwargs\n"
        "print('quiet ok')\n",
        tmp_path,
        extra_env={
            "PYTHONPATH": os.pathsep.join(
                [str(EXECUTOR_DIR), str(REPO_ROOT), str(fake_pkg)]
            )
        },
    )
    assert proc.returncode == 0, proc.stderr
    assert "quiet ok" in proc.stdout


def test_moviepy_2x_flat_layout_forced_quiet(tmp_path):
    """moviepy 2.x drops moviepy.editor and the verbose kwarg; the patch
    keys on the top-level module and forces only logger=None."""
    fake_pkg = tmp_path / "pkgs"
    (fake_pkg / "moviepy").mkdir(parents=True)
    (fake_pkg / "moviepy" / "__init__.py").write_text(
        "class VideoClip:\n"
        "    def write_videofile(self, path, logger='bar', **kw):\n"
        "        return {'logger': logger, **kw}\n"
    )
    proc = run_sandboxed(
        "import moviepy\n"
        "kwargs = moviepy.VideoClip().write_videofile('out.mp4')\n"
        "assert kwargs == {'logger': None}, kwargs\n"
        "print('quiet ok')\n",
        tmp_path,
        extra_env={
            "PYTHONPATH": os.pathsep.join(
                [str(EXECUTOR_DIR), str(REPO_ROOT), str(fake_pkg)]
            )
        },
    )
    assert proc.returncode == 0, proc.stderr
    assert "quiet ok" in proc.stdout
