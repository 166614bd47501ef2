"""Sandbox-wide import patches, auto-loaded into every user Python process.

Installed into the sandbox venv's site-packages (reference parity:
executor/sitecustomize.py via executor/Dockerfile:107). Patches are applied
lazily via an import hook so non-matching code pays ~nothing:

- matplotlib.pyplot.show() → savefig("plot.png") (headless sandbox)
- PIL.ImageShow.show() → img.save("image.png")
- json → datetime/date-aware default encoder + ISO-parsing decoder
- numpy → the TPU dispatch shim (bee_code_interpreter_fs_tpu.ops.npdispatch),
  when APP_NUMPY_DISPATCH=1: user-submitted array code transparently runs on
  XLA/TPU (the north-star hook point, SURVEY.md §2.15).
"""

import builtins
import os
import sys

_PATCHED: set[str] = set()


def _patch_matplotlib_pyplot(plt) -> None:
    def _show(*args, **kwargs):  # noqa: ANN002, ANN003
        try:
            plt.savefig("plot.png")
        finally:
            plt.close("all")

    plt.show = _show


def _patch_pil_imageshow(imageshow) -> None:
    def _show(image, title=None, **options):  # noqa: ANN001, ANN003
        image.save("image.png")
        return True

    imageshow.show = _show


def _patch_moviepy(module) -> None:
    """Force quiet, loggerless video writes: moviepy's progress bars flood
    the captured stdout that Execute returns to the client. Keyed on both
    the 1.x (`moviepy.editor`, has a `verbose` kwarg) and 2.x (`moviepy`,
    logger-only) module layouts; the signature decides what to force."""
    import inspect

    clip_cls = getattr(module, "VideoClip", None)
    if clip_cls is None or not hasattr(clip_cls, "write_videofile"):
        return
    original = clip_cls.write_videofile
    try:
        has_verbose = "verbose" in inspect.signature(original).parameters
    except (TypeError, ValueError):
        has_verbose = False

    def write_videofile(self, *args, **kwargs):  # noqa: ANN001, ANN002, ANN003
        if has_verbose:
            kwargs["verbose"] = False
        kwargs["logger"] = None
        return original(self, *args, **kwargs)

    clip_cls.write_videofile = write_videofile


def _patch_json(json_mod) -> None:
    import datetime

    _default_encoder = json_mod.JSONEncoder

    class DateTimeEncoder(_default_encoder):
        def default(self, o):  # noqa: ANN001
            if isinstance(o, (datetime.datetime, datetime.date, datetime.time)):
                return o.isoformat()
            return super().default(o)

    _orig_dumps = json_mod.dumps
    _orig_dump = json_mod.dump

    def dumps(*args, **kwargs):  # noqa: ANN002, ANN003
        kwargs.setdefault("cls", DateTimeEncoder)
        return _orig_dumps(*args, **kwargs)

    def dump(*args, **kwargs):  # noqa: ANN002, ANN003
        kwargs.setdefault("cls", DateTimeEncoder)
        return _orig_dump(*args, **kwargs)

    json_mod.dumps = dumps
    json_mod.dump = dump
    json_mod.DateTimeEncoder = DateTimeEncoder


def _patch_jax_profile(jax_mod) -> None:
    """APP_JAX_PROFILE=1 (cold-subprocess path; the warm runner handles this
    itself): start a profiler trace at first jax import, stop + zip it to
    ./profile.zip at exit so the changed-file scan ships it back."""
    if str(os.environ.get("APP_JAX_PROFILE", "")).lower() in ("", "0", "false"):
        return
    import atexit

    import jax_profile  # deployed alongside this file

    trace_dir = jax_profile.start_trace()

    def _finish() -> None:
        try:
            jax_profile.finish_trace(trace_dir)
        except Exception:  # noqa: BLE001 — profiling is best-effort
            pass

    atexit.register(_finish)


_PATCHES = {
    "matplotlib.pyplot": _patch_matplotlib_pyplot,
    "PIL.ImageShow": _patch_pil_imageshow,
    "moviepy.editor": _patch_moviepy,  # moviepy 1.x
    "moviepy": _patch_moviepy,  # moviepy 2.x (flat layout)
    "json": _patch_json,
    "jax": _patch_jax_profile,
}

_orig_import = builtins.__import__


def _patched_import(name, globals=None, locals=None, fromlist=(), level=0):  # noqa: A002
    module = _orig_import(name, globals, locals, fromlist, level)
    for mod_name, patch in _PATCHES.items():
        if mod_name in sys.modules and mod_name not in _PATCHED:
            target = sys.modules[mod_name]
            # The hook also fires on imports nested inside mod_name's own
            # __init__ (where the module exists in sys.modules but is only
            # partially initialized — e.g. jax has no `profiler` attr yet).
            # Defer until the module finishes importing.
            spec = getattr(target, "__spec__", None)
            if spec is not None and getattr(spec, "_initializing", False):
                continue
            _PATCHED.add(mod_name)
            try:
                patch(target)
            except Exception:  # noqa: BLE001 — patches are best-effort
                pass
    return module


builtins.__import__ = _patched_import

if os.environ.get("APP_NUMPY_DISPATCH", "0") not in ("0", "false", ""):
    try:
        from bee_code_interpreter_fs_tpu.ops.npdispatch import install as _install_np

        _install_np()
    except Exception:  # noqa: BLE001 — reported, then fatal
        import traceback

        sys.stderr.write("[sitecustomize] numpy dispatch install failed:\n")
        traceback.print_exc()
        # A failed start, not a warning: with the shim asked for, user array
        # code on stock host numpy would succeed on the wrong device.
        # SystemExit because site.py swallows every Exception raised here.
        raise SystemExit(1) from None
