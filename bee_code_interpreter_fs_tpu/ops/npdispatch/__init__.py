"""numpy → jax.numpy dispatch shim: transparent TPU acceleration for
user-submitted array code.

This is the north-star hook (BASELINE.json; SURVEY.md §2.15): the sandbox's
sitecustomize calls :func:`install` before user code runs, replacing the
``numpy`` module in ``sys.modules`` with a shim that

- keeps **everything structural** (dtypes, ndarray class, constants, testing,
  io, errstate, …) passing straight through to real numpy, so libraries like
  pandas/scipy that import numpy keep working;
- overrides a curated set of **compute functions** (creation, elementwise,
  reductions, linalg, fft, random) with dispatchers that run on XLA/TPU when
  the data is big enough to win, returning :class:`~.shim.TpuArray` handles
  that live on device;
- keeps small arrays on the host (below ``APP_NUMPY_DISPATCH_THRESHOLD``
  elements, default 2**17), so metadata-sized numpy use pays ~zero overhead —
  the BASELINE.json config-2 requirement (benchmark-fib / using_imports must
  be unaffected).

Precision policy: like stock JAX, float64 requests are computed in float32 on
TPU (``APP_NUMPY_DISPATCH_X64=1`` opts into true 64-bit, which TPUs emulate
slowly). Mutation (``a[i] = v``, ``+=``) is supported on TpuArray via
functional ``.at[].set`` rebinding.

Non-array code never reaches this module: the shim is only installed in the
sandbox, and only touches the ``numpy`` entry in ``sys.modules``.
"""

from __future__ import annotations

import os
import sys

_installed = False
_saved_modules: dict[str, object] = {}


def install(threshold: int | None = None) -> None:
    """Replace ``sys.modules['numpy']`` (+ random/linalg/fft) with the shim."""
    global _installed
    if _installed:
        return
    import numpy as _real_numpy  # noqa: F401 — ensure real numpy is loaded first

    import jax

    if os.environ.get("APP_NUMPY_DISPATCH_X64", "0") not in ("0", "false", ""):
        jax.config.update("jax_enable_x64", True)

    from . import lazy, shim

    # numpy users expect float32 matmuls to be float32: on TPU the MXU would
    # otherwise run bf16 passes and round (e.g. 257.0 -> 256.0). "highest"
    # keeps numpy-compatible accuracy — but SCOPED to shim-dispatched
    # computations (lazy.precision_scope), never as a global
    # jax_default_matmul_precision: user jax code in the same sandbox must
    # keep its own numerics, and Pallas kernels break under a global
    # "highest" (bf16 dots lower with an fp32 contract precision Mosaic
    # rejects).
    lazy.MATMUL_PRECISION = os.environ.get(
        "APP_NUMPY_DISPATCH_MATMUL_PRECISION", "highest"
    )
    # Fail at install time, not from inside the user's first dispatched op:
    # entering the scope once validates the string against jax's enum.
    with lazy.precision_scope():
        pass

    if threshold is None:
        threshold = int(os.environ.get("APP_NUMPY_DISPATCH_THRESHOLD", str(2**17)))
    module = shim.build_shim_module(threshold=threshold)
    for name in ("numpy", "numpy.random", "numpy.linalg", "numpy.fft"):
        _saved_modules[name] = sys.modules.get(name)
    sys.modules["numpy"] = module
    sys.modules["numpy.random"] = module.random
    sys.modules["numpy.linalg"] = module.linalg
    sys.modules["numpy.fft"] = module.fft
    _installed = True


def uninstall() -> None:
    """Restore real numpy (used by tests)."""
    global _installed
    if not _installed:
        return
    for name, mod in _saved_modules.items():
        if mod is None:
            sys.modules.pop(name, None)
        else:
            sys.modules[name] = mod
    _saved_modules.clear()
    _installed = False


def take_counters() -> dict | None:
    """What the shim did since this was last called (`lazy.Counters`, whose
    docstring lists the fields and what each means), and zero it; None where
    no shim is installed. The warm runner calls it at the end of a turn's
    user code, for the reply, and on /reset."""
    if not _installed:
        return None
    from . import lazy

    return lazy.counters.take()
