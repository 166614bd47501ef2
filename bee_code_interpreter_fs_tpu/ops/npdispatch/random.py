"""Stateful numpy.random facade over JAX's functional PRNG.

numpy's random API is stateful (global seed, sequential draws); JAX's is
functional (explicit keys). The shim bridges them with an internal key that is
split per call — seeded via ``seed()`` for reproducibility within the shim
(sequences won't match CPython numpy's MT19937 bit-for-bit; the contract is
distributional, which is what sandboxed analytics code actually relies on).

Small draws (< threshold elements) go to real numpy: they are metadata-sized,
and host RNG is faster than a device round-trip.
"""

from __future__ import annotations

import os
import types
from typing import Any

import jax
import jax.numpy as jnp
import numpy as real_np

from . import lazy, shim
from .shim import TpuArray, _shape_size


def _lazy_draw(op_name, op, key, shape, *extra) -> TpuArray:
    """Build a lazy node for a random draw; key is a concrete leaf, shape a
    static arg (so it enters the structure key)."""
    result = shim.try_lazy(op_name, op, (key, shape, *extra), {})
    if result is not None:
        return result
    return TpuArray(op(key, shape, *extra))


def _normalize_shape(size) -> tuple:
    if size is None:
        return ()
    if isinstance(size, (int, real_np.integer)):
        return (int(size),)
    return tuple(int(s) for s in size)


class RandomShim(types.ModuleType):
    def __init__(self, threshold: int):
        super().__init__("numpy.random")
        self._threshold = threshold
        # Made at the first big draw, not here: a key is a device array, and
        # install() runs in EVERY sandbox Python process (sitecustomize) —
        # beside a runner that holds the chip, and before
        # jax.distributed.initialize on a slice, neither of which survives
        # an import-time backend init.
        self._key = None
        # Fresh entropy per process: unseeded runs must differ across
        # sandbox executions (Monte Carlo across runs relies on it).
        self._seed = int.from_bytes(os.urandom(4), "little")

    def _next_key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed & 0x7FFFFFFF)
        self._key, sub = jax.random.split(self._key)
        return sub

    def _big(self, shape: tuple) -> bool:
        return _shape_size(shape) >= self._threshold

    # -- seeding -------------------------------------------------------------
    def seed(self, seed=None):
        real_np.random.seed(seed)
        if seed is None:
            seed = int.from_bytes(os.urandom(4), "little")
        self._seed, self._key = int(seed), None

    def default_rng(self, seed=None):
        return real_np.random.default_rng(seed)  # host generator API

    # -- draws ---------------------------------------------------------------
    def rand(self, *shape):
        if self._big(shape):
            return _lazy_draw(
                "random.uniform", lazy.random_uniform_op, self._next_key(), shape
            )
        return real_np.random.rand(*shape)

    def randn(self, *shape):
        if self._big(shape):
            return _lazy_draw(
                "random.normal", lazy.random_normal_op, self._next_key(), shape
            )
        return real_np.random.randn(*shape)

    def random(self, size=None):
        shape = _normalize_shape(size)
        if self._big(shape):
            return _lazy_draw(
                "random.uniform", lazy.random_uniform_op, self._next_key(), shape
            )
        return real_np.random.random(size)

    random_sample = random
    sample = random
    ranf = random

    def uniform(self, low=0.0, high=1.0, size=None):
        shape = _normalize_shape(size)
        if self._big(shape):
            return TpuArray(
                jax.random.uniform(
                    self._next_key(), shape, minval=low, maxval=high
                )
            )
        return real_np.random.uniform(low, high, size)

    def normal(self, loc=0.0, scale=1.0, size=None):
        shape = _normalize_shape(size)
        if self._big(shape):
            return TpuArray(
                jax.random.normal(self._next_key(), shape) * scale + loc
            )
        return real_np.random.normal(loc, scale, size)

    def randint(self, low, high=None, size=None, dtype=int):
        shape = _normalize_shape(size)
        if self._big(shape):
            lo, hi = (0, low) if high is None else (low, high)
            try:
                return TpuArray(
                    jax.random.randint(self._next_key(), shape, lo, hi, dtype=dtype)
                )
            except (TypeError, ValueError):
                pass  # dtype unsupported on device — draw on host
        return real_np.random.randint(low, high, size, dtype)

    def exponential(self, scale=1.0, size=None):
        shape = _normalize_shape(size)
        if self._big(shape):
            return TpuArray(jax.random.exponential(self._next_key(), shape) * scale)
        return real_np.random.exponential(scale, size)

    def permutation(self, x):
        if isinstance(x, TpuArray):
            return TpuArray(jax.random.permutation(self._next_key(), x._arr))
        if isinstance(x, (int, real_np.integer)) and int(x) >= self._threshold:
            return TpuArray(jax.random.permutation(self._next_key(), int(x)))
        return real_np.random.permutation(x)

    def shuffle(self, x):
        if isinstance(x, TpuArray):
            # In-place contract: rebind the array's backing value.
            x._concrete = jax.random.permutation(self._next_key(), x._arr)
            x._node = None
            return None
        return real_np.random.shuffle(x)

    def choice(self, a, size=None, replace=True, p=None):
        if isinstance(a, TpuArray):
            return TpuArray(
                jax.random.choice(
                    self._next_key(),
                    a._arr,
                    _normalize_shape(size),
                    replace=replace,
                    p=None if p is None else jnp.asarray(p),
                )
            )
        return real_np.random.choice(a, size, replace, p)

    # everything else (beta, gamma, poisson, RandomState, ...) → host numpy
    def __getattr__(self, name: str) -> Any:
        return getattr(real_np.random, name)
