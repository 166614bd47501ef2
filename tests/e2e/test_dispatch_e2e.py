"""End-to-end dispatch-shim tests: run the examples through the full Execute
stack with APP_NUMPY_DISPATCH enabled in the sandbox (CPU JAX backend here;
the same path hits the TPU in production/bench)."""

# Optional-dep guard: a missing dependency must degrade this module to a
# SKIP at collection, not an ERROR that interrupts the whole run.
import pytest

pytest.importorskip("httpx", reason="optional e2e dependency not installed")

from pathlib import Path


from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.local import LocalSandboxBackend
from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
from bee_code_interpreter_fs_tpu.services.storage import Storage

EXAMPLES = Path(__file__).resolve().parent.parent.parent / "examples"


@pytest.fixture
async def executor(tmp_path):
    config = Config(
        file_storage_path=str(tmp_path / "storage"),
        local_sandbox_root=str(tmp_path / "sandboxes"),
        executor_pod_queue_target_length=0,
        jax_compilation_cache_dir="",
        default_execution_timeout=120.0,
    )
    backend = LocalSandboxBackend(config, warm_import_jax=True)
    executor = CodeExecutor(backend, Storage(config.file_storage_path), config)
    yield executor
    await executor.close()


async def test_shim_active_in_sandbox(executor):
    result = await executor.execute(
        "import numpy as np\n"
        "a = np.random.rand(300000)\n"
        "print(type(a).__name__)\n"
        "print(type(np.zeros(3)).__name__)\n"
        "s = float((a * a).sum())\n"
        "print(0.28 < s / 300000 < 0.39)\n"
    )
    assert result.exit_code == 0, result.stderr
    lines = result.stdout.splitlines()
    assert lines[0] == "TpuArray"  # big arrays on device
    assert lines[1] == "ndarray"  # small arrays on host
    assert lines[2] == "True"


async def test_benchmark_fib_unaffected(executor):
    source = (EXAMPLES / "benchmark-fib.py").read_text()
    result = await executor.execute(source, timeout=120)
    assert result.exit_code == 0, result.stderr
    assert "fib(10000) x1000" in result.stdout


@pytest.mark.parametrize(
    "example", ["benchmark-attention.py", "benchmark-matmul.py"]
)
async def test_tpu_payload_refuses_the_cpu(executor, example):
    """The chip payloads chip_smoke.py sends have no CPU mode: on the CPU
    test platform they exit non-zero, saying so, instead of printing the
    same markers from a shrunken run (the kernel's own numerics are covered
    interpreted in tests/unit/test_flash_attention.py and compiled for the
    chip in tests/unit/test_tpu_aot_compile.py)."""
    source = (EXAMPLES / example).read_text()
    result = await executor.execute(source, timeout=120)
    assert result.exit_code != 0
    assert "is a TPU payload; jax attached cpu" in result.stderr
    assert "TFLOPS=" not in result.stdout


async def test_using_imports_with_shim(executor):
    source = (EXAMPLES / "using_imports.py").read_text()
    result = await executor.execute(source, timeout=120)
    assert result.exit_code == 0, result.stderr
    assert result.stdout.strip().endswith("ok")


async def test_escaping_example(executor):
    source = (EXAMPLES / "escaping.py").read_text()
    result = await executor.execute(source)
    assert result.exit_code == 0
    assert "quotes: ' \"" in result.stdout


async def test_crash_example(executor):
    source = (EXAMPLES / "crash.py").read_text()
    result = await executor.execute(source)
    assert result.exit_code == 3
    assert "about to crash" in result.stdout
