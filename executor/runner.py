"""Warm execution runner: a persistent Python process that pre-initializes
JAX/TPU at sandbox boot and then executes user scripts on demand.

Why it exists (TPU design, SURVEY.md §7 hard part #2): libtpu init + device
enumeration costs seconds. The reference spawned a fresh interpreter per
execution (via xonsh, executor/server.rs:202-218), which is fine on CPU but
would put TPU init on every Execute's critical path. Here the executor server
(server.cpp) starts this runner when the sandbox boots — i.e. while the
sandbox is still sitting in the warm pool — so by the time an Execute arrives,
`import jax` and device init are already done and user code sees a hot TPU.

Protocol: newline-delimited JSON. fd 3 = requests in, fd 4 = responses out.
Request:  {"source_path": ..., "stdout_path": ..., "stderr_path": ..., "env": {...},
           "sent_mono": <the server's CLOCK_MONOTONIC at the pipe write>}
Response: {"exit_code": int, "stages": [[name, start_offset_s, duration_s], ...],
           "user_cpu_s": the process's CPU seconds inside the `user_code` stage,
           "shim": {<counter>: number, ...} where the numpy shim is installed}
Ready line (sent once at boot):
  {"ready": true, "backend": ..., "device_count": n, "device_kind": ...,
   "attach_stages": {"interpreter_start": s, "import_jax": s, "distributed_init": s,
                     "devices": s, "first_compile": s}}

User scripts run in-process via runpy with stdout/stderr redirected at the fd
level, fresh sys.argv, and __main__ semantics.

Sandboxes are single-use, but the runner is NOT: the TPU lease (this process,
with jax imported and the chip attached) outlives each sandbox generation.
Between generations the server sends a `{"op": "reset"}` request and the
runner scrubs every trace of the previous user: stray child processes are
killed, workspace-origin modules are dropped from sys.modules, os.environ and
cwd and sys.stdout/stderr are restored to their boot snapshot, and device
buffers are garbage-collected. Only after an ok-reset does the control plane
hand the sandbox to a new request; anything un-scrubbable (runner killed on
timeout, reset failure) falls back to full process disposal. This is what
keeps Execute p50 at pool-pop speed instead of a ~seconds jax/libtpu re-init
per request (the round-2 bench's 3.4 s queue_wait).
"""

import json
import os
import runpy
import sys
import threading
import time
import traceback

REQ_FD = 3
RESP_FD = 4

# Trace context for runner-authored log lines: the server forwards the
# request's trace id (parsed from the control plane's traceparent) and the
# runner prefixes its own diagnostics with it, so a demuxed batch job's
# sandbox output is attributable to its originating request. Thread-local:
# batch jobs run in threads, each under its own request's trace id.
_TRACE_LOCAL = threading.local()


def _set_trace_id(trace_id) -> None:
    _TRACE_LOCAL.trace_id = trace_id if isinstance(trace_id, str) else None


def _log(msg: str) -> None:
    """Runner diagnostic line, trace-id-prefixed when the request carried
    trace context (goes to the executor's log via inherited stderr, or to
    the job's capture while a redirect is active — both are the places an
    operator reconstructs a batched run from)."""
    trace_id = getattr(_TRACE_LOCAL, "trace_id", None)
    prefix = f"[runner trace={trace_id}] " if trace_id else "[runner] "
    try:
        sys.stderr.write(prefix + msg + "\n")
    except Exception:  # noqa: BLE001 — logging must never kill the runner
        pass

# Persistent-compilation-cache traffic, counted via jax.monitoring events
# (registered in _warm_import): the per-request delta rides the execute
# reply so the fleet compile cache's hit rate is observable per run.
_CACHE_EVENTS = {"hits": 0, "misses": 0}
_CACHE_LISTENING = False


def _register_cache_listener() -> None:
    """Count jax's compilation-cache hit/miss monitoring events."""
    global _CACHE_LISTENING
    from jax._src import monitoring

    def on_event(event: str, *args, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _CACHE_EVENTS["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _CACHE_EVENTS["misses"] += 1

    monitoring.register_event_listener(on_event)
    _CACHE_LISTENING = True


def _cache_counts() -> tuple[int, int]:
    """(hits, misses) so far."""
    return _CACHE_EVENTS["hits"], _CACHE_EVENTS["misses"]


# ---------------------------------------------------------------------------
# Stage clock of a serial turn. The server puts its CLOCK_MONOTONIC reading at
# the pipe write (`sent_mono`) into the request line; time.monotonic() is the
# same kernel clock on the same host, so the reply's `stages` list
# ([name, start_offset_s, duration_s], offsets from that reading) tiles the
# turn from the write to the reply: each stage ends where the next begins.
# The server forwards them as `runner.<name>` in its `trace` block. Names are
# a fixed set (the server's allow-list; they label a histogram upstream).

_STAGES: list = []  # [(name, started)] of the request in hand
_USER_CPU: list = []  # CPU seconds of its `user_code` stage (`_run_one`), until taken
_STAGE_ANNOTATION: list = []  # the open TraceAnnotation, while a capture runs
_ANNOTATING = False  # True only between the profiler's start and its stop
# [(started, seconds)] of the full collection after the last reset's ack,
# reported once, on the next reply that carries stages.
_GC_AFTER_RESET: list = []


def _trace_annotation(name: str):
    """The stage as a host-plane event of the running JAX capture, joined
    to GET /traces/{trace_id} by the request's trace id."""
    import jax

    trace_id = getattr(_TRACE_LOCAL, "trace_id", None) or ""
    return jax.profiler.TraceAnnotation(f"runner.{name}", trace_id=trace_id)


def _annotate(on: bool) -> None:
    """Close the open annotation; with `on`, wrap the stage in hand (and
    every later one, until `_annotate(False)`) in one of its own. Only a
    turn that runs under the profiler ever gets here with `on`."""
    global _ANNOTATING
    while _STAGE_ANNOTATION:
        try:
            _STAGE_ANNOTATION.pop().__exit__(None, None, None)
        except Exception:  # noqa: BLE001 — an annotation never fails a turn
            pass
    _ANNOTATING = on
    if on and _STAGES:
        try:
            annotation = _trace_annotation(_STAGES[-1][0])
            annotation.__enter__()
            _STAGE_ANNOTATION.append(annotation)
        except Exception:  # noqa: BLE001
            _ANNOTATING = False


def _stage(name: str, started: float | None = None) -> None:
    """`name` begins now (or began at `started`); the stage before it ends."""
    _STAGES.append((name, time.monotonic() if started is None else started))
    if _ANNOTATING:
        _annotate(True)


def _begin_stages(name: str, started: float) -> None:
    del _STAGES[:]
    del _USER_CPU[:]
    _annotate(False)
    _stage(name, started)


def _take_stages(sent_mono) -> list | None:
    """The request's stages for its reply, `pickup` (pipe write until the
    line was read) first; None where the server sent no clock reading."""
    ended = time.monotonic()
    _annotate(False)
    marks = list(_STAGES)
    del _STAGES[:]
    if not isinstance(sent_mono, (int, float)) or isinstance(sent_mono, bool):
        return None
    out = []
    while _GC_AFTER_RESET:
        started, seconds = _GC_AFTER_RESET.pop()
        out.append(["gc_after_reset", round(started - sent_mono, 6), round(seconds, 6)])
    marks.insert(0, ("pickup", float(sent_mono)))
    for (name, started), (_next, until) in zip(marks, marks[1:] + [("", ended)]):
        out.append([name, round(started - sent_mono, 6), round(max(0.0, until - started), 6)])
    return out


def _process_cpu_s() -> float:
    """The process's CPU seconds so far, user and system, every thread: the
    clock the CPU guard reads (`_apply_user_rlimits`)."""
    import resource

    usage = resource.getrusage(resource.RUSAGE_SELF)
    return usage.ru_utime + usage.ru_stime


def _take_user_cpu() -> float | None:
    """What `_run_one` measured, once; None where no user code ran."""
    return round(_USER_CPU.pop(), 6) if _USER_CPU else None


def _take_shim() -> dict | None:
    """The numpy shim's counters of the request in hand (`lazy.Counters` in
    `ops/npdispatch`, whose docstring lists them), taken and zeroed
    the way `_take_stages` takes the stage clocks; None in a runner whose
    interpreter started without the shim."""
    shim = sys.modules.get("bee_code_interpreter_fs_tpu.ops.npdispatch")
    if shim is None:
        return None
    try:
        return shim.take_counters()
    except Exception:  # noqa: BLE001 — a counter never fails a turn
        return None


def _send(obj: dict) -> None:
    try:
        os.write(RESP_FD, (json.dumps(obj) + "\n").encode())
    except OSError:
        # Server died while we were executing; nothing left to report to.
        # Skip atexit (jax.distributed shutdown would block on dead peers).
        os._exit(0)


def _distributed_init(jax) -> None:
    """Multi-host slice bootstrap (SURVEY.md §7.6): the backend spawns one
    executor per host with APP_NUM_HOSTS / APP_HOST_ID / APP_COORDINATOR_ADDR;
    host 0 binds the coordinator, peers dial it over DCN, and after this call
    every host sees the slice's full device set — user code gets a
    pre-established global mesh without any cooperation on its part (the
    reference's NCCL/MPI role, done the JAX way)."""
    num_hosts = int(os.environ.get("APP_NUM_HOSTS", "1") or "1")
    if num_hosts <= 1:
        return
    coordinator = os.environ["APP_COORDINATOR_ADDR"]
    host_id = int(os.environ.get("APP_HOST_ID", "0"))
    # On the CPU platform (tests, dev) cross-process collectives need gloo;
    # the knob is ignored by the TPU backend, which uses ICI.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_hosts,
        process_id=host_id,
    )


def _expected_platform() -> str:
    """The platform this runner was started for: the first entry of
    JAX_PLATFORMS when the operator stated one (tests and chipless dev say
    `cpu`), else the TPU this service exists to serve."""
    stated = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    # jax names the GPU platforms by vendor, their devices say "gpu"
    return {"cuda": "gpu", "rocm": "gpu"}.get(stated, stated) or "tpu"


def _warm_import() -> dict:
    """Pre-import jax and touch the devices so TPU init happens now.

    A runner that cannot import jax, or gets another platform than the one
    it was started for (jax itself falls back to the CPU with a warning
    when it finds no TPU), reports ready=false: the server marks warm-up
    failed and the control plane refuses the sandbox, instead of user code
    quietly running on the host."""
    info = {"ready": True, "backend": "none", "device_count": 0}
    if os.environ.get("APP_WARM_IMPORT_JAX", "1") in ("0", "false"):
        # Explicit escape hatch (plumbing tests / no-JAX dev); on a slice
        # this forgoes the mesh knowingly.
        return info
    # Where the attach's seconds go, step by step (GET /device-stats beside
    # `attach_seconds`, and one line of the sandbox's log). `interpreter_start`
    # is everything before this function: python's own start and
    # sitecustomize, which imports jax and numpy to install the numpy shim, so
    # that `import_jax` here finds the module loaded.
    stages = info["attach_stages"] = {}
    age = _process_age_s()
    if age is not None:
        stages["interpreter_start"] = age
    mark = time.monotonic()

    def step_done(name: str) -> None:
        nonlocal mark
        now = time.monotonic()
        stages[name] = round(now - mark, 6)
        mark = now

    try:
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        import jax

        step_done("import_jax")
        if cache_dir:
            _register_cache_listener()

        _distributed_init(jax)
        step_done("distributed_init")
        if cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            # Persist every kernel: the default 1s min-compile-time filter
            # would skip most eager-op kernels, so fresh sandboxes would
            # recompile everything and the pool's cache amortization
            # (SURVEY.md §7 hard part #2) would never engage.
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        devices = jax.devices()
        step_done("devices")
        info["backend"] = devices[0].platform
        info["device_count"] = len(devices)  # global across the slice
        # Device kind for the telemetry plane ("TPU v5 lite" etc.; CPU
        # devices report "cpu") — surfaced via GET /device-stats so operators
        # see what hardware a lane's hosts actually hold.
        info["device_kind"] = str(devices[0].device_kind)
        if info["backend"] != (expected := _expected_platform()):
            raise RuntimeError(
                f"runner started for platform {expected!r} but jax attached "
                f"{info['backend']!r} ({info['device_kind']})"
            )
        if jax.process_count() > 1:
            info["process_count"] = jax.process_count()
            info["process_index"] = jax.process_index()
            info["local_device_count"] = jax.local_device_count()
        # Trigger one tiny compile so the XLA pipeline is paged in.
        import jax.numpy as jnp

        jnp.add(jnp.ones(()), 1.0).block_until_ready()
        step_done("first_compile")
    except Exception:  # noqa: BLE001 — reported to the server, then fatal
        traceback.print_exc()
        _log("fatal: jax warm-up failed")
        info["ready"] = False
    return info


def _process_age_s() -> float | None:
    """Seconds since this process was started, by the kernel's own record
    (/proc: the start in clock ticks since boot, against the uptime; both to
    10 ms); None where /proc does not say."""
    try:
        with open("/proc/self/stat") as f:
            started_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return round(max(0.0, uptime - started_ticks / os.sysconf("SC_CLK_TCK")), 3)
    except (OSError, ValueError, IndexError):
        return None


def _profile_requested(env: dict) -> bool:
    return str(env.get("APP_JAX_PROFILE", "")).lower() not in ("", "0", "false")


def _device_memory_snapshot() -> tuple[int, int]:
    """(live_bytes, peak_bytes) summed across local devices, or -1 where
    the signal is unavailable. TPU/GPU devices report allocator stats via
    device.memory_stats() (bytes_in_use / peak_bytes_in_use); the CPU
    platform usually reports none, so live bytes fall back to summing
    jax.live_arrays() (no peak tracking there — the caller brackets the
    run and uses max(before, after) instead). Never imports jax: if the
    warm import didn't run, there is nothing to measure."""
    jax = sys.modules.get("jax")
    if jax is None:
        return -1, -1
    try:
        live = peak = 0
        reported = False
        for device in jax.local_devices():
            stats_fn = getattr(device, "memory_stats", None)
            stats = stats_fn() if callable(stats_fn) else None
            if not isinstance(stats, dict):
                continue
            in_use = stats.get("bytes_in_use")
            if not isinstance(in_use, int):
                continue
            reported = True
            live += in_use
            peak_b = stats.get("peak_bytes_in_use")
            peak += peak_b if isinstance(peak_b, int) else in_use
        if reported:
            return live, peak
        total = 0
        for arr in jax.live_arrays():
            nbytes = getattr(arr, "nbytes", 0)
            if isinstance(nbytes, int):
                total += nbytes
        return total, -1
    except Exception:  # noqa: BLE001 — accounting must never kill a run
        return -1, -1


def _rss_bytes() -> int:
    """This process's resident set, or -1."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return -1


class _DeviceMemoryProbe:
    """Brackets one run with device-memory samples and shapes the reply
    block. Armed per request (the control plane asks via the request's
    `device_memory` flag — the perf-observer kill switch keeps sampling,
    and its tiny cost, entirely off the wire when the plane is off)."""

    __slots__ = ("live_before", "peak_before")

    def __init__(self) -> None:
        self.live_before, self.peak_before = _device_memory_snapshot()

    def finish(self) -> dict:
        live_after, peak_after = _device_memory_snapshot()
        return {
            "live_bytes_before": self.live_before,
            "live_bytes_after": live_after,
            "peak_bytes_before": self.peak_before,
            "peak_bytes_after": peak_after,
            "rss_bytes": _rss_bytes(),
        }


def _resolve_mem_budget() -> int:
    """APP_MAX_USER_MEMORY_BYTES: extra address-space bytes user code may
    allocate beyond the warm baseline. "auto" = 80% of the host's physical
    RAM; 0/unset = no limit."""
    raw = os.environ.get("APP_MAX_USER_MEMORY_BYTES", "").strip().lower()
    if not raw or raw in ("0", "false", "off"):
        return 0
    if raw == "auto":
        try:
            return int(
                0.8 * os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
            )
        except (ValueError, OSError):
            return 0
    try:
        return max(int(raw), 0)
    except ValueError:
        return 0


class _CpuTimeExceeded(BaseException):
    """Raised by the SIGXCPU handler when the per-request CPU budget runs
    out: a BaseException so user-code `except Exception` blocks can't
    swallow the limit, unwinding to _run_one which reports the typed
    `cpu_time` violation — the warm process (and its device lease) stays
    alive, unlike the executor watchdog's group kill."""


def _request_limit(limits: dict, key: str, env_value: int) -> int:
    """Effective in-process bound: request value min-clamped by the env
    budget (operator policy may only be tightened, never raised)."""
    try:
        requested = int(limits.get(key) or 0)
    except (TypeError, ValueError):
        requested = 0
    if requested <= 0:
        return env_value
    if env_value <= 0:
        return requested
    return min(requested, env_value)


def _apply_user_rlimits(limits: dict | None = None):
    """Bound the user script with soft rlimits; returns a restore thunk.

    RLIMIT_AS soft = current VmSize + budget: an allocation bomb inside
    user code gets a clean in-process MemoryError (traceback in its stderr,
    exit_code 1) instead of inviting the host OOM killer. The window is
    relative to the CURRENT footprint because the warm runner already holds
    jax + device mappings — an absolute cap below that would fail every
    future mmap including benign ones. RLIMIT_NOFILE soft comes from
    APP_MAX_OPEN_FILES (0 = inherit).

    `limits` is the per-request budget the executor server forwards
    (memory_bytes / cpu_seconds / nofile / fsize_bytes) — request values
    only ever TIGHTEN the env policy. cpu_seconds arms a soft RLIMIT_CPU at
    (current process CPU + budget) with a SIGXCPU handler that raises
    _CpuTimeExceeded, and fsize_bytes arms a soft RLIMIT_FSIZE with SIGXFSZ
    ignored so an oversized write surfaces as OSError(EFBIG) instead of the
    default signal killing the warm process.

    Soft-only on purpose: the hard limits stay put so the post-run restore
    works without privilege. This is a guardrail against runaway agent
    snippets, not a security boundary (user code could raise its own soft
    limit — the executor's watchdog is the backstop; same residual-risk
    contract as _reset's). The kubernetes backend bounds memory with
    container resources instead; the reference delegates isolation
    wholesale to the cluster runtime (README.md:56-57).
    """
    import resource
    import signal as _signal

    limits = limits or {}
    restores = []
    signal_restores = []

    def lower_soft(which, target) -> None:
        soft, hard = resource.getrlimit(which)
        if hard != resource.RLIM_INFINITY:
            target = min(target, hard)
        if soft == resource.RLIM_INFINITY or target < soft:
            resource.setrlimit(which, (target, hard))
            restores.append((which, (soft, hard)))

    budget = _request_limit(limits, "memory_bytes", _resolve_mem_budget())
    if budget > 0:
        try:
            with open("/proc/self/statm") as f:
                vm_bytes = int(f.read().split()[0]) * os.sysconf("SC_PAGE_SIZE")
            lower_soft(resource.RLIMIT_AS, vm_bytes + budget)
        except (OSError, ValueError):
            pass
    nofile_raw = os.environ.get("APP_MAX_OPEN_FILES", "").strip()
    nofile_env = int(nofile_raw) if nofile_raw.isdigit() else 0
    nofile = _request_limit(limits, "nofile", nofile_env)
    if nofile > 0:
        try:
            lower_soft(resource.RLIMIT_NOFILE, nofile)
        except (OSError, ValueError):
            pass
    fsize = _request_limit(limits, "fsize_bytes", 0)
    if fsize > 0:
        try:
            lower_soft(resource.RLIMIT_FSIZE, fsize)
            saved = _signal.signal(_signal.SIGXFSZ, _signal.SIG_IGN)
            signal_restores.append((_signal.SIGXFSZ, saved))
        except (OSError, ValueError):
            pass
    try:
        cpu_budget = float(limits.get("cpu_seconds") or 0)
    except (TypeError, ValueError):
        cpu_budget = 0.0
    if cpu_budget > 0:
        try:
            spent = _process_cpu_s()

            def on_xcpu(signum, frame):
                raise _CpuTimeExceeded(
                    f"CPU time limit ({cpu_budget:.0f}s) exceeded"
                )

            saved = _signal.signal(_signal.SIGXCPU, on_xcpu)
            signal_restores.append((_signal.SIGXCPU, saved))
            # RLIMIT_CPU has whole-second granularity and counts the whole
            # process, so the soft ceiling rides on top of what the warm
            # runner has already spent.
            lower_soft(resource.RLIMIT_CPU, int(spent + cpu_budget) + 1)
        except (OSError, ValueError):
            pass

    def restore() -> None:
        # Idempotent (pops as it goes): called from the except path to get
        # headroom back BEFORE traceback formatting, then again in finally.
        while restores:
            lim, vals = restores.pop()
            try:
                resource.setrlimit(lim, vals)
            except (OSError, ValueError):
                pass
        while signal_restores:
            signum, handler = signal_restores.pop()
            try:
                _signal.signal(signum, handler)
            except (ValueError, TypeError, OSError):
                pass

    return restore


def _import_jax_profile():
    return _import_sibling("jax_profile")


def _start_profile() -> str | None:
    """Begin a JAX profiler trace; returns the trace dir, or None."""
    try:
        return _import_jax_profile().start_trace()
    except Exception:  # noqa: BLE001 — profiling is best-effort
        traceback.print_exc()
        return None


def _finish_profile(trace_dir: str) -> None:
    """Stop the trace and zip it to ./profile.zip (cwd = workspace, so the
    changed-file scan returns it to the client)."""
    try:
        _import_jax_profile().finish_trace(trace_dir)
    except Exception:  # noqa: BLE001
        traceback.print_exc()


def _import_sibling(name: str):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# APP_JAX_PROFILE stays out of os.environ: the warm runner profiles the
# run itself, and leaking the var would make a sitecustomize on the path
# double-start the profiler at first jax import. The rlimit knobs stay
# out too: they are operator policy from the sandbox's boot env, and a
# request-supplied override would let the very snippets the guardrail
# targets turn it off.
_OPERATOR_ONLY = (
    "APP_JAX_PROFILE",
    "APP_MAX_USER_MEMORY_BYTES",
    "APP_MAX_OPEN_FILES",
)


def _exit_code_of(e: SystemExit) -> int:
    """What the interpreter does with sys.exit(x): None is 0, an int is the
    code, anything else is printed to stderr and exits 1."""
    if e.code is None:
        return 0
    if isinstance(e.code, int):
        return e.code
    print(e.code, file=sys.stderr)
    return 1


def _run_one(req: dict) -> tuple[int, str | None]:
    """Execute one request; returns (exit_code, violation) where violation
    is the typed limit kind when an in-process resource guard ended the run
    (None otherwise — including plain user errors)."""
    source_path = req["source_path"]
    run_path = source_path
    try:
        # Mixed Python/shell snippets run via the shellfb transform — the
        # xonsh role (reference server.rs:197-207) without its 80 ms tax.
        run_path = _import_sibling("shellfb").prepare(source_path)
    except Exception:  # noqa: BLE001 — fallback is best-effort
        traceback.print_exc()
    env = req.get("env") or {}
    env_to_set = {k: v for k, v in env.items() if k not in _OPERATOR_ONLY}
    saved_env = {k: os.environ.get(k) for k in env_to_set}
    os.environ.update({k: str(v) for k, v in env_to_set.items()})

    out_fd = os.open(req["stdout_path"], os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    err_fd = os.open(req["stderr_path"], os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    saved_out, saved_err = os.dup(1), os.dup(2)
    sys.stdout.flush()
    sys.stderr.flush()
    os.dup2(out_fd, 1)
    os.dup2(err_fd, 2)
    os.close(out_fd)
    os.close(err_fd)
    saved_argv = sys.argv
    exit_code = 0
    violation = None
    limits = req.get("limits") or {}
    # Is a memory budget actually armed? A MemoryError under an armed window
    # is the oom violation caught cleanly; without one it is ordinary user
    # code raising (or exhausting the host for real — the watchdog's case).
    mem_limited = _request_limit(limits, "memory_bytes", _resolve_mem_budget()) > 0
    trace_dir = None
    if _profile_requested(env):
        _stage("profile_start")
        trace_dir = _start_profile()
        if trace_dir is not None:
            _annotate(True)
    _stage("limits_arm")
    restore_rlimits = _apply_user_rlimits(limits)
    # User code may rebind/ignore SIGINT; restore it afterwards or a single
    # tenant could permanently disable the server's cooperative timeout
    # cancellation for every later generation of this warm process.
    import signal as _signal

    saved_sigint = _signal.getsignal(_signal.SIGINT)
    try:
        sys.argv = [source_path]  # argv[0] stays the user's path
        _stage("user_code")
        cpu_before = _process_cpu_s()
        try:
            runpy.run_path(run_path, run_name="__main__")
        finally:
            _USER_CPU[:] = [_process_cpu_s() - cpu_before]
            _stage("limits_restore")
    except SystemExit as e:
        exit_code = _exit_code_of(e)
    except _CpuTimeExceeded:
        # Restore first: the soft RLIMIT_CPU re-fires SIGXCPU every second
        # past the ceiling, and the next one must not land mid-report.
        restore_rlimits()
        traceback.print_exc()
        exit_code = 1
        violation = "cpu_time"
    except MemoryError:
        # Limits off first: after a window-exhausting MemoryError, the
        # traceback formatting itself needs allocation headroom.
        restore_rlimits()
        traceback.print_exc()
        exit_code = 1
        if mem_limited:
            violation = "oom"
    except BaseException:  # noqa: BLE001 — report, don't die
        restore_rlimits()
        traceback.print_exc()
        exit_code = 1
    finally:
        restore_rlimits()
        try:
            _signal.signal(_signal.SIGINT, saved_sigint)
        except (ValueError, TypeError):  # non-main thread / exotic handler
            pass
        sys.argv = saved_argv
        if trace_dir is not None:
            _annotate(False)
            _stage("profile_stop")
            # Inside the redirect so profiler chatter lands in the capture.
            _finish_profile(trace_dir)
        _stage("finish")
        try:
            sys.stdout.flush()
            sys.stderr.flush()
        except Exception:  # noqa: BLE001
            pass
        os.dup2(saved_out, 1)
        os.dup2(saved_err, 2)
        os.close(saved_out)
        os.close(saved_err)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if run_path != source_path:
            try:
                os.unlink(run_path)
            except OSError:
                pass
    return exit_code, violation


# ---------------------------------------------------------------------------
# Batched dispatch (the "op": "batch" request): N small jobs from ONE tenant
# run concurrently in this warm process, each thread pinned to its own
# device of the lane's local device set — the Anakin/Sebulba placement that
# keeps every chip of a multi-chip slice busy instead of idling 7/8 of it
# behind serial round-trips. One address space means env, rlimits, and the
# CPU budget are BATCH-level (the control plane only coalesces jobs whose
# env and limits are identical); stdout/stderr are demuxed per job via a
# thread-routing stream proxy, and each job thread gets a PRIVATE cwd via
# unshare(CLONE_FS) so relative-path file writes land in its own workdir.


class _StreamRouter:
    """sys.stdout/sys.stderr stand-in during a batched run: writes route to
    the calling thread's bound per-job capture file, falling back to the
    batch-level stream for main-thread/runner output. fd-level writes from
    C extensions bypass Python streams and land in the batch-level capture
    — the server surfaces batch-level stdout and the control plane then
    reruns the batch serially, so that output is never dropped."""

    def __init__(self, fallback) -> None:
        self._fallback = fallback
        self._local = threading.local()

    def bind(self, fh) -> None:
        self._local.fh = fh

    def unbind(self) -> None:
        self._local.fh = None

    @property
    def _target(self):
        return getattr(self._local, "fh", None) or self._fallback

    def write(self, data) -> int:
        return self._target.write(data)

    def writelines(self, lines) -> None:
        self._target.writelines(lines)

    def flush(self) -> None:
        try:
            self._target.flush()
        except ValueError:  # closed underlying file
            pass

    def isatty(self) -> bool:
        return False

    @property
    def encoding(self):
        return getattr(self._target, "encoding", "utf-8")

    def fileno(self) -> int:
        return self._fallback.fileno()


_CLONE_FS = 0x00000200


def _unshare_fs() -> bool:
    """Give the calling THREAD a private filesystem context (cwd/umask) via
    unshare(CLONE_FS), so concurrent batch jobs each chdir into their own
    workdir without racing. No privilege needed. False when unavailable
    (non-Linux libc, seccomp policy) — the job then runs from the shared
    workspace root and its relative-path writes are not demuxable."""
    try:
        import ctypes

        libc = ctypes.CDLL(None, use_errno=True)
        return libc.unshare(_CLONE_FS) == 0
    except Exception:  # noqa: BLE001
        return False


def _job_device_ctx(device_index, fallback_index: int):
    """Pin the job thread's jax dispatches to one local device (the batch's
    device-axis placement). jax config context managers are thread-local,
    so concurrent jobs land on distinct chips. Only a runner that never
    imported jax (APP_WARM_IMPORT_JAX=0: CPU-only jobs) gets a null
    context; with jax loaded a placement failure fails the job, it never
    silently lands every job on device 0."""
    import contextlib

    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    devices = jax.local_devices()
    index = device_index if isinstance(device_index, int) else fallback_index
    return jax.default_device(devices[index % len(devices)])


def _run_batch_job(index: int, job: dict, results: list, mem_limited: bool,
                   proxies: tuple, t_base: float,
                   want_memory: bool = False) -> None:
    """One job thread: bind capture files, isolate cwd, pin the device,
    exec the source. Never raises — the entry records the outcome (a
    per-job MemoryError under an armed budget is THIS job's typed oom
    violation; its batchmates never notice)."""
    proxy_out, proxy_err = proxies
    _set_trace_id(job.get("trace_id"))
    start = time.monotonic()
    entry = {
        "exit_code": 0,
        "start_offset_s": round(max(0.0, start - t_base), 6),
    }
    # Per-job device-memory bracket. One address space means concurrent
    # batchmates' allocations land inside each other's windows — the
    # per-job delta is best-effort under concurrency (documented on the
    # wire block); the batch-level peak stays exact.
    mem_probe = _DeviceMemoryProbe() if want_memory else None
    out = err = None
    try:
        out = open(job["stdout_path"], "w", buffering=1)
        err = open(job["stderr_path"], "w", buffering=1)
        proxy_out.bind(out)
        proxy_err.bind(err)
        isolated = _unshare_fs()
        if isolated:
            try:
                os.chdir(job["cwd"])
            except OSError:
                isolated = False
        entry["cwd_isolated"] = isolated
        if not isolated:
            _log(
                "batch job %d: no per-thread cwd isolation; relative-path "
                "writes land in the shared workspace" % index
            )
        source_path = job["source_path"]
        with open(source_path) as f:
            code = compile(f.read(), source_path, "exec")
        with _job_device_ctx(job.get("device_index"), index):
            exec(  # noqa: S102 — this IS the sandbox's purpose
                code,
                {
                    "__name__": "__main__",
                    "__file__": source_path,
                    "__builtins__": __builtins__,
                },
            )
    except SystemExit as e:
        entry["exit_code"] = _exit_code_of(e)
    except MemoryError:
        traceback.print_exc()  # routed to this job's stderr by the proxy
        entry["exit_code"] = 1
        if mem_limited:
            entry["violation"] = "oom"
    except BaseException:  # noqa: BLE001 — report, don't die
        traceback.print_exc()
        entry["exit_code"] = 1
    finally:
        entry["duration_s"] = round(time.monotonic() - start, 6)
        if mem_probe is not None:
            entry["device_memory"] = mem_probe.finish()
        proxy_out.unbind()
        proxy_err.unbind()
        for fh in (out, err):
            if fh is not None:
                try:
                    fh.close()
                except OSError:
                    pass
        _set_trace_id(None)
        results[index] = entry


def _run_batch(req: dict) -> dict:
    """Execute a coalesced batch: all jobs concurrently, one reply carrying
    per-job results. Batch-level state (env, rlimits, SIGINT handler, the
    fd-level redirect) is set up once around the whole run — the control
    plane only batches jobs whose env/limits are identical, so there is
    nothing per-job to disagree about."""
    jobs = req.get("jobs") or []
    if not jobs:
        return {"exit_code": -2, "error": "empty batch"}
    env = req.get("env") or {}
    env_to_set = {k: v for k, v in env.items() if k not in _OPERATOR_ONLY}
    saved_env = {k: os.environ.get(k) for k in env_to_set}
    os.environ.update({k: str(v) for k, v in env_to_set.items()})
    limits = req.get("limits") or {}
    mem_limited = (
        _request_limit(limits, "memory_bytes", _resolve_mem_budget()) > 0
    )
    # fd-level redirect to the batch capture (C-extension writes); Python-
    # level streams route per job through the proxies.
    out_fd = os.open(
        req["stdout_path"], os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644
    )
    err_fd = os.open(
        req["stderr_path"], os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644
    )
    saved_out, saved_err = os.dup(1), os.dup(2)
    sys.stdout.flush()
    sys.stderr.flush()
    os.dup2(out_fd, 1)
    os.dup2(err_fd, 2)
    os.close(out_fd)
    os.close(err_fd)
    fallback_out = os.fdopen(os.dup(1), "w", buffering=1)
    fallback_err = os.fdopen(os.dup(2), "w", buffering=1)
    proxy_out = _StreamRouter(fallback_out)
    proxy_err = _StreamRouter(fallback_err)
    prev_stdout, prev_stderr = sys.stdout, sys.stderr
    sys.stdout, sys.stderr = proxy_out, proxy_err
    restore_rlimits = _apply_user_rlimits(limits)
    import signal as _signal

    saved_sigint = _signal.getsignal(_signal.SIGINT)
    results: list = [None] * len(jobs)
    violation = None
    t_base = time.monotonic()
    want_memory = bool(req.get("device_memory"))
    threads = [
        threading.Thread(
            target=_run_batch_job,
            args=(i, job, results, mem_limited, (proxy_out, proxy_err), t_base,
                  want_memory),
            name=f"batch-job-{i}",
            daemon=True,
        )
        for i, job in enumerate(jobs)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    except _CpuTimeExceeded:
        # The batch's shared CPU budget ran out (the rlimit counts the
        # whole process — signal lands here, in the joining main thread,
        # unattributable to one job). Restore limits FIRST: the soft
        # ceiling re-fires every second past it.
        restore_rlimits()
        violation = "cpu_time"
    except MemoryError:
        restore_rlimits()
        if mem_limited:
            violation = "oom"
    except BaseException:  # noqa: BLE001 — report, don't die
        restore_rlimits()
        traceback.print_exc()
    finally:
        restore_rlimits()
        try:
            _signal.signal(_signal.SIGINT, saved_sigint)
        except (ValueError, TypeError):
            pass
        sys.stdout, sys.stderr = prev_stdout, prev_stderr
        for fh in (fallback_out, fallback_err):
            try:
                fh.close()
            except OSError:
                pass
        os.dup2(saved_out, 1)
        os.dup2(saved_err, 2)
        os.close(saved_out)
        os.close(saved_err)
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    aborted = violation is not None
    for i, entry in enumerate(results):
        if entry is None:
            # Thread never finished (batch-level abort while it ran): its
            # result is unusable — the control plane re-runs it serially.
            results[i] = {"exit_code": -1, "aborted": True}
    reply = {"jobs": results, "exit_code": 0}
    if violation:
        reply["violation"] = violation
    if aborted:
        reply["batch_aborted"] = True
    return reply


def _descendant_pids() -> list[int]:
    """All live descendants of this process, via one /proc scan (user code
    runs in-process, so anything it spawned is a child of the runner)."""
    children: dict[int, list[int]] = {}
    try:
        entries = os.listdir("/proc")
    except OSError:
        return []
    for entry in entries:
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat", "rb") as f:
                stat = f.read()
            # Fields after the parenthesized comm: state, ppid, ...
            ppid = int(stat.rsplit(b") ", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        children.setdefault(ppid, []).append(int(entry))
    victims: list[int] = []
    stack = [os.getpid()]
    while stack:
        for child in children.get(stack.pop(), []):
            victims.append(child)
            stack.append(child)
    return victims


def _reset(snapshot: dict) -> bool:
    """Scrub per-generation state so the warm process can serve a fresh
    sandbox: the device lease survives, the previous user's traces do not.

    Returns False when the process is NOT scrubbable — the control plane
    must dispose it instead of recycling. Unscrubbable today: user code left
    a live thread behind (it would keep running beside the next
    generation's code; threads cannot be killed from outside in CPython).

    The full gc (which releases the previous user's host+device buffers) is
    the caller's job AFTER acking the reset: in a jax-laden interpreter a
    full collection costs tens of ms, and running it post-ack lets it
    overlap the control plane's workspace wipe and pool bookkeeping instead
    of sitting on the next request's queue-wait.

    Residual-risk contract (documented, not silently assumed): in-place
    mutations of SHARED module state (e.g. ``json.loads = evil``) by hostile
    code are not detectable and not scrubbed — process reuse trades that
    sliver of isolation for the TPU lease surviving generations. Deployments
    executing mutually-hostile tenants should set
    APP_EXECUTOR_REUSE_SANDBOXES=0 and pay the respawn (the reference's
    single-use-pod model)."""
    import signal
    import threading
    import time

    victims = _descendant_pids()
    for pid in victims:
        try:
            os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    # Reap, not just kill: a zombie still "exists" to the next generation's
    # process checks. Direct children are waited for (bounded — SIGKILL is
    # prompt outside unkillable D-state); deeper descendants get reparented
    # and reaped by init once their parent dies.
    deadline = time.time() + 5.0
    for pid in victims:
        while True:
            try:
                done, _ = os.waitpid(pid, os.WNOHANG)
            except ChildProcessError:
                break  # not our direct child (or already reaped)
            if done == pid or time.time() > deadline:
                break
            time.sleep(0.01)
    # A thread the previous generation started would keep running beside —
    # and observing — the next generation's code; CPython cannot kill it.
    # Compare against the boot snapshot (jax may own internal Python
    # threads) and refuse the reset if anything new is still alive.
    survivors = [
        t
        for t in threading.enumerate()
        if t.is_alive() and t.ident not in snapshot["threads"]
    ]
    if survivors:
        _log(
            "reset refused: user thread(s) survived: "
            f"{[t.name for t in survivors]}"
        )
        return False
    # A module imported from the previous generation's workspace, exec
    # scratch, or auto-installed runtime-packages must not shadow the next
    # generation's — the server wipes runtime-packages on disk, so a stale
    # sys.modules entry would resurrect a package the wipe just removed.
    import tempfile

    workspace = snapshot["cwd"]
    # Exec scratch dirs live under TMPDIR (sandbox-private when the backend
    # provides one) — match wherever they actually are.
    prefixes = [workspace + os.sep, os.path.join(tempfile.gettempdir(), "exec-")]
    runtime_packages = os.environ.get("APP_RUNTIME_PACKAGES")
    if runtime_packages:
        prefixes.append(runtime_packages.rstrip(os.sep) + os.sep)
    for name, mod in list(sys.modules.items()):
        origin = getattr(mod, "__file__", None) or ""
        if any(origin.startswith(p) for p in prefixes):
            del sys.modules[name]
    os.environ.clear()
    os.environ.update(snapshot["environ"])
    try:
        os.chdir(workspace)
    except OSError:
        pass
    # User code may have rebound the stream objects (fd redirection in
    # _run_one restores fds, not Python-level bindings).
    sys.stdout, sys.stderr = sys.__stdout__, sys.__stderr__
    sys.path[:] = snapshot["path"]
    return True


# Interpreter-state serialization (session durability): the cross-turn
# state this runner actually carries. Per-turn globals do NOT persist
# (each turn runs under runpy with a fresh namespace), so what survives —
# and what a snapshot must capture — is exactly: env-var mutations made by
# user code, the working directory, and workspace-origin modules whose
# module-level globals user turns import and mutate. Device buffers are
# deliberately NOT captured: they re-materialize on first touch after a
# restore (recompute/reload is the contract, same as a process restart).
_STATE_VERSION = 1

# Values are pickled by ALLOWLIST, not by "whatever pickles": only plain
# data (scalars + containers thereof) rides a snapshot. Anything else —
# open files, sockets, threads, jax arrays, live objects of workspace
# classes — is skipped and honestly reported, never half-captured.
_PICKLE_SCALARS = (type(None), bool, int, float, complex, str, bytes)


def _plain_data(value: object, depth: int = 0) -> bool:
    if depth > 8:
        return False
    if isinstance(value, _PICKLE_SCALARS):
        return True
    if isinstance(value, (list, tuple, set, frozenset)):
        return all(_plain_data(v, depth + 1) for v in value)
    if isinstance(value, dict):
        return all(
            _plain_data(k, depth + 1) and _plain_data(v, depth + 1)
            for k, v in value.items()
        )
    return False


class _PlainUnpickler:
    """Restricted loads(): refuses any global lookup, so a corrupted or
    adversarial snapshot blob cannot instantiate arbitrary classes — plain
    data needs no globals at all."""

    def __init__(self) -> None:
        import io
        import pickle

        class Unpickler(pickle.Unpickler):
            def find_class(self, module, name):  # noqa: ARG002
                raise pickle.UnpicklingError(
                    f"snapshot state may not reference {module}.{name}"
                )

        self._io = io
        self._cls = Unpickler

    def loads(self, data: bytes) -> object:
        return self._cls(self._io.BytesIO(data)).load()


def _workspace_module_prefixes(snapshot: dict) -> list[str]:
    """Same selection rule _reset uses to scrub: a module is session state
    (not interpreter infrastructure) iff its file lives under the
    workspace, exec scratch, or auto-installed runtime-packages."""
    import tempfile

    workspace = snapshot["cwd"]
    prefixes = [workspace + os.sep, os.path.join(tempfile.gettempdir(), "exec-")]
    runtime_packages = os.environ.get("APP_RUNTIME_PACKAGES")
    if runtime_packages:
        prefixes.append(runtime_packages.rstrip(os.sep) + os.sep)
    return prefixes


def _installed_packages() -> list[str]:
    """Top-level names under the auto-install dir — recorded in the
    snapshot for honesty/observability (restore does NOT reinstall; the
    package FILES ride the workspace manifest like any other files)."""
    runtime_packages = os.environ.get("APP_RUNTIME_PACKAGES")
    if not runtime_packages:
        return []
    try:
        return sorted(os.listdir(runtime_packages))
    except OSError:
        return []


def _snapshot_state(snapshot: dict, req: dict) -> dict:
    """Serialize this runner's cross-turn interpreter state into a JSON
    document (op "snapshot"). Never raises on a weird value — skipped
    names are reported, the rest is captured."""
    import base64
    import pickle

    boot_env = snapshot["environ"]
    env_set = {
        k: v
        for k, v in os.environ.items()
        if boot_env.get(k) != v
    }
    env_del = sorted(k for k in boot_env if k not in os.environ)
    try:
        cwd = os.getcwd()
    except OSError:
        cwd = snapshot["cwd"]

    prefixes = _workspace_module_prefixes(snapshot)
    modules = []
    skipped: list[str] = []
    for name, mod in sorted(sys.modules.items()):
        origin = getattr(mod, "__file__", None) or ""
        if not any(origin.startswith(p) for p in prefixes):
            continue
        values = {}
        for attr, value in vars(mod).items():
            if attr.startswith("__"):
                continue
            if not _plain_data(value):
                skipped.append(f"{name}.{attr}")
                continue
            try:
                blob = pickle.dumps(value, protocol=2)
            except Exception:  # noqa: BLE001
                skipped.append(f"{name}.{attr}")
                continue
            values[attr] = base64.b64encode(blob).decode("ascii")
        modules.append({"name": name, "values": values})

    state = {
        "version": _STATE_VERSION,
        "env_set": env_set,
        "env_del": env_del,
        "cwd": cwd,
        "modules": modules,
        "packages": _installed_packages(),
        "skipped": sorted(skipped),
    }
    max_bytes = int(req.get("max_bytes") or 0)
    if max_bytes and len(json.dumps(state)) > max_bytes:
        return {"ok": False, "reason": "state_too_large"}
    return {"ok": True, "state": state}


def _restore_state(snapshot: dict, req: dict) -> dict:
    """Rehydrate a snapshot (op "restore") into this warm runner. The
    workspace files are ALREADY in place (they ride the manifest-delta
    upload path before this op fires); this re-imports workspace modules
    and overlays their captured globals, then replays env/cwd deltas.
    All-or-nothing per the trust model: a malformed state document is
    refused up front rather than half-applied."""
    import base64
    import importlib

    state = req.get("state")
    if not isinstance(state, dict) or state.get("version") != _STATE_VERSION:
        return {"ok": False, "reason": "bad_state_version"}

    loader = _PlainUnpickler()
    # Decode every blob BEFORE touching interpreter state: a corrupt pickle
    # refuses the whole restore instead of leaving a half-written session.
    decoded = []
    try:
        for entry in state.get("modules") or []:
            values = {
                attr: loader.loads(base64.b64decode(blob))
                for attr, blob in (entry.get("values") or {}).items()
            }
            decoded.append((entry["name"], values))
        env_set = dict(state.get("env_set") or {})
        env_del = list(state.get("env_del") or [])
        cwd = state.get("cwd")
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        return {"ok": False, "reason": "corrupt_state"}

    for k, v in env_set.items():
        os.environ[str(k)] = str(v)
    for k in env_del:
        os.environ.pop(k, None)
    if isinstance(cwd, str) and cwd:
        try:
            os.chdir(cwd)
        except OSError:
            pass

    # During a turn, workspace imports resolve however the user arranged
    # them (sys.path insert, cwd-relative tricks); between turns none of
    # that holds — pin the workspace root for the re-import pass only.
    workspace = snapshot["cwd"]
    added = workspace not in sys.path
    if added:
        sys.path.insert(0, workspace)
    skipped: list[str] = []
    try:
        for name, values in decoded:
            try:
                mod = importlib.import_module(name)
            except Exception:  # noqa: BLE001
                skipped.append(name)
                continue
            for attr, value in values.items():
                try:
                    setattr(mod, attr, value)
                except Exception:  # noqa: BLE001
                    skipped.append(f"{name}.{attr}")
    finally:
        if added:
            try:
                sys.path.remove(workspace)
            except ValueError:
                pass
    return {"ok": True, "skipped": sorted(skipped)}


def _start_server_watchdog() -> None:
    """Die the instant the executor server does — even while the main thread
    is blocked in jax init / jax.distributed rendezvous (where it cannot see
    the request pipe's EOF). POLLHUP on the request pipe fires when the
    server's write end closes; polling without POLLIN steals no request
    bytes from the main loop."""
    import select
    import threading

    def watch() -> None:
        poller = select.poll()
        poller.register(REQ_FD, 0)  # HUP/ERR/NVAL are always reported
        # POLLNVAL: user code closed fd 3 out from under us. The runner can
        # never receive another request, and without exiting on it poll()
        # would return NVAL instantly forever — a 100%-CPU busy spin.
        fatal = select.POLLHUP | select.POLLERR | select.POLLNVAL
        while True:
            for _, event in poller.poll():
                if event & fatal:
                    os._exit(0)

    threading.Thread(target=watch, name="server-watchdog", daemon=True).start()


def main() -> None:
    # Detach stdin; keep stdout/stderr (they reach the executor's log).
    devnull = os.open(os.devnull, os.O_RDONLY)
    os.dup2(devnull, 0)
    os.close(devnull)

    _start_server_watchdog()
    info = _warm_import()
    _send(info)
    if not info["ready"]:
        # Exit, never linger: a half-initialized runner may hold the chip.
        # atexit is skipped (jax.distributed's shutdown barrier would block
        # on peers that are dying too).
        os._exit(1)
    # Boot snapshot for generation resets — taken AFTER the warm import so
    # anything jax init itself set (TPU env, worker threads) persists and
    # is never misread as user residue.
    import threading

    snapshot = {
        "environ": dict(os.environ),
        "cwd": os.getcwd(),
        "path": list(sys.path),
        "threads": {t.ident for t in threading.enumerate()},
    }

    buf = b""
    while True:
        try:
            chunk = os.read(REQ_FD, 65536)
        except KeyboardInterrupt:
            # The server's cooperative-cancellation SIGINT raced the user
            # code finishing: it landed here, between requests. Dying now
            # would throw away a healthy runner (and its device lease) over
            # a request that already completed — swallow and keep serving.
            continue
        if not chunk:
            # Server is gone; this sandbox is dead. Skip atexit — nothing
            # needs flushing, and jax.distributed's shutdown barrier would
            # block for minutes waiting for peers that are dying too.
            os._exit(0)
        buf += chunk
        while b"\n" in buf:
            line, buf = buf.split(b"\n", 1)
            if not line.strip():
                continue
            line_read = time.monotonic()
            req = None
            replied = False

            def _reply(obj):
                nonlocal replied
                replied = True
                _send(obj)

            def _reply_error():
                if replied:
                    return
                op = req.get("op") if isinstance(req, dict) else None
                if op in ("reset", "snapshot", "restore"):
                    _reply({"ok": False})
                else:
                    _reply({"exit_code": -2})

            try:
                req = json.loads(line)
                if req.get("op") == "reset":
                    _begin_stages("scrub", line_read)
                    ok = _reset(snapshot)
                    _take_shim()  # the next tenant's counters start at zero
                    reply = {"ok": ok}
                    stages = _take_stages(req.get("sent_mono"))
                    if stages is not None:
                        reply["stages"] = stages
                    _reply(reply)
                    if ok:
                        import gc

                        # Post-ack: drop the previous generation's host and
                        # device buffers while the server wipes the
                        # workspace — off the next request's critical path
                        # unless that request is already waiting, which its
                        # `pickup` and this `gc_after_reset` then show.
                        gc_started = time.monotonic()
                        gc.collect()
                        _GC_AFTER_RESET[:] = [
                            (gc_started, time.monotonic() - gc_started)
                        ]
                elif req.get("op") == "snapshot":
                    _reply(_snapshot_state(snapshot, req))
                elif req.get("op") == "restore":
                    _reply(_restore_state(snapshot, req))
                elif req.get("op") == "batch":
                    _set_trace_id(req.get("trace_id"))
                    hits_before, misses_before = _cache_counts()
                    reply = _run_batch(req)
                    if _CACHE_LISTENING:
                        hits_after, misses_after = _cache_counts()
                        reply["cache_hits"] = hits_after - hits_before
                        reply["cache_misses"] = misses_after - misses_before
                    _set_trace_id(None)
                    _reply(reply)
                else:
                    _begin_stages("prepare", line_read)
                    _set_trace_id(req.get("trace_id"))
                    hits_before, misses_before = _cache_counts()
                    # Device-memory bracket around the run, only when the
                    # control plane asked (the perf-observer kill switch
                    # keeps the wire — and the sampling cost — untouched).
                    mem_probe = (
                        _DeviceMemoryProbe()
                        if req.get("device_memory")
                        else None
                    )
                    exit_code, violation = _run_one(req)
                    reply = {"exit_code": exit_code}
                    user_cpu = _take_user_cpu()
                    if user_cpu is not None:
                        reply["user_cpu_s"] = user_cpu
                    shim = _take_shim()
                    if shim is not None:
                        reply["shim"] = shim
                    if mem_probe is not None:
                        reply["device_memory"] = mem_probe.finish()
                    if violation:
                        reply["violation"] = violation
                    if _CACHE_LISTENING:
                        hits_after, misses_after = _cache_counts()
                        reply["cache_hits"] = hits_after - hits_before
                        reply["cache_misses"] = misses_after - misses_before
                    stages = _take_stages(req.get("sent_mono"))
                    if stages is not None:
                        reply["stages"] = stages
                    _set_trace_id(None)
                    _reply(reply)
            except KeyboardInterrupt:
                # The cancellation SIGINT raced past user code and landed in
                # RUNNER code (dispatch, _send, _run_one's unwind after the
                # handler was restored). The request it aimed at is already
                # over — answer whatever request is in flight (never twice)
                # and keep the process, and its device lease, alive.
                _reply_error()
            except Exception:  # noqa: BLE001
                traceback.print_exc()
                _reply_error()


if __name__ == "__main__":
    main()
