"""Kubernetes backend + kubectl adapter against a fake kubectl binary.

The fake records every invocation (argv + stdin) into a directory and plays
back canned responses, so manifest shape, TPU scheduling fields, wait/delete
flows, and error paths are all testable without a cluster — the gap the
reference left open (SURVEY.md §4: no unit layer, no fake backends).
"""

import json
import os
import stat
from pathlib import Path

import pytest

from bee_code_interpreter_fs_tpu.config import Config
from bee_code_interpreter_fs_tpu.services.backends.base import SandboxSpawnError
from bee_code_interpreter_fs_tpu.services.backends.kubernetes import (
    KubernetesSandboxBackend,
    deep_merge,
)
from bee_code_interpreter_fs_tpu.services.kubectl import Kubectl, KubectlError

FAKE_KUBECTL = r"""#!/usr/bin/env python3
import json, os, sys
state = os.environ["FAKE_KUBECTL_DIR"]
stdin = sys.stdin.read() if not sys.stdin.isatty() else ""
with open(os.path.join(state, "calls.jsonl"), "a") as f:
    f.write(json.dumps({"argv": sys.argv[1:], "stdin": stdin}) + "\n")
args = sys.argv[1:]
verb = args[0] if args else ""
if os.path.exists(os.path.join(state, "fail_" + verb)):
    sys.stderr.write(verb + " exploded\n")
    sys.exit(1)
if verb == "create":
    manifest = json.loads(stdin)
    with open(os.path.join(state, manifest["metadata"]["name"] + ".json"), "w") as f:
        json.dump(manifest, f)
    print(json.dumps(manifest))
elif verb == "get":
    name = args[2] if len(args) > 2 and not args[2].startswith("-") else None
    path = os.path.join(state, (name or "none") + ".json")
    if name and os.path.exists(path):
        manifest = json.load(open(path))
        manifest.setdefault("status", {})["podIP"] = "10.0.0.7"
        status_path = os.path.join(state, "status.json")
        if os.path.exists(status_path):
            manifest["status"].update(json.load(open(status_path)))
        manifest["metadata"]["uid"] = "uid-" + name
        print(json.dumps(manifest))
    else:
        sys.stderr.write("NotFound\n")
        sys.exit(1)
elif verb == "wait":
    print("pod condition met")
elif verb == "delete":
    print("pod deleted")
elif verb == "logs":
    logs_path = os.path.join(state, "logs.txt")
    if os.path.exists(logs_path):
        print(open(logs_path).read())
    else:
        sys.stderr.write("no logs\n")
        sys.exit(1)
else:
    sys.exit(2)
"""


@pytest.fixture
def fake_kubectl(tmp_path, monkeypatch):
    state = tmp_path / "state"
    state.mkdir()
    binary = tmp_path / "kubectl"
    binary.write_text(FAKE_KUBECTL)
    binary.chmod(binary.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("FAKE_KUBECTL_DIR", str(state))
    monkeypatch.delenv("HOSTNAME", raising=False)

    def calls():
        path = state / "calls.jsonl"
        if not path.exists():
            return []
        return [json.loads(line) for line in path.read_text().splitlines()]

    return Kubectl(binary=str(binary)), state, calls


async def _await_calls(calls, predicate, *, timeout=10.0, settle=0.2):
    """Deadline-poll the fake-kubectl call log until `predicate(calls())` is
    truthy, then hold one settle interval so a spurious LATE extra call
    (e.g. a double-delete regression) still fails the caller's exact
    asserts. Replaces the fixed 0.2s sleeps that flaked whenever a loaded
    host ran the fire-and-forget delete subprocesses slowly (the recurring
    F's documented in CHANGES.md)."""
    import asyncio

    deadline = asyncio.get_running_loop().time() + timeout
    while not predicate(calls()) and asyncio.get_running_loop().time() < deadline:
        await asyncio.sleep(0.05)
    await asyncio.sleep(settle)
    return calls()


def _backend(kubectl, **config_kwargs) -> KubernetesSandboxBackend:
    config = Config(
        tpu_node_selector={
            "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
            "cloud.google.com/gke-tpu-topology": "2x2",
        },
        **config_kwargs,
    )
    return KubernetesSandboxBackend(config, kubectl=kubectl)


async def test_spawn_cpu_pod(fake_kubectl):
    kubectl, state, calls = fake_kubectl
    backend = _backend(kubectl)
    sandbox = await backend.spawn(chip_count=0)
    assert sandbox.url == "http://10.0.0.7:8000"
    manifest = json.loads((state / (sandbox.id + ".json")).read_text())
    container = manifest["spec"]["containers"][0]
    assert manifest["metadata"]["labels"]["app"] == "code-executor"
    assert "nodeSelector" not in manifest["spec"]
    assert "google.com/tpu" not in json.dumps(container["resources"])
    verbs = [c["argv"][0] for c in calls()]
    assert verbs == ["create", "wait", "get"]


async def test_spawn_tpu_pod_gets_chips_and_selector(fake_kubectl):
    kubectl, state, _ = fake_kubectl
    backend = _backend(kubectl)
    sandbox = await backend.spawn(chip_count=4)
    manifest = json.loads((state / (sandbox.id + ".json")).read_text())
    container = manifest["spec"]["containers"][0]
    assert container["resources"]["limits"]["google.com/tpu"] == "4"
    assert container["resources"]["requests"]["google.com/tpu"] == "4"
    assert (
        manifest["spec"]["nodeSelector"]["cloud.google.com/gke-tpu-topology"] == "2x2"
    )
    env = {e["name"]: e["value"] for e in container["env"]}
    assert env["APP_CHIP_COUNT"] == "4"
    assert env["APP_NUMPY_DISPATCH"] == "1"


async def test_pod_spec_extra_merges(fake_kubectl):
    kubectl, state, _ = fake_kubectl
    backend = _backend(
        kubectl,
        executor_pod_spec_extra={
            "tolerations": [{"key": "google.com/tpu", "operator": "Exists"}],
            "containers": [],  # list merge keeps the executor container
        },
        executor_container_resources={"limits": {"memory": "2Gi"}},
    )
    sandbox = await backend.spawn(chip_count=4)
    manifest = json.loads((state / (sandbox.id + ".json")).read_text())
    assert manifest["spec"]["tolerations"][0]["key"] == "google.com/tpu"
    limits = manifest["spec"]["containers"][0]["resources"]["limits"]
    assert limits == {"memory": "2Gi", "google.com/tpu": "4"}


def test_compile_cache_volume_mounted(fake_kubectl):
    """The cache dir is a real volume (emptyDir by default), not an env var
    pointing at the container overlay: the pod-side path is guaranteed
    writable and survives container restarts within the pod."""
    kubectl, _, _ = fake_kubectl
    backend = _backend(kubectl)
    manifest = backend.pod_manifest("p", 0, None)
    cache_dir = backend.config.jax_compilation_cache_dir
    assert manifest["spec"]["volumes"] == [
        {"name": "jax-compile-cache", "emptyDir": {}}
    ]
    container = manifest["spec"]["containers"][0]
    assert container["volumeMounts"] == [
        {"name": "jax-compile-cache", "mountPath": cache_dir}
    ]
    env = {e["name"]: e["value"] for e in container["env"]}
    assert env["JAX_COMPILATION_CACHE_DIR"] == cache_dir
    assert env["APP_COMPILE_CACHE"] == "1"
    # emptyDir is pod-private: per-sandbox taint vouches for it, so the
    # executor's harvest gate sees a private dir.
    assert backend.compile_cache_dir_scope == "private"


def test_compile_cache_volume_source_knob(fake_kubectl):
    kubectl, _, _ = fake_kubectl
    backend = _backend(
        kubectl,
        compile_cache_volume_source={
            "persistentVolumeClaim": {"claimName": "fleet-jax-cache"}
        },
    )
    manifest = backend.pod_manifest("p", 0, None)
    assert manifest["spec"]["volumes"][0]["persistentVolumeClaim"] == {
        "claimName": "fleet-jax-cache"
    }
    # A shared PVC is writable by other pods' tenants — parties this
    # control plane never sees — so the harvest gate must see "external"
    # (structurally never harvested).
    assert backend.compile_cache_dir_scope == "external"


def test_compile_cache_kill_switch_reaches_pod_env(fake_kubectl):
    kubectl, _, _ = fake_kubectl
    backend = _backend(kubectl, compile_cache_enabled=False)
    manifest = backend.pod_manifest("p", 0, None)
    container = manifest["spec"]["containers"][0]
    env = {e["name"]: e["value"] for e in container["env"]}
    # The per-pod cache dir still works host-locally; only the fleet
    # endpoints are off.
    assert env["APP_COMPILE_CACHE"] == "0"
    # No volume at the cache dir when the cache is disabled: the executor's
    # reset preserve is off, so a mounted-but-unpreserved cache dir under
    # /var/tmp would survive each wipe as an empty mount point (the wipe
    # forgives the mount's EBUSY) — skipping the mount restores the exact
    # pre-cache pod spec and turnover instead.
    assert "volumes" not in manifest["spec"]
    assert "volumeMounts" not in container
    # /var/tmp stays on the wipe list: with no mount the cache dir is
    # ordinary residue, removed at turnover — exact pre-cache behavior.
    assert "/var/tmp" in env["APP_RESET_EXTRA_WIPE_DIRS"]


def test_no_cache_dir_means_no_volume(fake_kubectl):
    kubectl, _, _ = fake_kubectl
    backend = _backend(kubectl, jax_compilation_cache_dir="")
    manifest = backend.pod_manifest("p", 0, None)
    assert "volumes" not in manifest["spec"]
    container = manifest["spec"]["containers"][0]
    assert "volumeMounts" not in container
    env_names = {e["name"] for e in container["env"]}
    assert "JAX_COMPILATION_CACHE_DIR" not in env_names


async def test_spawn_failure_deletes_pod(fake_kubectl):
    kubectl, state, calls = fake_kubectl
    (state / "fail_wait").touch()
    backend = _backend(kubectl)
    with pytest.raises(SandboxSpawnError):
        await backend.spawn(chip_count=0)
    # Fire-and-forget delete: poll with a deadline instead of a fixed sleep.
    seen = await _await_calls(
        calls, lambda cs: any(c["argv"][0] == "delete" for c in cs)
    )
    assert "delete" in [c["argv"][0] for c in seen]


async def test_spawn_failure_includes_pod_diagnostics(fake_kubectl):
    """A failed spawn must carry WHY: pod phase/conditions/container state
    plus the kubectl-logs tail — the k8s analogue of the local backend's
    stderr tail (VERDICT r2 #7)."""
    kubectl, state, calls = fake_kubectl
    (state / "fail_wait").touch()
    (state / "status.json").write_text(
        json.dumps(
            {
                "phase": "Pending",
                "conditions": [
                    {
                        "type": "Ready",
                        "status": "False",
                        "reason": "ContainersNotReady",
                        "message": "containers with unready status: [executor]",
                    }
                ],
                "containerStatuses": [
                    {
                        "name": "executor",
                        "state": {
                            "waiting": {
                                "reason": "CrashLoopBackOff",
                                "message": "back-off 40s restarting failed container",
                            }
                        },
                    }
                ],
            }
        )
    )
    (state / "logs.txt").write_text(
        "RuntimeError: TPU initialization failed: device busy\n"
    )
    backend = _backend(kubectl)
    with pytest.raises(SandboxSpawnError) as exc_info:
        await backend.spawn(chip_count=0)
    message = str(exc_info.value)
    assert "did not become ready" in message
    assert "phase=Pending" in message
    assert "CrashLoopBackOff" in message
    assert "TPU initialization failed: device busy" in message
    await backend.close()  # drain the fire-and-tracked failure-path delete


async def test_spawn_failure_diagnostics_degrade_gracefully(fake_kubectl):
    """Logs/status fetch failures must not mask the original error."""
    kubectl, state, calls = fake_kubectl
    (state / "fail_wait").touch()
    (state / "fail_get").touch()  # no logs.txt either -> logs verb fails
    backend = _backend(kubectl)
    with pytest.raises(SandboxSpawnError) as exc_info:
        await backend.spawn(chip_count=0)
    message = str(exc_info.value)
    assert "did not become ready" in message
    assert "pod status unavailable" in message
    assert "pod logs unavailable" in message
    await backend.close()  # drain the fire-and-tracked failure-path delete


async def test_delete_and_close(fake_kubectl):
    kubectl, state, calls = fake_kubectl
    backend = _backend(kubectl)
    s1 = await backend.spawn()
    s2 = await backend.spawn()
    await backend.delete(s1)
    await backend.close()
    deletes = [c["argv"] for c in calls() if c["argv"][0] == "delete"]
    deleted = {argv[2] for argv in deletes}
    assert deleted == {s1.id, s2.id}
    assert any("--ignore-not-found" in argv for argv in deletes[0:1])


async def test_owner_reference_attached_in_cluster(fake_kubectl, monkeypatch):
    kubectl, state, _ = fake_kubectl
    # Pretend we run as pod "control-plane-0".
    (state / "control-plane-0.json").write_text(
        json.dumps({"metadata": {"name": "control-plane-0"}})
    )
    monkeypatch.setenv("HOSTNAME", "control-plane-0")
    backend = _backend(kubectl)
    sandbox = await backend.spawn()
    manifest = json.loads((state / (sandbox.id + ".json")).read_text())
    owner = manifest["metadata"]["ownerReferences"][0]
    assert owner["name"] == "control-plane-0"
    assert owner["uid"] == "uid-control-plane-0"


async def test_kubectl_error_surface(fake_kubectl):
    kubectl, state, _ = fake_kubectl
    (state / "fail_create").touch()
    backend = _backend(kubectl)
    with pytest.raises(SandboxSpawnError, match="create failed"):
        await backend.spawn()


async def test_kubectl_flags_and_json(fake_kubectl):
    kubectl, state, calls = fake_kubectl
    ns = Kubectl(binary=kubectl.binary, namespace="bee")
    await ns.wait("pod", "p1", **{"for": "condition=Ready"}, timeout="60s")
    argv = calls()[-1]["argv"]
    assert argv[:2] == ["wait", "pod/p1"]
    assert "--namespace=bee" in argv
    assert "--for=condition=Ready" in argv
    assert "--timeout=60s" in argv


def test_deep_merge():
    base = {"a": {"x": 1}, "list": [1], "keep": True}
    extra = {"a": {"y": 2}, "list": [2], "new": "v"}
    assert deep_merge(base, extra) == {
        "a": {"x": 1, "y": 2},
        "list": [1, 2],
        "keep": True,
        "new": "v",
    }


# ----------------------------------------------------------- multi-host slices


async def test_spawn_multihost_group(fake_kubectl):
    """chip_count > chips-per-host → one pod per host, coordinator bootstrap
    (SURVEY.md §7.6): pod 0 is created first, peers get its IP as the
    jax.distributed coordinator address, every pod requests only its own
    host's chips, and the Sandbox aggregates all host URLs."""
    kubectl, state, calls = fake_kubectl
    backend = _backend(kubectl, tpu_chips_per_host=4, coordinator_port=8476)
    sandbox = await backend.spawn(chip_count=8)

    assert sandbox.chip_count == 8
    assert sandbox.num_hosts == 2
    assert sandbox.host_urls == ["http://10.0.0.7:8000", "http://10.0.0.7:8000"]
    assert sandbox.url == sandbox.host_urls[0]
    assert sandbox.meta["pods"] == [f"{sandbox.id}-h0", f"{sandbox.id}-h1"]

    manifests = [
        json.loads((state / f"{sandbox.id}-h{i}.json").read_text()) for i in range(2)
    ]
    for i, manifest in enumerate(manifests):
        container = manifest["spec"]["containers"][0]
        # each host requests its own 4 chips, not the slice's 8
        assert container["resources"]["limits"]["google.com/tpu"] == "4"
        env = {e["name"]: e["value"] for e in container["env"]}
        assert env["APP_NUM_HOSTS"] == "2"
        assert env["APP_HOST_ID"] == str(i)
        assert manifest["metadata"]["labels"]["code-executor/slice-group"] == sandbox.id
        # libtpu single-slice multi-host worker identity + stable DNS names
        assert env["TPU_WORKER_ID"] == str(i)
        assert env["TPU_WORKER_HOSTNAMES"] == (
            f"{sandbox.id}-h0.{sandbox.id},{sandbox.id}-h1.{sandbox.id}"
        )
        assert manifest["spec"]["hostname"] == f"{sandbox.id}-h{i}"
        assert manifest["spec"]["subdomain"] == sandbox.id
    env0 = {e["name"]: e["value"] for e in manifests[0]["spec"]["containers"][0]["env"]}
    env1 = {e["name"]: e["value"] for e in manifests[1]["spec"]["containers"][0]["env"]}
    assert env0["APP_COORDINATOR_ADDR"] == "0.0.0.0:8476"  # host 0 binds
    assert env1["APP_COORDINATOR_ADDR"] == "10.0.0.7:8476"  # peers dial host 0

    # the headless service gives not-yet-Ready pods resolvable names
    service = json.loads((state / f"{sandbox.id}.json").read_text())
    assert service["kind"] == "Service"
    assert service["spec"]["clusterIP"] == "None"
    assert service["spec"]["publishNotReadyAddresses"] is True
    assert service["spec"]["selector"] == {
        "code-executor/slice-group": sandbox.id
    }

    # service → pod 0 created → IP polled → peer created → both waited on
    verbs = [c["argv"][0] for c in calls()]
    assert verbs[0] == "create"  # the service
    assert verbs[1] == "create"  # pod 0
    assert "get" in verbs[2:verbs.index("create", 2)]  # IP poll before peer create
    assert verbs.count("create") == 3
    assert verbs.count("wait") == 2


async def test_multihost_topology_selector_by_slice_size(fake_kubectl):
    """ADVICE r1 #1: the slice's TOTAL chip count picks the node topology —
    a static single-host selector would scatter group pods across unrelated
    slices where the ICI mesh cannot form."""
    kubectl, state, _ = fake_kubectl
    backend = _backend(
        kubectl,
        tpu_chips_per_host=4,
        tpu_node_selector_by_chip_count={
            "8": {
                "cloud.google.com/gke-tpu-accelerator": "tpu-v5-lite-podslice",
                "cloud.google.com/gke-tpu-topology": "2x4",
            }
        },
    )
    sandbox = await backend.spawn(chip_count=8)
    for i in range(2):
        manifest = json.loads((state / f"{sandbox.id}-h{i}.json").read_text())
        assert (
            manifest["spec"]["nodeSelector"]["cloud.google.com/gke-tpu-topology"]
            == "2x4"
        )
    # single-host spawns keep the static selector
    single = await backend.spawn(chip_count=4)
    manifest = json.loads((state / f"{single.id}.json").read_text())
    assert (
        manifest["spec"]["nodeSelector"]["cloud.google.com/gke-tpu-topology"]
        == "2x2"
    )


async def test_multihost_delete_removes_all_pods(fake_kubectl):
    kubectl, state, calls = fake_kubectl
    backend = _backend(kubectl, tpu_chips_per_host=4)
    sandbox = await backend.spawn(chip_count=16)
    assert sandbox.num_hosts == 4
    await backend.delete(sandbox)
    # The headless-service delete is fire-and-tracked: poll for the full
    # expected set (4 pods + the service) instead of a fixed sleep.
    expected = {f"{sandbox.id}-h{i}" for i in range(4)} | {sandbox.id}
    seen = await _await_calls(
        calls,
        lambda cs: {c["argv"][2] for c in cs if c["argv"][0] == "delete"}
        >= expected,
    )
    deleted = {c["argv"][2] for c in seen if c["argv"][0] == "delete"}
    assert deleted == expected


async def test_multihost_spawn_failure_cleans_whole_group(fake_kubectl):
    kubectl, state, calls = fake_kubectl
    (state / "fail_wait").touch()
    backend = _backend(kubectl, tpu_chips_per_host=4)
    with pytest.raises(SandboxSpawnError):
        await backend.spawn(chip_count=8)
    seen = await _await_calls(
        calls,
        lambda cs: len({c["argv"][2] for c in cs if c["argv"][0] == "delete"})
        >= 3,
    )
    deleted = {c["argv"][2] for c in seen if c["argv"][0] == "delete"}
    # both pods AND the group's headless service: no partial slices left
    assert len(deleted) == 3


def test_num_hosts_for_tiling():
    from bee_code_interpreter_fs_tpu.services.backends.base import num_hosts_for

    assert num_hosts_for(0, 4) == 1      # CPU lane
    assert num_hosts_for(1, 4) == 1      # sub-host slice (v5e-1)
    assert num_hosts_for(4, 4) == 1      # full host
    assert num_hosts_for(8, 4) == 2
    assert num_hosts_for(16, 4) == 4
    with pytest.raises(ValueError, match="does not tile"):
        num_hosts_for(6, 4)              # would silently reserve 8 chips
    with pytest.raises(ValueError, match="does not tile"):
        num_hosts_for(9, 4)


async def test_non_tiling_chip_count_rejected_before_spawn(fake_kubectl, tmp_path):
    from bee_code_interpreter_fs_tpu.services.code_executor import CodeExecutor
    from bee_code_interpreter_fs_tpu.services.storage import Storage

    kubectl, state, calls = fake_kubectl
    backend = _backend(kubectl, tpu_chips_per_host=4)
    executor = CodeExecutor(backend, Storage(tmp_path / "storage"), backend.config)
    with pytest.raises(ValueError, match="does not tile"):
        await executor.execute("print(1)", chip_count=6)
    assert calls() == []  # rejected before any kubectl traffic
    await executor.close()


# ------------------------------------------- pod-watch breaker integration


async def test_group_watch_failures_feed_lane_breaker(fake_kubectl):
    """Satellite (ISSUE 2): multi-host pod-watch failures record one lane
    strike PER failed host watch, the moment the watch fails — not one
    aggregate strike when the whole group spawn surfaces."""
    from bee_code_interpreter_fs_tpu.services.circuit_breaker import BreakerBoard

    kubectl, state, _ = fake_kubectl
    (state / "fail_wait").touch()  # every readiness watch fails
    backend = _backend(kubectl, tpu_chips_per_host=4)
    board = BreakerBoard(failure_threshold=100, cooldown=60.0)
    backend.bind_breakers(board)
    with pytest.raises(SandboxSpawnError):
        await backend.spawn(chip_count=8)  # 2 hosts -> 2 failed watches
    assert board.lane(8)._failures == 2
    await backend.close()  # drain the fire-and-tracked failure-path deletes


def test_loop_teardown_with_undrained_deletes_ends(fake_kubectl):
    """A loop closed while the failure path's tracked deletes are still in
    flight (close() never awaited) must end, and the deletes must still
    reach kubectl: asyncio's teardown cancels every task, and a kubectl call
    cancelled mid-start used to wait forever."""
    import asyncio
    import threading
    import time

    kubectl, state, calls = fake_kubectl
    (state / "fail_wait").touch()

    async def failed_group_spawn():
        backend = _backend(kubectl, tpu_chips_per_host=4)
        with pytest.raises(SandboxSpawnError):
            await backend.spawn(chip_count=8)

    runner = threading.Thread(
        target=asyncio.run, args=(failed_group_spawn(),), daemon=True
    )
    runner.start()
    # A hang is forever, so the bound only has to outlast a loaded host's
    # eight kubectl starts.
    runner.join(timeout=120.0)
    assert not runner.is_alive()

    def deleted():
        return {c["argv"][2] for c in calls() if c["argv"][0] == "delete"}

    # The teardown cancels a delete between its Popen and the worker thread
    # picking up `communicate`: the kubectl process is started and runs to
    # its end, but nothing waits for it, so neither the thread pool's
    # shutdown nor the loop's end says when it has logged its call. Wait for
    # the calls themselves.
    deadline = time.monotonic() + 120.0
    while len(deleted()) < 3 and time.monotonic() < deadline:
        time.sleep(0.05)
    kubectl._threads.shutdown(wait=True)
    assert len(deleted()) == 3  # both pods and the group's headless service


async def test_single_host_watch_failure_leaves_strike_to_executor(fake_kubectl):
    """Single-host spawns surface ONE SandboxSpawnError that the executor's
    spawn ladder counts; the backend must not also record it (double
    strike)."""
    from bee_code_interpreter_fs_tpu.services.circuit_breaker import BreakerBoard

    kubectl, state, _ = fake_kubectl
    (state / "fail_wait").touch()
    backend = _backend(kubectl)
    board = BreakerBoard(failure_threshold=100, cooldown=60.0)
    backend.bind_breakers(board)
    with pytest.raises(SandboxSpawnError):
        await backend.spawn(chip_count=0)
    assert board.lane(0)._failures == 0


async def test_pod_ip_watch_aborts_when_lane_opens(fake_kubectl):
    """The coordinator pod-IP poll is breaker-aware: once the lane opens
    (e.g. a sibling's failures crossed the threshold), the watch aborts
    immediately instead of polling blind until its own timeout."""
    from bee_code_interpreter_fs_tpu.services.circuit_breaker import BreakerBoard

    kubectl, state, _ = fake_kubectl
    backend = _backend(kubectl, executor_pod_ready_timeout=30.0)
    board = BreakerBoard(failure_threshold=1, cooldown=60.0)
    backend.bind_breakers(board)
    board.lane(8).record_failure()  # opens at threshold 1
    with pytest.raises(SandboxSpawnError, match="circuit opened"):
        await backend._wait_pod_ip("nonexistent-pod", 8)


async def test_fault_wrapper_passes_breakers_through(fake_kubectl):
    from bee_code_interpreter_fs_tpu.services.backends.faults import (
        FaultInjectingBackend,
        FaultSpec,
    )
    from bee_code_interpreter_fs_tpu.services.circuit_breaker import BreakerBoard

    kubectl, _, _ = fake_kubectl
    inner = _backend(kubectl)
    wrapped = FaultInjectingBackend(inner, FaultSpec.parse("seed:1"))
    board = BreakerBoard()
    wrapped.bind_breakers(board)
    assert inner._breakers is board


async def test_pool_capacity_per_lane_overrides(fake_kubectl):
    """tpu_warm_pool_capacity_by_chip_count: the physical ceiling the
    autoscaler's dynamic targets are clamped under, declared per lane — a
    cluster with three 4-chip slices can pool three warm 4-chip pods while
    bigger lanes keep the flat default."""
    kubectl, _, _ = fake_kubectl
    backend = _backend(
        kubectl,
        tpu_warm_pool_capacity=1,
        tpu_warm_pool_capacity_by_chip_count={"4": 3},
    )
    assert backend.pool_capacity(0) is None  # CPU lanes stay unconstrained
    assert backend.pool_capacity(4) == 3
    assert backend.pool_capacity(8) == 1  # flat default
