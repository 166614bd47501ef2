"""Kubernetes sandbox backend: single-use executor pods on TPU-slice nodes.

Behavior parity with the reference's pod management
(src/code_interpreter/services/kubernetes_code_executor.py:203-279) —
ownerReferences for cascading GC (:230-239), ``app=code-executor`` label
(:227-229), random 6-char name suffix (:216-218), image/resources/pod-spec
merge hooks (:241-251), Ready wait with bounded timeout (:254-256), delete on
failed spawn (:257-261) — re-designed TPU-first:

- ``chip_count`` drives scheduling: the container gets a ``google.com/tpu``
  resource request/limit and the pod gets the configured TPU accelerator /
  topology nodeSelector, so a 4-chip lane actually lands on a v5e-4 slice.
- The executor container starts its warm JAX runner at boot (executor/
  runner.py), so pool residency time — not the Execute critical path —
  absorbs libtpu init; a shared JAX compilation-cache volume/path persists
  XLA compiles across pod generations (SURVEY.md §7 hard part #2).
- No path-joining accidents: the control plane talks to ``podIP:8000`` with
  workspace-relative paths (the reference's absolute-path collapse bug,
  SURVEY.md §0.4, does not exist here).
"""

from __future__ import annotations

import asyncio
import logging
import os
import uuid
from typing import Any

from ...config import Config
from ..kubectl import Kubectl, KubectlError
from ..limits import sandbox_limit_env
from .base import (
    ResetClient,
    Sandbox,
    SandboxBackend,
    SandboxSpawnError,
    num_hosts_for,
    reset_sandbox_over_http,
)

logger = logging.getLogger(__name__)

EXECUTOR_PORT = 8000


def _raise_first(results: list, group: str) -> None:
    """Surface the first failure from a settled gather as SandboxSpawnError."""
    failure = next((r for r in results if isinstance(r, BaseException)), None)
    if failure is None:
        return
    if isinstance(failure, SandboxSpawnError):
        raise failure
    raise SandboxSpawnError(f"slice group {group} spawn failed: {failure!r}")


def deep_merge(base: dict, extra: dict) -> dict:
    """Recursive dict merge (extra wins); lists are concatenated — matches
    how the reference splices ``executor_pod_spec_extra`` into the spec."""
    out = dict(base)
    for key, value in extra.items():
        if key in out and isinstance(out[key], dict) and isinstance(value, dict):
            out[key] = deep_merge(out[key], value)
        elif key in out and isinstance(out[key], list) and isinstance(value, list):
            out[key] = out[key] + value
        else:
            out[key] = value
    return out


class KubernetesSandboxBackend(SandboxBackend):
    def __init__(
        self,
        config: Config | None = None,
        *,
        kubectl: Kubectl | None = None,
        numpy_dispatch: bool = True,
    ) -> None:
        self.config = config or Config()
        self.kubectl = kubectl or Kubectl()
        self.numpy_dispatch = numpy_dispatch
        self._owner_ref: dict | None | bool = None  # None = not looked up yet
        self._owner_lock = asyncio.Lock()
        self._live: dict[str, Sandbox] = {}
        self._reset_client = ResetClient()
        self._cleanup_tasks: set[asyncio.Task] = set()
        self._breakers = None  # BreakerBoard, bound by the executor

    @property
    def compile_cache_dir_scope(self) -> str:
        """emptyDir (any config — sizeLimit/medium) is always pod-private,
        so per-sandbox taint vouches for the dir. Any other volume source
        (PVC/hostPath) can be written by OTHER pods' tenants — parties this
        control plane never sees — so nothing can vouch for it and harvest
        is structurally off ("external"). The shared volume itself already
        moves compiles across pods; harvest would add a cross-tenant
        admission channel, not coverage."""
        source = self.config.compile_cache_volume_source
        if not source or set(source) == {"emptyDir"}:
            return "private"
        return "external"

    def lease_scope(self, chip_count: int, sandbox=None) -> str:
        """Per-NODE lease scopes (the PR 13 carried follow-up): a sandbox
        whose pods' nodes are known leases `lane-<n>@node-a[+node-b...]`,
        so fencing a wedged host quarantines exactly that node's (or
        slice's node-set's) chips — replacements elsewhere in the lane
        keep serving, instead of the whole chip-count lane re-earning its
        clean-probe streak for one bad node. Callers without a sandbox
        (the executor's lane-level recovering gate) — and pods whose node
        the API never reported — get the coarse lane scope; the registry
        and wire format take any string, so no other layer changes."""
        if sandbox is not None:
            nodes = sandbox.meta.get("node_names")
            if isinstance(nodes, list):
                named = sorted(str(n) for n in nodes if n)
                if named:
                    return f"lane-{chip_count}@" + "+".join(named)
        return f"lane-{chip_count}"

    def bind_breakers(self, board) -> None:
        """Give the pod-watch path direct access to the executor's per-lane
        spawn breakers: a failed `kubectl wait` / IP-assignment watch counts
        a lane failure the moment it happens (a multi-host group spawn feeds
        one strike per failed host watch, not one for the whole group), and
        the pod-IP polling loop aborts as soon as the lane opens instead of
        retrying blind against a dead apiserver/nodepool."""
        self._breakers = board

    def _record_watch_failure(self, lane: int, error: Exception | None = None) -> None:
        if self._breakers is not None:
            self._breakers.lane(lane).record_failure()
            if error is not None:
                # Tell the executor's spawn ladder this failure already
                # counted: without the marker it would record the surfaced
                # SandboxSpawnError again (double strike per failure).
                error.breaker_recorded = True

    def _check_lane_open(self, lane: int) -> None:
        """Fail the watch fast when the lane's breaker is hard-open (opened
        by this watch's own strikes or a sibling host's)."""
        if self._breakers is not None and self._breakers.is_open(lane):
            spawn_error = SandboxSpawnError(
                f"lane-{lane} spawn circuit opened while watching pods; "
                "aborting watch"
            )
            # Not a NEW backend failure — the lane is already open; the
            # executor must not count the abort as another strike.
            spawn_error.breaker_recorded = True
            raise spawn_error

    def _delete_soon(self, name: str) -> None:
        """Fire-and-track pod deletion: off the caller's critical path (and
        safe inside CancelledError handlers), but guaranteed to be awaited by
        close() — a fire-and-FORGET delete can die with the event loop and
        leak the pod."""
        task = asyncio.get_running_loop().create_task(self.delete_by_name(name))
        self._cleanup_tasks.add(task)
        task.add_done_callback(self._cleanup_tasks.discard)

    # ------------------------------------------------------------ manifest

    async def _owner_reference(self) -> dict | None:
        """ownerReference to our own pod → orphaned executor pods are
        garbage-collected if the control plane dies (reference :230-239).
        Outside a cluster (no HOSTNAME pod), pods are simply unowned."""
        async with self._owner_lock:
            if self._owner_ref is None:
                hostname = os.environ.get("HOSTNAME", "")
                try:
                    me = await self.kubectl.get("pod", hostname) if hostname else None
                    self._owner_ref = me and {
                        "apiVersion": "v1",
                        "kind": "Pod",
                        "name": me["metadata"]["name"],
                        "uid": me["metadata"]["uid"],
                        "blockOwnerDeletion": False,
                    }
                except KubectlError:
                    logger.warning(
                        "could not resolve own pod %r; executor pods will be "
                        "unowned (no cascading GC)",
                        hostname,
                    )
                    self._owner_ref = False
            return self._owner_ref or None

    def _node_selector_for(self, slice_chip_count: int) -> dict:
        """Selector for the node shape that can host this SLICE: the
        per-chip-count map wins (a 2-host v5e-8 slice needs different
        topology nodes than a single-host v5e-4), else the static default."""
        by_count = self.config.tpu_node_selector_by_chip_count
        override = by_count.get(str(slice_chip_count)) or by_count.get(
            slice_chip_count
        )
        if override:
            return dict(override)
        return dict(self.config.tpu_node_selector)

    def pod_manifest(
        self,
        name: str,
        chip_count: int,
        owner: dict | None,
        *,
        env_extra: list[dict] | None = None,
        group: str | None = None,
        slice_chip_count: int | None = None,
        hostname: str | None = None,
        subdomain: str | None = None,
    ) -> dict:
        resources = deep_merge({}, self.config.executor_container_resources)
        spec: dict[str, Any] = {}
        if hostname:
            spec["hostname"] = hostname
        if subdomain:
            spec["subdomain"] = subdomain
        if chip_count > 0:
            tpu = self.config.tpu_resource_requests or {"google.com/tpu": None}
            chip_resources = {
                key: str(chip_count) if value is None else str(value)
                for key, value in tpu.items()
            }
            resources = deep_merge(
                resources,
                {"limits": dict(chip_resources), "requests": dict(chip_resources)},
            )
            selector = self._node_selector_for(slice_chip_count or chip_count)
            if selector:
                spec["nodeSelector"] = selector

        env = [
            {"name": "APP_LISTEN_ADDR", "value": f"0.0.0.0:{EXECUTOR_PORT}"},
            {
                "name": "APP_WARM_RUNNER",
                "value": "1" if self.config.executor_warm_runner else "0",
            },
            # Pods warm eagerly at boot (the default), but the in-server
            # runner ready budget must match the control plane's warm budget
            # — its 180s built-in default would give up on a slow TPU init
            # that /readyz and _ready_wait_seconds() are still waiting on.
            {
                "name": "APP_RUNNER_READY_TIMEOUT",
                "value": str(self.config.executor_warm_ready_timeout),
            },
            {"name": "APP_CHIP_COUNT", "value": str(chip_count)},
            # Pod reuse (generation turnover) must wipe every container-
            # private path user code can write outside the workspace:
            # /tmp (tempfile), ~/.local (pip --user lands on sys.path), and
            # /var/tmp — which now hosts the default compilation-cache dir,
            # whose subtree the executor preserves THROUGH this wipe (so
            # compiled kernels survive turnover while everything else a
            # tenant parked in /var/tmp does not).
            {
                "name": "APP_RESET_EXTRA_WIPE_DIRS",
                "value": "/tmp:~/.local:/var/tmp",
            },
        ]
        # Resource-governance caps (APP_LIMIT_* + the output cap). Container
        # resources still bound the pod as a whole; these add the TYPED
        # per-request enforcement (violation kinds) inside it.
        env.extend(
            {"name": name, "value": value}
            for name, value in sandbox_limit_env(self.config).items()
        )
        volumes: list[dict] = []
        volume_mounts: list[dict] = []
        if self.config.jax_compilation_cache_dir:
            env.append(
                {
                    "name": "JAX_COMPILATION_CACHE_DIR",
                    "value": self.config.jax_compilation_cache_dir,
                }
            )
            env.append(
                {
                    "name": "APP_COMPILE_CACHE",
                    "value": "1" if self.config.compile_cache_enabled else "0",
                }
            )
            if self.config.compile_cache_enabled:
                # A real volume at the cache dir, not just an env var into
                # the container overlay: the pod-side path is guaranteed
                # writable and survives container restarts within the pod.
                # The source is a knob — emptyDir by default; a PVC/hostPath
                # shares compiles across pods without any control-plane
                # seeding. A non-emptyDir source also turns fleet HARVEST
                # off (compile_cache_dir_scope == "external"): other pods'
                # tenants can write a shared volume, so per-sandbox
                # provenance can't vouch for its contents.
                # Cache DISABLED skips the mount entirely: the executor's
                # preserve is off then, so the reset wipe would empty the
                # mount each turnover (the wipe forgives the mount point's
                # EBUSY, so /reset still succeeds — but an empty mount
                # point would linger where pre-cache pods had nothing).
                # Without the mount the cache dir is an ordinary path under
                # /var/tmp that the wipe removes like any other residue —
                # exact pre-cache pod spec AND turnover.
                volumes.append(
                    {
                        "name": "jax-compile-cache",
                        **deep_merge(
                            {}, self.config.compile_cache_volume_source or
                            {"emptyDir": {}}
                        ),
                    }
                )
                volume_mounts.append(
                    {
                        "name": "jax-compile-cache",
                        "mountPath": self.config.jax_compilation_cache_dir,
                    }
                )
        if self.numpy_dispatch:
            env.append({"name": "APP_NUMPY_DISPATCH", "value": "1"})
        if env_extra:
            env.extend(env_extra)

        if volumes:
            spec = deep_merge(spec, {"volumes": volumes})
        spec = deep_merge(
            {
                "containers": [
                    {
                        "name": "executor",
                        "image": self.config.executor_image,
                        "ports": [{"containerPort": EXECUTOR_PORT}],
                        "env": env,
                        "resources": resources,
                        **(
                            {"volumeMounts": volume_mounts}
                            if volume_mounts
                            else {}
                        ),
                        # The server listens immediately; warm-up (libtpu
                        # init) runs in the background and /readyz turns 200
                        # only once the runner is hot — so pod Ready still
                        # means "TPU hot" without the server's existence
                        # depending on TPU init.
                        "readinessProbe": {
                            "httpGet": {"path": "/readyz", "port": EXECUTOR_PORT},
                            "periodSeconds": 2,
                            "failureThreshold": 300,
                        },
                        "livenessProbe": {
                            "httpGet": {"path": "/healthz", "port": EXECUTOR_PORT},
                            "periodSeconds": 10,
                            "failureThreshold": 6,
                        },
                    }
                ],
                "restartPolicy": "Never",
                **spec,
            },
            self.config.executor_pod_spec_extra,
        )
        metadata: dict[str, Any] = {
            "name": name,
            "labels": {
                "app": "code-executor",
                "code-executor/chip-count": str(chip_count),
            },
        }
        if group:
            metadata["labels"]["code-executor/slice-group"] = group
        if owner:
            metadata["ownerReferences"] = [owner]
        return {"apiVersion": "v1", "kind": "Pod", "metadata": metadata, "spec": spec}

    def _group_service_manifest(self, group: str, owner: dict | None) -> dict:
        """Headless Service giving a slice group's pods stable DNS names
        ({pod}.{group}) before they are Ready — required for
        TPU_WORKER_HOSTNAMES and usable by the jax.distributed bootstrap."""
        metadata: dict[str, Any] = {
            "name": group,
            "labels": {"app": "code-executor", "code-executor/slice-group": group},
        }
        if owner:
            metadata["ownerReferences"] = [owner]
        return {
            "apiVersion": "v1",
            "kind": "Service",
            "metadata": metadata,
            "spec": {
                "clusterIP": "None",
                "publishNotReadyAddresses": True,
                "selector": {"code-executor/slice-group": group},
                "ports": [
                    {"name": "executor", "port": EXECUTOR_PORT},
                    {"name": "coordinator", "port": self.config.coordinator_port},
                ],
            },
        }

    async def _create_service(self, manifest: dict) -> None:
        name = manifest["metadata"]["name"]
        try:
            await self.kubectl.create(manifest)
        except KubectlError as e:
            raise SandboxSpawnError(f"service {name} create failed: {e}") from e

    def _delete_service_soon(self, name: str) -> None:
        async def delete_service() -> None:
            try:
                await self.kubectl.delete("service", name, wait=False)
            except KubectlError as e:
                logger.warning("service %s delete failed: %s", name, e)

        task = asyncio.get_running_loop().create_task(delete_service())
        self._cleanup_tasks.add(task)
        task.add_done_callback(self._cleanup_tasks.discard)

    # ------------------------------------------------------------ lifecycle

    async def _create_pod(self, manifest: dict) -> None:
        """kubectl-create a pod, cancellation-safely: a cancel landing
        mid-create (service shutdown during prefill) does not kill the
        kubectl subprocess, which goes on to create the pod anyway — so on
        cancellation the create is allowed to finish in a tracked cleanup
        task and the resulting pod is deleted."""
        name = manifest["metadata"]["name"]
        create = asyncio.get_running_loop().create_task(self.kubectl.create(manifest))
        try:
            await asyncio.shield(create)
        except asyncio.CancelledError:
            async def finish_then_delete() -> None:
                try:
                    await create
                except Exception:  # noqa: BLE001 — create failed: nothing to delete
                    return
                await self.delete_by_name(name)

            task = asyncio.get_running_loop().create_task(finish_then_delete())
            self._cleanup_tasks.add(task)
            task.add_done_callback(self._cleanup_tasks.discard)
            raise
        except KubectlError as e:
            raise SandboxSpawnError(f"pod {name} create failed: {e}") from e

    def pool_capacity(self, chip_count: int) -> int | None:
        """TPU lanes hold at most `tpu_warm_pool_capacity` warm pods (each
        owns its chips while pooled); CPU lanes keep the configured target.
        `tpu_warm_pool_capacity_by_chip_count` overrides per lane — the
        physical ceiling a cluster with N same-topology slices declares so
        the autoscaler's dynamic targets have room to use them."""
        if chip_count <= 0:
            return None
        override = self.config.tpu_warm_pool_capacity_by_chip_count.get(
            str(chip_count)
        )
        if override is not None:
            return max(0, int(override))
        return self.config.tpu_warm_pool_capacity

    def _ready_wait_seconds(self) -> int:
        # Pod Ready gates on /readyz (warm runner hot), so the wait budget
        # must cover scheduling + image pull + TPU init — not just boot.
        budget = self.config.executor_pod_ready_timeout
        if self.config.executor_warm_runner:
            budget += self.config.executor_warm_ready_timeout
        return int(budget)

    async def _spawn_diagnostics(self, name: str) -> str:
        """Why did this pod fail? Status conditions + container states +
        kubectl-logs tail — the Kubernetes analogue of the local backend's
        stderr tail (a wedged jax/libtpu init leaves its traceback in the
        container log, and 'did not become ready' alone is undiagnosable;
        VERDICT r2 #7; reference streaming surface kubectl.py:190-193)."""
        parts: list[str] = []
        try:
            pod = await self.kubectl.get("pod", name)
            status = pod.get("status", {})
            if status.get("phase"):
                parts.append(f"phase={status['phase']}")
            conditions = [
                " ".join(
                    filter(
                        None,
                        (
                            f"{c.get('type')}={c.get('status')}",
                            c.get("reason"),
                            c.get("message"),
                        ),
                    )
                )
                for c in status.get("conditions", [])
            ]
            if conditions:
                parts.append("conditions: " + "; ".join(conditions))
            for cs in status.get("containerStatuses", []):
                state = cs.get("state", {})
                detail = state.get("waiting") or state.get("terminated")
                if detail:
                    parts.append(
                        f"container {cs.get('name')}: "
                        + " ".join(
                            filter(
                                None,
                                (detail.get("reason"), detail.get("message")),
                            )
                        )
                    )
        except Exception as e:  # noqa: BLE001 — diagnostics must never mask
            # the original spawn error (e.g. truncated kubectl JSON output
            # raising JSONDecodeError during an apiserver hiccup)
            parts.append(f"(pod status unavailable: {e})")
        try:
            logs = await self.kubectl.logs(name, tail=40)
            if logs.strip():
                parts.append("--- pod log tail ---\n" + logs.strip()[-1500:])
        except Exception as e:  # noqa: BLE001 — same: best-effort only
            parts.append(f"(pod logs unavailable: {e})")
        return "\n".join(parts)

    async def _wait_ready_ip(
        self, name: str, lane: int = 0, *, record: bool = False
    ) -> tuple[str, str]:
        """(podIP, nodeName) once the pod is Ready. The node name feeds
        `lease_scope`: fencing quarantines the NODE's chips, not the whole
        chip-count lane."""
        try:
            await self.kubectl.wait(
                "pod",
                name,
                **{"for": "condition=Ready"},
                timeout=f"{self._ready_wait_seconds()}s",
            )
            pod = await self.kubectl.get("pod", name)
            pod_ip = pod["status"].get("podIP")
            if not pod_ip:
                raise SandboxSpawnError(f"pod {name} Ready but has no podIP")
            return pod_ip, str(pod.get("spec", {}).get("nodeName") or "")
        except KubectlError as e:
            # Group spawns record a lane strike PER failed host watch, the
            # moment it happens — N dead pods of one slice are N independent
            # failures, not one aggregate strike when the whole spawn
            # surfaces. Single-host spawns leave the (single) strike to the
            # executor's spawn ladder — recording here too would double it.
            diagnostics = await self._spawn_diagnostics(name)
            spawn_error = SandboxSpawnError(
                f"pod {name} did not become ready: {e}"
                + (f"\n{diagnostics}" if diagnostics else "")
            )
            if record:
                self._record_watch_failure(lane, spawn_error)
            raise spawn_error from e

    async def _wait_pod_ip(self, name: str, lane: int = 0) -> str:
        """Poll until the pod is scheduled and addressable. Distinct from
        Ready: a multi-host coordinator pod can't pass its readiness probe
        until its peers join, but peers need its IP to be created at all.
        The poll is breaker-aware: once the lane opens (this watch's own
        failures or a sibling's), it aborts instead of polling blind."""
        deadline = (
            asyncio.get_running_loop().time() + self.config.executor_pod_ready_timeout
        )
        while True:
            self._check_lane_open(lane)
            try:
                pod = await self.kubectl.get("pod", name)
            except KubectlError as e:
                spawn_error = SandboxSpawnError(
                    f"pod {name} vanished while starting: {e}"
                )
                self._record_watch_failure(lane, spawn_error)
                raise spawn_error
            pod_ip = pod.get("status", {}).get("podIP")
            if pod_ip:
                return pod_ip
            if asyncio.get_running_loop().time() > deadline:
                spawn_error = SandboxSpawnError(
                    f"pod {name} was never assigned an IP"
                )
                self._record_watch_failure(lane, spawn_error)
                raise spawn_error
            await asyncio.sleep(0.5)

    async def spawn(self, chip_count: int = 0) -> Sandbox:
        num_hosts = num_hosts_for(chip_count, self.config.tpu_chips_per_host)
        if num_hosts > 1:
            return await self._spawn_group(chip_count, num_hosts)
        name = self.config.executor_pod_name_prefix + uuid.uuid4().hex[:6]
        owner = await self._owner_reference()
        await self._create_pod(self.pod_manifest(name, chip_count, owner))
        try:
            pod_ip, node_name = await self._wait_ready_ip(name)
        except (SandboxSpawnError, asyncio.CancelledError):
            # Failed or cancelled spawn must not leak a pod (reference
            # :257-261; cancellation happens on service shutdown).
            self._delete_soon(name)
            raise
        sandbox = Sandbox(
            id=name,
            url=f"http://{pod_ip}:{EXECUTOR_PORT}",
            chip_count=chip_count,
            meta={
                "pod_ip": pod_ip,
                "node_names": [node_name] if node_name else [],
            },
        )
        self._live[name] = sandbox
        logger.info("spawned executor pod %s (%d chips) at %s", name, chip_count, pod_ip)
        return sandbox

    async def _spawn_group(self, chip_count: int, num_hosts: int) -> Sandbox:
        """A multi-host TPU slice: one executor pod per host (SURVEY.md §7.6).

        Host 0 runs the jax.distributed coordinator; its IP must be known to
        the peers at creation, so pod 0 is created first, the peers are
        created as soon as it is scheduled, and only then does the group
        rendezvous — every pod turns Ready exactly when the whole slice's
        mesh is up (the readiness probe waits on the warm runner, which
        blocks in jax.distributed.initialize until all hosts join).
        """
        group = self.config.executor_pod_name_prefix + uuid.uuid4().hex[:6]
        names = [f"{group}-h{i}" for i in range(num_hosts)]
        chips_per_host = max(1, self.config.tpu_chips_per_host)
        owner = await self._owner_reference()
        coord_port = self.config.coordinator_port
        # Stable DNS names via a per-group headless Service (pods get
        # hostname/subdomain): libtpu's single-slice multi-host bootstrap
        # needs every worker to know its peers by stable name BEFORE any pod
        # is Ready, hence publishNotReadyAddresses.
        worker_hostnames = ",".join(f"{name}.{group}" for name in names)

        def host_env(host_id: int, coordinator: str) -> list[dict]:
            return [
                {"name": "APP_NUM_HOSTS", "value": str(num_hosts)},
                {"name": "APP_HOST_ID", "value": str(host_id)},
                {"name": "APP_COORDINATOR_ADDR", "value": coordinator},
                # GKE TPU worker identity: libtpu forms the ICI mesh across
                # hosts from these (single-slice multi-host bootstrap).
                {"name": "TPU_WORKER_ID", "value": str(host_id)},
                {"name": "TPU_WORKER_HOSTNAMES", "value": worker_hostnames},
            ]

        def pod(i: int, coordinator: str) -> dict:
            return self.pod_manifest(
                names[i],
                chips_per_host,
                owner,
                env_extra=host_env(i, coordinator),
                group=group,
                slice_chip_count=chip_count,
                hostname=names[i],
                subdomain=group,
            )

        try:
            await self._create_service(self._group_service_manifest(group, owner))
            # Host 0 binds the coordinator port itself; 0.0.0.0 is valid for
            # the binding side of jax.distributed.initialize.
            await self._create_pod(pod(0, f"0.0.0.0:{coord_port}"))
            coordinator_ip = await self._wait_pod_ip(names[0], chip_count)
            # return_exceptions on both gathers: every sibling create/wait
            # must settle before cleanup runs, or an in-flight create could
            # land after its delete and leak a pod holding TPU chips.
            created = await asyncio.gather(
                *(
                    self._create_pod(pod(i, f"{coordinator_ip}:{coord_port}"))
                    for i in range(1, num_hosts)
                ),
                return_exceptions=True,
            )
            _raise_first(created, group)
            ready = await asyncio.gather(
                *(
                    self._wait_ready_ip(n, chip_count, record=True)
                    for n in names
                ),
                return_exceptions=True,
            )
            _raise_first(ready, group)
        except (SandboxSpawnError, asyncio.CancelledError):
            for name in names:  # no partial slices
                self._delete_soon(name)
            self._delete_service_soon(group)
            raise
        ips = [ip for ip, _ in ready]
        node_names = sorted({node for _, node in ready if node})
        urls = [f"http://{ip}:{EXECUTOR_PORT}" for ip in ips]
        sandbox = Sandbox(
            id=group,
            url=urls[0],
            chip_count=chip_count,
            host_urls=urls,
            meta={
                "pods": names,
                "coordinator_ip": coordinator_ip,
                "node_names": node_names,
            },
        )
        self._live[group] = sandbox
        logger.info(
            "spawned executor slice group %s (%d hosts × %d chips) at %s",
            group,
            num_hosts,
            chips_per_host,
            ips,
        )
        return sandbox

    async def reset(self, sandbox: Sandbox) -> Sandbox | None:
        """Recycle a pod (or a whole slice group) across sandbox generations:
        POST /reset on every host scrubs the warm runner and wipes workspace +
        runtime-packages while the pod — and its TPU chips, which would take
        a full pod respawn + libtpu init to reacquire — stays hot. Any host
        refusing (runner killed on timeout, mid-rewarm) disqualifies the whole
        sandbox and the caller deletes it (the reference's per-request pod
        disposal, kubernetes_code_executor.py:263-279, becomes the fallback
        path rather than the rule)."""
        if not self.config.executor_reuse_sandboxes:
            return None
        if sandbox.id not in self._live:
            return None  # already deleted / unknown
        return await reset_sandbox_over_http(
            sandbox, self._reset_client, timeout=15.0
        )

    async def delete_by_name(self, name: str) -> None:
        self._live.pop(name, None)
        try:
            await self.kubectl.delete("pod", name, wait=False)
        except KubectlError as e:
            logger.warning("pod %s delete failed: %s", name, e)

    async def delete(self, sandbox: Sandbox) -> None:
        pods = sandbox.meta.get("pods")
        if pods:
            self._live.pop(sandbox.id, None)
            await asyncio.gather(*(self.delete_by_name(name) for name in pods))
            self._delete_service_soon(sandbox.id)
        else:
            await self.delete_by_name(sandbox.id)

    async def close(self) -> None:
        await self._reset_client.aclose()
        pending = list(self._cleanup_tasks)
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)
        await asyncio.gather(
            *(self.delete(sandbox) for sandbox in list(self._live.values())),
            return_exceptions=True,
        )
