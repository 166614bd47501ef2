"""Device-mesh construction.

TPU slices have a physical ICI topology (e.g. v5e-4 is a 2x2 ring); mapping
logical mesh axes onto it well decides whether collectives ride neighbor ICI
links or bounce across the slice. `jax.experimental.mesh_utils`'s
`create_device_mesh` knows the TPU topologies, so we delegate to it and only
solve the layer above: choosing a logical shape (dp, sp, tp) for a given
device count, and naming the axes consistently across the framework.

Axis conventions (used by models/ and __graft_entry__):
  dp — data parallel: batch is split, gradients all-reduced.
  sp — sequence/context parallel: sequence dimension split (ring attention).
  ep — expert parallel: MoE experts split; per-layer partial sums psum'd.
  tp — tensor parallel: attention heads / MLP hidden split, activations
       all-reduced per block. Last = ICI-nearest (its collectives fire the
       most often per layer).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — the framework's one import point
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXES = ("dp", "sp", "ep", "tp")


def job_mesh(n_jobs: int | None = None, *, devices=None) -> Mesh:
    """A 1-axis ``("jobs",)`` mesh for fused small-job dispatch: each job of
    a coalesced batch owns one device along the axis. Unlike the model
    meshes above there is no cross-job communication — the axis exists only
    to place independent blocks, so no ICI-nearness ordering applies and a
    plain device-list mesh is correct on any topology.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices) if n_jobs is None else n_jobs
    if n < 1 or n > len(devices):
        raise ValueError(f"job mesh needs 1..{len(devices)} devices, got {n}")
    return Mesh(np.array(devices[:n]), ("jobs",))


def job_device_assignment(n_jobs: int, n_devices: int | None) -> list[int | None]:
    """Device-axis placement for a batched small-job dispatch: job i of a
    coalesced batch runs on device ``assignment[i]`` of the lane's local
    device list (the "jobs" axis of the batch — one independent program per
    chip, the Anakin/Sebulba placement rather than one sharded program).

    Jobs are dealt round-robin so a partial batch still spreads across the
    whole slice (4 jobs on 8 chips use 4 DISTINCT chips, not chips 0-3 of a
    contiguous block twice over on wrap-around). ``n_devices`` None/0 means
    the caller doesn't know the lane's device count (chip_count=0 lanes);
    the sandbox runner then applies the same round-robin against whatever
    it enumerates locally.
    """
    if n_jobs < 1:
        raise ValueError("n_jobs must be >= 1")
    if not n_devices or n_devices < 1:
        return [None] * n_jobs
    return [i % n_devices for i in range(n_jobs)]


@dataclass(frozen=True)
class MeshSpec:
    """A logical mesh shape over named axes (order matters: ICI-nearest last)."""

    shape: tuple[int, ...]
    axes: tuple[str, ...] = AXES

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes}")

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.shape))


def best_mesh_shape(
    n_devices: int,
    *,
    tp: int | None = None,
    sp: int | None = None,
    ep: int | None = None,
) -> MeshSpec:
    """Pick a (dp, sp, ep, tp) factorization of n_devices.

    Heuristic: tp wants the ICI-nearest (fastest, last) axis and benefits most
    up to the MXU-efficient head count, so give tp the largest power-of-two
    factor <= 4 unless pinned; sp and ep default to 1 unless pinned; dp
    absorbs the rest. All axes must divide n_devices.
    """
    if n_devices < 1:
        raise ValueError("n_devices must be >= 1")
    if tp is None:
        tp = 1
        for cand in (4, 2):
            if n_devices % cand == 0:
                tp = cand
                break
    if n_devices % tp != 0:
        raise ValueError(f"tp={tp} does not divide n_devices={n_devices}")
    rest = n_devices // tp
    if sp is None:
        sp = 1
    if rest % sp != 0:
        raise ValueError(f"sp={sp} does not divide n_devices/tp={rest}")
    rest //= sp
    if ep is None:
        ep = 1
    if rest % ep != 0:
        raise ValueError(f"ep={ep} does not divide n_devices/(tp*sp)={rest}")
    dp = rest // ep
    return MeshSpec(shape=(dp, sp, ep, tp))


def make_mesh(
    spec: MeshSpec | None = None,
    *,
    n_devices: int | None = None,
    devices=None,
) -> Mesh:
    """Build a `jax.sharding.Mesh` from a spec (or a device count).

    `create_device_mesh` handles the physical->logical assignment: on TPU it
    orders devices so the last mesh axis lands on nearest-neighbor ICI; on CPU
    (tests, driver dry-run) it is a plain reshape.
    """
    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = best_mesh_shape(n_devices if n_devices is not None else len(devices))
    if spec.n_devices > len(devices):
        raise ValueError(
            f"mesh needs {spec.n_devices} devices, only {len(devices)} present"
        )
    devices = devices[: spec.n_devices]
    device_array = mesh_utils.create_device_mesh(spec.shape, devices=devices)
    return Mesh(device_array, spec.axes)
