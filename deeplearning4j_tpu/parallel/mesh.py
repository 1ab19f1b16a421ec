"""Device mesh / topology abstraction — the distributed backbone.

Reference equivalents (SURVEY.md §2.4, §5.8): the entire Aeron UDP transport
(`nd4j-aeron` ``AeronNDArrayPublisher``/``NDArrayMessage`` chunking), the
``VoidParameterServer`` mesh, and ``AffinityManager`` device pinning. On TPU
all of that collapses into XLA collectives compiled into the program: this
module only names the axes, builds the ``jax.sharding.Mesh``, and hands out
``NamedSharding``s; ``psum``/``all_gather``/``ppermute`` ride ICI within a
slice and DCN across slices, inserted by the compiler.

Axis convention (the full menu; unused axes just have size 1):
``data`` (DP replicas), ``model`` (TP shards), ``pipeline`` (PP stages),
``sequence`` (SP/ring-attention shards), ``expert`` (EP/MoE shards).
"""

from __future__ import annotations

import dataclasses
import typing as tp

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPELINE_AXIS = "pipeline"
SEQUENCE_AXIS = "sequence"
EXPERT_AXIS = "expert"

ALL_AXES = (DATA_AXIS, MODEL_AXIS, PIPELINE_AXIS, SEQUENCE_AXIS, EXPERT_AXIS)


@dataclasses.dataclass
class MeshConfig:
    """Declarative mesh shape. Unspecified axes default to 1; ``data=-1``
    (the default) absorbs all remaining devices, so the same config scales
    from 1 chip to a pod unchanged."""

    data: int = -1
    model: int = 1
    pipeline: int = 1
    sequence: int = 1
    expert: int = 1
    devices: tp.Optional[tp.Sequence] = None  # default: jax.devices()

    def build(self) -> Mesh:
        devices = list(self.devices if self.devices is not None
                       else jax.devices())
        n = len(devices)
        fixed = self.model * self.pipeline * self.sequence * self.expert
        data = self.data
        if data == -1:
            if n % fixed != 0:
                raise ValueError(
                    f"{n} devices not divisible by model*pipeline*sequence*"
                    f"expert={fixed}")
            data = n // fixed
        total = data * fixed
        if total > n:
            raise ValueError(f"mesh needs {total} devices, have {n}")
        shape = (data, self.model, self.pipeline, self.sequence, self.expert)
        arr = np.array(devices[:total]).reshape(shape)
        return Mesh(arr, ALL_AXES)


def single_host_mesh(n_devices: int | None = None, **axes) -> Mesh:
    """Convenience: mesh over the first n local devices (default: all)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return MeshConfig(devices=devices, **axes).build()


def data_parallel_spec(mesh: Mesh) -> NamedSharding:
    """Batch sharded over 'data', everything else replicated — the
    ParallelWrapper layout."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch):
    """Place a host batch so its leading dim is split over the 'data' axis
    (the role of ParallelWrapper's splitter + per-worker MagicQueues).

    Multi-process (``jax.distributed``): ``batch`` holds THIS process's
    local partition (the reference's RDD partition per Spark executor); the
    global array is assembled from every process's contribution."""
    sharding = data_parallel_spec(mesh)
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)), batch)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sharding), batch)


def replicate(mesh: Mesh, tree):
    """Replicate params/opt-state across the mesh (the reference copies
    replica params to each device via AffinityManager)."""
    sharding = replicated_spec(mesh)
    return jax.tree_util.tree_map(
        lambda x: stage_host(x, sharding), tree)


def stage_host(x, sharding) -> jax.Array:
    """Commit one host value under ``sharding``, at ANY process count:
    ``jax.make_array_from_callback`` hands each process only the index
    boxes of its OWN addressable shards, so a pod host stages exactly
    its slice of the global array and never touches (or needs) remote
    devices. At ``process_count == 1`` this is bitwise the old
    ``device_put`` path (pinned by test_sharding's parity suite);
    device-resident single-process values keep the plain ``device_put``
    fast path (no host round-trip)."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def host_gather(tree):
    """Device tree -> host numpy tree, at ANY process count: a fully-
    addressable leaf is a plain ``device_get``; a process-SPANNING leaf
    (a pod's ZeRO opt slices, TP shards on remote hosts) first
    replicates through a compiled identity — XLA inserts the cross-host
    all-gather — and reads the local copy. This is the multi-host
    gather that lets checkpoints stay full-host-array and
    mesh-shape-agnostic on a pod (the single-process path is bitwise
    the old ``np.asarray`` route)."""
    def pull(x):
        if not isinstance(x, jax.Array) \
                or getattr(x, "is_fully_addressable", True):
            return np.asarray(jax.device_get(x))
        sh = getattr(x, "sharding", None)
        m = getattr(sh, "mesh", None)
        if m is None:  # exotic sharding: let jax try (clear error > hang)
            return np.asarray(jax.device_get(x))
        # through the AOT-cached compiled identity (comms.reshard):
        # gathers of the same (placement, aval) reuse one executable —
        # a fresh jit per leaf would re-trace the cross-host all-gather
        # on every checkpoint
        from deeplearning4j_tpu.comms.reshard import commit_compiled

        rep = commit_compiled(x, NamedSharding(m, P()))
        return np.asarray(rep.addressable_shards[0].data)

    return jax.tree_util.tree_map(pull, tree)


def pad_leading(tree, target: int):
    """Zero-pad every leaf's leading (batch) dim to ``target`` rows. Padded
    rows carry a zero label-mask so they contribute nothing to loss/grads
    (the role of the reference splitter handling ragged final batches)."""
    import jax.numpy as jnp

    def pad(x):
        x = jnp.asarray(x)
        n = x.shape[0]
        if n == target:
            return x
        return jnp.concatenate(
            [x, jnp.zeros((target - n,) + x.shape[1:], x.dtype)])

    return jax.tree_util.tree_map(pad, tree)


def shard_valid_counts(rows: int, workers: int) -> np.ndarray:
    """Valid (non-padded) row count per shard after ``pad_leading`` to
    ``ceil(rows/workers)*workers`` and an even split: shard i holds rows
    [i*s, (i+1)*s)."""
    s = -(-rows // workers)
    return np.clip(rows - np.arange(workers) * s, 0, s).astype(np.float32)


def ensure_varying(x, axes):
    """Mark ``x`` device-varying over the given mesh axes: pcast to
    varying only on the axes ``x`` does not already vary on (pcast
    errors on varying->varying; shard-mapped inputs arrive already
    varying on their sharded axes)."""
    have = set(jax.typeof(x).vma)
    need = tuple(a for a in axes if a not in have)
    return jax.lax.pcast(x, need, to="varying") if need else x


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host bootstrap (reference: Spark master/worker setup + Aeron
    ``VoidParameterServer`` join — SURVEY.md §3.5). One call per host;
    afterwards ``jax.devices()`` spans the whole pod and the same Mesh code
    scales across hosts, collectives riding ICI intra-slice / DCN inter-
    slice. No-op when every argument is None and env vars configure it."""
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def device_count(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    return mesh.shape[axis]


