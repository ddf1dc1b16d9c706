"""Mesh construction and batch-axis sharding.

The communication design (SURVEY.md §2.7, §5): one 1-D logical axis,
``"batch"``, laid over all available devices (ICI within a host/slice, DCN
across hosts).  Each device solves its shard of the problem batch in
lockstep; no collectives are needed during the solve because problems are
independent — an all-gather of the small outcome/selection tensors happens
implicitly when results are fetched.  This replaces, tpu-natively, what a
NCCL/MPI backend would be in a GPU framework: the mesh axes + shardings ARE
the communication topology, and XLA inserts the transfers.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..utils.platform_env import assert_env_platform

# ``default_mesh()``/``clause_mesh()`` are often a user process's first
# backend query; make ``JAX_PLATFORMS`` decide which backends it
# initializes (see platform_env.assert_env_platform).
assert_env_platform()

BATCH_AXIS = "batch"


def default_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-D mesh over ``devices`` (default: all local devices) with the
    single ``"batch"`` axis used by the batched solver."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (BATCH_AXIS,))


def mesh_devices_from_env() -> Optional[int]:
    """Parse ``DEPPY_TPU_MESH_DEVICES`` (the ``--mesh-devices`` env
    mirror): ``all`` or ``-1`` → -1 (every local device, the same
    spelling the CLI flag documents), a positive integer → that many,
    unset/empty/``0``/``1`` → None (mesh serving off — the historical
    single-device dispatch).  Malformed values warn and degrade to off,
    like every other fault-layer env knob."""
    from .. import config

    raw = (config.env_raw("DEPPY_TPU_MESH_DEVICES") or "").strip().lower()
    if not raw or raw in ("0", "1", "off", "none"):
        return None
    if raw == "all":
        return -1
    try:
        n = int(raw)
    except ValueError:
        n = None
    if n == -1:
        return -1
    if n is None or n < 0:
        import sys

        print(f"[deppy] ignoring malformed DEPPY_TPU_MESH_DEVICES={raw!r} "
              f"(want an integer or 'all'); mesh serving stays off",
              file=sys.stderr, flush=True)
        return None
    return n if n > 1 else None


def serving_mesh(n_devices: Optional[int] = None) -> Optional[Mesh]:
    """The batch-axis mesh the scheduler's sharded drain dispatches over
    (ISSUE 6), or None when mesh serving is off.  ``n_devices`` -1 (or
    ``DEPPY_TPU_MESH_DEVICES=all``) takes every local device; a count
    above the platform's device total clamps with a warning rather than
    failing serving.  Callers resolve this lazily, on the first device
    dispatch."""
    if n_devices is None:
        n_devices = mesh_devices_from_env()
    if n_devices is None:
        return None
    devs = jax.devices()
    if n_devices == -1:
        n_devices = len(devs)
    if n_devices > len(devs):
        import sys

        print(f"[deppy] mesh-devices={n_devices} > {len(devs)} local "
              f"devices; clamping to {len(devs)}", file=sys.stderr,
              flush=True)
        n_devices = len(devs)
    if n_devices < 2:
        return None
    return default_mesh(devs[:n_devices])


def batch_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard a rank-``ndim`` array's leading (batch) axis over the mesh;
    all trailing axes replicated per shard."""
    return NamedSharding(mesh, PartitionSpec(BATCH_AXIS, *([None] * (ndim - 1))))


def shard_batch(mesh: Mesh, tree):
    """Device-put every leaf of a stacked problem pytree with its batch axis
    sharded over the mesh.  Scalars-per-problem (rank-1 leaves) shard too;
    the batch size must divide evenly (the driver pads to a multiple of the
    mesh size).

    Works on multi-process meshes too: when the sharding spans devices
    this process cannot address (a ``jax.distributed`` fleet),
    ``device_put`` of a host array is illegal, so each process instead
    contributes only its addressable shards via
    ``make_array_from_callback`` — every process holds the same full
    host-side batch (the deterministic build happens everywhere), and
    the callback slices out the local pieces."""
    def put(leaf):
        arr = np.asarray(leaf)
        sharding = batch_sharding(mesh, arr.ndim)
        if sharding.is_fully_addressable:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    return jax.tree_util.tree_map(put, tree)


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated output sharding: jitting a batched solve with this
    as ``out_shardings`` makes XLA all-gather the (small) result tensors,
    so every process of a multi-host fleet can ``device_get`` the global
    outcome without a host-side gather step."""
    return NamedSharding(mesh, PartitionSpec())


def initialize_distributed(**kwargs) -> None:
    """Multi-host entry: initialize the JAX distributed runtime so
    ``jax.devices()`` spans the fleet and ``default_mesh()`` lays the batch
    axis over ICI + DCN.  Thin passthrough to ``jax.distributed.initialize``
    (coordinator_address / num_processes / process_id kwargs); call once per
    process before building a mesh.  On a single host with no cluster
    environment it is a no-op convenience so launch scripts can call it
    unconditionally; when a cluster IS configured (kwargs given or a
    recognized cluster environment), failures propagate — silently falling
    back to single-host there would make every host redundantly solve the
    full batch."""
    if not kwargs:
        from jax._src.clusters import ClusterEnv

        if not any(c.is_env_present() for c in ClusterEnv._cluster_types):
            return  # plain single-process launch: nothing to initialize
    if (os.environ.get("JAX_PLATFORMS") or "").strip() == "cpu":
        # Cross-process collectives on XLA:CPU need an explicit transport
        # (TPU fleets ride ICI/DCN natively); without this the first
        # collective hangs.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(**kwargs)
