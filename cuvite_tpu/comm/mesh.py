"""Device mesh helpers.

The reference bootstraps distribution with MPI_Init + a shared-memory
sub-communicator for GPU binding (/root/reference/main.cpp:67-74,
louvain_cuda.cu:1634-1669).  The TPU-native analog is a 1-D
`jax.sharding.Mesh` over all addressable devices; multi-host deployments call
`jax.distributed.initialize` before building it.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

VERTEX_AXIS = "v"

# Two-level exchange axes (ISSUE 18): the hybrid mesh factors the flat
# vertex axis into a slow outer axis (DCN / data-center network, or
# host-to-host) and a fast inner axis (ICI / the chip interconnect of
# one slice).  Community tables replicate only inside the ICI submesh;
# cross-group traffic rides the sparse ghost protocol on the DCN axis.
DCN_AXIS = "dcn"
ICI_AXIS = "ici"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"requested a {n_devices}-device mesh but only "
                    f"{len(devices)} jax device(s) are visible; for a "
                    f"virtual CPU mesh set XLA_FLAGS="
                    f"--xla_force_host_platform_device_count={n_devices} "
                    f"before jax initializes"
                )
            if jax.process_count() > 1 and n_devices != len(devices):
                # Slicing jax.devices()[:n] would keep only the lowest
                # ranks' devices, leaving other processes with no
                # addressable mesh entry — a deadlock, not a smaller run.
                raise ValueError(
                    f"a multi-process mesh must span all "
                    f"{len(devices)} global devices; got n_devices="
                    f"{n_devices} (launch fewer processes/devices instead)"
                )
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (VERTEX_AXIS,))


def make_hybrid_mesh(dcn: int, ici: int, devices=None) -> Mesh:
    """2-D ``('dcn', 'ici')`` mesh for the two-level exchange.

    On a multi-slice TPU deployment this prefers
    ``mesh_utils.create_hybrid_device_mesh`` (SNIPPETS.md [1]) so the
    outer axis really maps to the slow inter-slice network.  Everywhere
    else — single slice, CPU virtual devices, tier-1 — it falls back to
    a factored reshape of the flat device list into ``[dcn, ici]`` with
    the ICI axis innermost (consecutive devices, which on a real slice
    are the physically adjacent ones).  The factored fallback exercises
    the REAL 2-axis collectives, so the CPU test tier covers the same
    program a hybrid deployment compiles.

    The flattened device order equals ``make_mesh(dcn * ici)``'s order,
    which is what makes the two-level shard numbering (shard
    ``g * ici + i`` owns ``[s*nv_pad, (s+1)*nv_pad)``) line up with the
    flat exchange's contiguous ownership map bit-for-bit.
    """
    if dcn < 1 or ici < 1:
        raise ValueError(f"mesh factors must be >= 1, got {dcn}x{ici}")
    n = dcn * ici
    if devices is None:
        flat = make_mesh(n).devices.reshape(-1)
    else:
        flat = np.asarray(devices).reshape(-1)
        if flat.size != n:
            raise ValueError(
                f"hybrid mesh {dcn}x{ici} needs {n} devices, got {flat.size}")
    if dcn > 1 and len({getattr(d, "slice_index", 0) for d in flat}) == dcn:
        # Real multi-slice topology: let jax group by slice so the DCN
        # axis crosses slices and the ICI axis stays inside one.
        try:
            from jax.experimental import mesh_utils

            arr = mesh_utils.create_hybrid_device_mesh(
                (ici,), (dcn,), devices=list(flat)).reshape(dcn, ici)
            return Mesh(arr, (DCN_AXIS, ICI_AXIS))
        except Exception:
            pass  # fall through to the factored reshape
    return Mesh(flat.reshape(dcn, ici), (DCN_AXIS, ICI_AXIS))


def hybrid_shape(mesh: Mesh) -> tuple[int, int]:
    """(n_dcn, n_ici) of a hybrid mesh; (1, n) for a flat 1-D mesh."""
    if mesh.axis_names == (DCN_AXIS, ICI_AXIS):
        return (mesh.devices.shape[0], mesh.devices.shape[1])
    return (1, int(np.prod(mesh.devices.shape)))


def vertex_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding axis 0 across EVERY mesh axis — the vertex
    layout.  ``P('v')`` on the flat mesh, ``P(('dcn','ici'))`` on the
    hybrid one (dcn-major, matching the flat device order)."""
    if mesh.axis_names == (DCN_AXIS, ICI_AXIS):
        return P((DCN_AXIS, ICI_AXIS))
    return P(VERTEX_AXIS)


def shard_1d(mesh: Mesh, arr, replicate: bool = False):
    """Place an array on the mesh, sharded along axis 0 (or replicated).
    On a hybrid mesh axis 0 shards across both axes dcn-major, so the
    per-device blocks are identical to the flat mesh's.  Works on
    single-process and multi-host meshes alike (the latter via
    per-process local blocks, comm/multihost.py)."""
    from cuvite_tpu.comm.multihost import place

    spec = P() if replicate else vertex_spec(mesh)
    return place(mesh, arr, spec)


def shard_outer(mesh: Mesh, arr):
    """Place an array sharded along axis 0 over the OUTER (dcn) axis
    only — replicated inside each ICI group.  The layout of the grouped
    exchange-plan arrays: every ici sibling drives the same group-scale
    sparse protocol, so each needs the whole group's plan rows."""
    from cuvite_tpu.comm.multihost import place

    if mesh.axis_names != (DCN_AXIS, ICI_AXIS):
        return place(mesh, arr, P(VERTEX_AXIS))
    return place(mesh, arr, P(DCN_AXIS))
