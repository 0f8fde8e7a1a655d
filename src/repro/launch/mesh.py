"""Production mesh construction.

TPU v5e target: 256 chips/pod as a (data=16, model=16) mesh; the multi-pod
configuration adds a leading "pod" axis (2 pods = 512 chips). The "pod"
axis is where the DFedRW gossip technique operates (each pod = one
federated client group); "data" is batch/fsdp parallelism; "model" is
tensor/expert parallelism.

Every mesh of the repo is built by ``make_mesh`` here. Its axes are
``AxisType.Auto``: the step builders are written for GSPMD propagation
(``PartitionSpec`` placements plus ``with_sharding_constraint`` under the
mesh context), while ``jax.make_mesh`` defaults every axis to ``Explicit``
sharding-in-types.

NOTE: defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh",
           "make_pod_mesh", "make_chains_mesh", "make_metal_mesh", "HW"]


class HW:
    """TPU v5e hardware constants used by the roofline analysis."""

    PEAK_FLOPS_BF16 = 197e12       # per chip
    HBM_BW = 819e9                 # bytes/s per chip
    ICI_BW = 50e9                  # bytes/s per link (~4 links/chip on v5e 2D torus)
    ICI_LINKS = 4
    HBM_BYTES = 16e9               # v5e HBM capacity


def make_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh of GSPMD (``Auto``) axes over ``devices`` in the given order,
    or over ``jax.make_mesh``'s choice of the process's devices."""
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)
    return Mesh(np.asarray(devices).reshape(shape), tuple(axes),
                axis_types=axis_types)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over the host's real devices (smoke tests / examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = max(1, min(data, n // model))
    return make_mesh((data, model), ("data", "model"))


def make_metal_mesh(chains: int = 0, *, coordinator: str | None = None,
                    num_processes: int = 1, process_id: int = 0):
    """Bring-up for the trace-driven metal deployment (launch/replay.py).

    Multi-process (``num_processes`` > 1): joins the ``jax.distributed``
    coordinator first, so every process sees the deployment's global device
    view — the live-fleet bring-up the sim-to-metal conformance harness
    exercises. Compute itself stays process-local (per-shard programs +
    explicit trajectory exchange): jaxlib's CPU backend refuses cross-process
    XLA computations, and a real DFedRW fleet exchanges *messages*, not an
    SPMD interconnect — see ``repro.sim.metal``.

    Returns ``(mesh, info)``: a 1-axis ``("chains",)`` mesh over the largest
    divisor-of-``chains`` prefix of the local devices (``chains=0`` = all of
    them — no padding is ever needed), plus the process/device census the
    launcher logs.
    """
    if num_processes > 1:
        if coordinator is None:
            raise ValueError("multi-process bring-up needs a coordinator "
                             "address (host:port)")
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    devs = jax.local_devices()
    mesh = make_chains_mesh(devs, chains or len(devs))
    info = {
        "process_id": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(devs),
        "global_devices": jax.device_count(),
        "mesh_axis": mesh.size,
    }
    return mesh, info


def make_pod_mesh(pods: int = 0):
    """Host-device mesh with a leading gossip axis: (pod, data, model).

    pods=0 puts every host device on the pod axis (one model replica per
    device); otherwise the remaining devices fold into the data axis."""
    n = len(jax.devices())
    pods = pods or n
    assert n % pods == 0, f"{n} devices not divisible into {pods} pods"
    return make_mesh((pods, n // pods, 1), ("pod", "data", "model"))


def make_chains_mesh(devices, chains: int) -> Mesh:
    """1-axis ``("chains",)`` mesh over the largest prefix of ``devices``
    whose length divides ``chains``, so no chain row is ever padded."""
    axis = max(a for a in range(1, min(len(devices), max(chains, 1)) + 1)
               if chains % a == 0)
    return make_mesh((axis,), ("chains",), devices[:axis])
