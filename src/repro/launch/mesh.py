"""Production mesh construction (TPU v5e target).

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is pure data parallelism across the ICI-disjoint pods (DCN).

Serving additionally uses an ("expert", "data") mesh
(``make_expert_mesh``): the stacked expert pytree's leading K axis shards
over "expert" (each device group holds K / n_expert_shards resident
experts) while request batches shard over "data".

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; everything else
sees the real single CPU device).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _auto(n: int) -> tuple:
    """Auto (compiler-propagated) axes: the serving code places arrays with
    ``NamedSharding`` + ``with_sharding_constraint``, which Explicit axes
    (``jax.make_mesh``'s default) reject."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh():
    """1-device mesh for CPU tests (same axis names as single-pod)."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def make_expert_mesh(n_expert_shards: int = 1, n_data_shards: int | None = None):
    """Expert-parallel serving mesh with axes ``("expert", "data")``.

    ``n_expert_shards`` partitions the stacked expert pytree's leading K
    axis (param storage: K / n_expert_shards resident experts per device
    group); ``n_data_shards`` partitions the request batch.  When
    ``n_data_shards`` is None the remaining devices fold into "data" so
    the mesh covers every visible device.  A (1, 1) mesh is the valid
    degenerate single-host case (bit-identical to unsharded serving).

    Unlike ``make_production_mesh`` this tolerates using a *prefix* of the
    visible devices (e.g. 2 expert shards on a 3-device host), so CPU
    hosts forced to N devices via ``--xla_force_host_platform_device_count``
    (the ``launch/dryrun.py`` trick) can stand up any smaller topology.
    """
    if n_expert_shards < 1:
        raise ValueError(f"n_expert_shards must be >= 1, got {n_expert_shards}")
    ndev = jax.device_count()
    if n_data_shards is None:
        n_data_shards = max(1, ndev // n_expert_shards)
    if n_data_shards < 1:
        raise ValueError(f"n_data_shards must be >= 1, got {n_data_shards}")
    need = n_expert_shards * n_data_shards
    if need > ndev:
        raise ValueError(
            f"mesh ({n_expert_shards}, {n_data_shards}) needs {need} "
            f"devices but only {ndev} are visible"
        )
    if need == ndev:
        return jax.make_mesh((n_expert_shards, n_data_shards),
                             ("expert", "data"), axis_types=_auto(2))
    devices = np.asarray(jax.devices()[:need]).reshape(
        n_expert_shards, n_data_shards
    )
    return jax.sharding.Mesh(devices, ("expert", "data"),
                             axis_types=_auto(2))


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes for this mesh ('pod' folds into data)."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def mesh_devices(mesh) -> int:
    return mesh.devices.size


# --- TPU v5e hardware constants (per chip) — roofline denominators ---------
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
