"""Production mesh definition (deliverable e).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state, so smoke tests see 1 CPU device while the
dry-run (which sets --xla_force_host_platform_device_count=512 before any
import) sees the full placeholder pod.  Every mesh is built by ``_mesh``
with ``Auto`` axes (sharding propagates from the placed arrays).
"""
from __future__ import annotations

import logging

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh_for_devices",
           "candidate_sharding", "population_sharding",
           "island_sharding", "default_islands"]

log = logging.getLogger(__name__)


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def candidate_sharding():
    """``NamedSharding`` over the DSE candidate batch axis, or ``None``
    on a single device (where sharding is a no-op anyway).

    The one sharding every engine evaluation path uses — the in-scan
    ``batch_eval`` evaluator AND the compile-free batched mapper+executor
    place their (B, ...) config/placement arrays with it, so a sweep or
    GA population spans whatever devices exist.  Batch sizes must be a
    multiple of ``mesh.size`` (``EvalEngine._pad_size`` rounds up after
    bucket rounding) or XLA falls back to per-device replication.
    """
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    mesh = _mesh((len(devs),), ("candidates",))
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("candidates"))


def population_sharding(population: int):
    """The GA generation loop's sharding: the (P, GENOME_LEN) population
    and its per-generation genetics dispatch
    (``core.dse.ga_device``) shard over the same ``"candidates"`` axis
    the evaluation batches use, so one mesh covers the whole
    search loop — selection/crossover/mutation on device AND the fused
    exact scoring dispatches.  Same divisibility rule: a population that
    is not a mesh-size multiple runs on one device (logged), as does a
    single-device host (``None``)."""
    sharding = candidate_sharding()
    if sharding is not None and int(population) % sharding.mesh.size:
        log.warning("population_sharding: population %d does not divide "
                    "over %d devices; the GA runs on one device",
                    int(population), sharding.mesh.size)
        return None
    return sharding


def island_sharding(n_islands: int):
    """Sharding for the island-model GA (``core.dse.ga_device`` fused
    loop): the population is carried flat as (P, GENOME_LEN) but is
    logically (islands, P/islands, GENOME_LEN), and sharding the leading
    axis places one contiguous block of islands per device.  Inside the
    jitted refinement loop the ring migration is a ``jnp.roll`` over the
    island axis — XLA lowers a roll of a sharded leading axis to a
    collective permute around the device ring, so migrants move
    device-to-device without a host hop.  Returns ``None`` on a single
    device or when ``n_islands`` doesn't divide over the mesh (the
    caller falls back to single-device placement — same numbers, no
    collectives)."""
    devs = jax.devices()
    if len(devs) <= 1:
        return None
    if int(n_islands) % len(devs) != 0:
        log.warning("island_sharding: %d islands do not divide over %d "
                    "devices; the island GA runs on one device",
                    int(n_islands), len(devs))
        return None
    mesh = _mesh((len(devs),), ("islands",))
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("islands"))


def default_islands(population: int) -> int:
    """Island count the fused GA defaults to: one island per local device
    when the population splits evenly, else a single panmictic island
    (which preserves the host-memo loop's exact genome stream)."""
    ndev = len(jax.devices())
    if ndev > 1 and population % ndev == 0 and population // ndev >= 2:
        return ndev
    return 1


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = 1):
    """Elastic mesh for whatever devices are healthy (train loop + tests):
    (n/model_parallel, model_parallel) over ("data", "model")."""
    if n_devices % model_parallel:
        raise ValueError(f"{n_devices} devices not divisible by "
                         f"model_parallel={model_parallel}")
    return _mesh((n_devices // model_parallel, model_parallel),
                 ("data", "model"))
