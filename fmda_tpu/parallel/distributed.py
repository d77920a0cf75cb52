"""Multi-host (multi-slice) runtime: process init + global input placement.

The reference's only cross-machine transport is Kafka between pipeline
*stages* (SURVEY.md §5 "distributed communication backend") — it has no
multi-machine ML at all.  This module is the framework's DCN story: one
jax.distributed job per host, a global mesh whose ``dp`` axis crosses the
host boundary (gradient all-reduce rides DCN between slices, ICI within —
the standard multi-slice data-parallel recipe), and process-local batch
placement so each host feeds only its own shard of every global batch.

Verified without a TPU pod by the 2-process CPU harness in
``tests/test_distributed.py`` (jax's Gloo CPU collectives), the same way
the CPU mesh stands in for single-host multi-chip elsewhere.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from fmda_tpu.parallel.mesh import replicated_sharding


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    *,
    local_device_ids: Optional[Tuple[int, ...]] = None,
) -> None:
    """Join this host to the distributed job (idempotent).

    Call before any other jax API on every host; afterwards
    ``jax.devices()`` spans all hosts and :func:`build_mesh` with
    ``MeshConfig(processes=num_processes)`` builds the global mesh.
    """
    # Idempotency check must not touch the backend (jax.process_count()
    # would initialise XLA and make jax.distributed.initialize fail).
    is_init = getattr(jax.distributed, "is_initialized", None)
    if is_init is not None and is_init():
        return
    # CPU cross-process collectives default to "none" on jax releases
    # that carry the knob — without Gloo every multi-process CPU
    # computation (including device_put's replication assert) dies with
    # "Multiprocess computations aren't implemented on the CPU backend".
    # Releases without the knob pick a working implementation themselves.
    if "cpu" in (jax.config.jax_platforms or ""):
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except (AttributeError, ValueError):  # knob gone or gloo not built
            pass
    kwargs = {}
    if local_device_ids is not None:
        kwargs["local_device_ids"] = list(local_device_ids)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )


def make_global_batch(
    mesh: Mesh, local_array: np.ndarray, spec: PartitionSpec
) -> jax.Array:
    """Assemble a global array from this process's local shard.

    ``local_array`` is the rows this host contributes (its slice of the
    global batch); the result is one global jax.Array laid out per
    ``spec`` with no cross-host data movement.
    """
    return jax.make_array_from_process_local_data(
        NamedSharding(mesh, spec), np.asarray(local_array)
    )


def place_replicated(mesh: Mesh, tree):
    """Replicate a host-identical tree over ``mesh``, multi-process safe.

    ``jax.device_put`` onto a sharding that spans processes first runs a
    host-side equality assert (``multihost_utils.assert_equal``) — a
    cross-process *computation* some CPU builds cannot run (and whose
    Gloo broadcast has crashed on size-mismatched frames).  The
    data-loading path sidesteps it: every process contributes its local
    (identical, by the caller's contract) value and jax assembles the
    global array with no host-side collective.  Leaves come back fresh
    (the host round-trip copies), so the result is donation-safe.
    """
    sharding = replicated_sharding(mesh)
    if jax.process_count() == 1:
        from fmda_tpu.parallel.sp_train import place_fresh_copy

        return place_fresh_copy(tree, sharding)
    return jax.tree.map(
        lambda a: jax.make_array_from_process_local_data(
            sharding, np.asarray(a)),
        tree,
    )


def shard_train_inputs_multihost(
    mesh: Mesh,
    x_local: np.ndarray,
    y_local: np.ndarray,
    params,
    opt_state,
    *,
    dp_axis: str = "dp",
    sp_axis: str = "sp",
) -> Tuple:
    """Multi-host variant of ``sp_train.shard_train_inputs``: x/y are this
    process's *local* batch rows; params/optimizer are replicated (every
    host passes identical values — true after identical init seeds or a
    checkpoint restore).

    Like the single-host helper, params/opt_state come back as fresh
    copies (:func:`~fmda_tpu.parallel.sp_train.place_fresh_copy`):
    ``make_sp_train_step`` donates argnums (0, 1), and a plain
    ``device_put`` may alias the caller's tree when placement already
    matches — the first step would then delete the caller's originals.
    """
    x = make_global_batch(
        mesh, x_local, PartitionSpec(dp_axis, sp_axis))
    y = make_global_batch(mesh, y_local, PartitionSpec(dp_axis))
    return (x, y, place_replicated(mesh, params),
            place_replicated(mesh, opt_state))


def place_local_batch(mesh: Mesh, batch, spec: PartitionSpec):
    """Place a process-local training Batch onto the global dp sharding
    ``spec`` (``P(dp)``; ``P(None, None, dp)`` for a group of stacked batches;
    used by the Trainer when the job spans processes)."""
    from fmda_tpu.data.pipeline import Batch

    return Batch(
        make_global_batch(mesh, batch.x, spec),
        make_global_batch(mesh, batch.y, spec),
        make_global_batch(mesh, batch.mask, spec),
    )
