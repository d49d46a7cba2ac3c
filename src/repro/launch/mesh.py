"""Mesh construction.

Every mesh in the repo (serving's (data, model) dev mesh, the sharded
train step's, the production topology) comes from :func:`make_mesh`.
Defined as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
must set XLA_FLAGS before the first jax initialisation.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: XLA propagates shardings, which
    is what ``shard_map`` and the ``NamedSharding`` placement here
    expect.  ``shape`` entries must multiply to a divisor-compatible
    device count — callers validate availability (e.g. serve_gen checks
    ``dp * mp <= jax.device_count()``) before landing here."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips).

    Axes: 'pod' (outer DP across the cross-pod DCI), 'data' (DP/FSDP
    within a pod), 'model' (TP/EP within a pod — the highest-bandwidth
    ICI dimension).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_dev_mesh(n_data: int = 1, n_model: int = 1):
    """Small (data, model) mesh for serving/tests on local devices."""
    return make_mesh((n_data, n_model), ("data", "model"))
