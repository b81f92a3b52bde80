"""Mesh construction: every mesh the program builds comes from here.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.

Every axis is ``AxisType.Auto``: the program places arrays with
``NamedSharding`` and steers GSPMD with ``with_sharding_constraint``
hints (``P.UNCONSTRAINED`` included), which Explicit axes — the
``jax.make_mesh`` default since jax 0.7 — reject.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of ``shape`` over ``axes`` with Auto axis types.

    ``devices=None`` lays out the visible devices the way
    ``jax.make_mesh`` does (topology-aware); an explicit device list is
    taken in order (elastic meshes over the surviving devices).
    """
    shape, axes = tuple(shape), tuple(axes)
    auto = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=auto)
    return Mesh(np.asarray(devices).reshape(shape), axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``model`` is the fast (ICI ring) axis used for tensor/expert
    parallelism; ``data`` carries FSDP + data parallelism; ``pod`` (DCN)
    only ever sees data-parallel gradient traffic (compressed — see
    CommunicationPass).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """(data, model) mesh over however many devices this host has."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} device(s) cannot host model={model}")
    return make_mesh((n // model, model), ("data", "model"))
