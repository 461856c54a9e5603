"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run forces 512 host devices *before* any
jax initialization; tests and benches see the default device count).
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "the first device query (launch/dryrun.py's main does this)"
        )
    return make_mesh(shape, axes, devices=devices[:n])


def data_axes_of(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)
