"""Repo-level pytest configuration.

Pins jax to the CPU unless ``JAX_PLATFORMS`` says otherwise, so a plain
local ``pytest`` never claims the TPU library (tests that compile for a
described TPU topology do so explicitly, in tests/kernels/test_tpu_compile.py).

Forces 8 virtual host devices *before* jax initializes so the stencil
subsystem (tests/stencil/) is drivable from this single pytest process on a
multi-device mesh — the same count the subprocess-based distributed checks
use.  The count is only injected when the user has not already pinned one in
``XLA_FLAGS``.  All pre-existing in-process tests use at most one device
(``jax.devices()[:1]``) and are insensitive to the total.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_FORCE = "--xla_force_host_platform_device_count"

if _FORCE not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FORCE}=8"
    ).strip()
