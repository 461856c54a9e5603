"""The in-place 27-point stencil kernel against the update's XLA path
(wrap, ``stencil27_ref``, write-back), plus conservation properties.

The kernel takes the stored block ``(Z+2, Y+2, X)`` and returns it with the
interior updated; every case checks that the z and y rims come back bit for
bit.  ``stencil27`` donates its input, so each call gets a fresh array.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from repro.testing import given, settings, st  # hypothesis or deterministic fallback

from repro.kernels.stencil27 import jacobi_weights, stencil27
from repro.stencil.domain import stencil27_update

#: both Pallas interpreters: the plain one, and the TPU one, which models
#: the kernel's DMAs and the output's aliasing of the input
INTERPRETERS = [True, pltpu.InterpretParams()]
INTERPRETER_IDS = ["interpret", "tpu-interpret"]


def _run(stored: np.ndarray, w, *, rows: int = 8, interpret=True) -> np.ndarray:
    return np.asarray(stencil27(jnp.asarray(stored), w, rows=rows,
                                interpret=interpret))


def _check(stored: np.ndarray, w, got: np.ndarray, tol: float) -> None:
    """``got`` is the XLA path's update of ``stored``, and its rims are
    ``stored``'s, bit for bit."""
    want = np.asarray(stencil27_update(w, impl="xla")(jnp.asarray(stored)))
    assert got.shape == stored.shape and got.dtype == stored.dtype
    for rim in (np.s_[0], np.s_[-1], np.s_[:, 0], np.s_[:, -1]):
        np.testing.assert_array_equal(got[rim], stored[rim])
    np.testing.assert_allclose(got.astype(np.float32), want.astype(np.float32),
                               rtol=tol, atol=tol)


def _stored(shape, dtype=jnp.float32, seed=0) -> np.ndarray:
    """A random stored block for the interior ``shape`` (Z, Y, X)."""
    z, y, x = shape
    rng = np.random.default_rng(seed)
    return np.asarray(jnp.asarray(rng.normal(size=(z + 2, y + 2, x)), dtype))


def _weights(seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(3, 3, 3)),
                       jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,rows", [
    ((8, 8, 16), 8),
    ((6, 30, 128), 8),        # row chunks plus a static tail
    ((4, 4, 4), 8),           # head and tail only
    ((16, 40, 32), 16),
])
def test_stencil_matches_ref(dtype, shape, rows):
    stored = _stored(shape, dtype)
    w = _weights()
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    _check(stored, w, _run(stored, w, rows=rows), tol)


def test_jacobi_constant_field_is_fixed_point():
    """Normalized box weights: a constant field maps to itself, rims too."""
    x = np.full((10, 10, 10), 3.25, np.float32)
    out = _run(x, jacobi_weights())
    np.testing.assert_allclose(out, 3.25, rtol=1e-6)


def test_identity_weights():
    """Center-only weights: the stencil leaves the block as it was."""
    x = _stored((6, 6, 8), seed=1)
    w = jnp.zeros((3, 3, 3), jnp.float32).at[1, 1, 1].set(1.0)
    np.testing.assert_allclose(_run(x, w), x, rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(
    z=st.sampled_from([1, 2, 4]), y=st.sampled_from([1, 2, 6, 22]),
    x=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**16),
)
def test_stencil_property(z, y, x, seed):
    stored = _stored((z, y, x), seed=seed)
    w = _weights(seed)
    _check(stored, w, _run(stored, w), 2e-5)


@pytest.mark.parametrize("interpret", INTERPRETERS, ids=INTERPRETER_IDS)
@pytest.mark.parametrize("shape,rows", [
    ((5, 32, 256), 8),        # several grid steps and row chunks
    ((7, 45, 128), 16),       # Y + 2 not a multiple of 8
])
def test_carried_planes(interpret, shape, rows):
    """Z >= 4: every plane after the second is computed from planes the
    kernel carried from earlier grid steps, whose HBM copy is already
    overwritten."""
    stored = _stored(shape, seed=2)
    w = _weights(2)
    _check(stored, w, _run(stored, w, rows=rows, interpret=interpret), 1e-5)


@pytest.mark.parametrize("interpret", INTERPRETERS, ids=INTERPRETER_IDS)
@pytest.mark.parametrize("shape", [(1, 30, 128), (6, 1, 128)],
                         ids=["z-shell", "y-shell"])
def test_overlap_shells(interpret, shape):
    """``overlap``'s boundary shells: three planes, or three rows a plane."""
    stored = _stored(shape, seed=3)
    w = _weights(3)
    _check(stored, w, _run(stored, w, interpret=interpret), 1e-5)


def test_lane_axis_must_fill_vector_registers():
    with pytest.raises(AssertionError, match="multiple of 128"):
        stencil27(jnp.zeros((4, 4, 96), jnp.float32), jacobi_weights())
