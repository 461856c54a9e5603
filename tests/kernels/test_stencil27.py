"""27-point stencil kernel vs pure-jnp oracle + conservation properties."""

import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import given, settings, st  # hypothesis or deterministic fallback

from repro.kernels.stencil27 import jacobi_weights, stencil27, stencil27_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,tile", [
    ((8, 8, 16), (4, 8)),
    ((6, 10, 12), (2, 8)),    # a row chunk plus a static tail
    ((4, 4, 4), (4, 8)),      # one step, tail only
    ((16, 8, 32), (8, 16)),
])
def test_stencil_matches_ref(dtype, shape, tile):
    rng = np.random.default_rng(0)
    ghosted = tuple(s + 2 for s in shape)
    x = jnp.asarray(rng.normal(size=ghosted), dtype)
    w = jnp.asarray(rng.normal(size=(3, 3, 3)), jnp.float32)
    got = stencil27(x, w, tile=tile, interpret=True)
    want = stencil27_ref(x, w)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_jacobi_constant_field_is_fixed_point():
    """Normalized box weights: a constant field maps to itself."""
    x = jnp.full((10, 10, 10), 3.25, jnp.float32)
    out = stencil27(x, jacobi_weights(), tile=(8, 8), interpret=True)
    np.testing.assert_allclose(np.asarray(out), 3.25, rtol=1e-6)


def test_identity_weights():
    """Center-only weights: stencil is the identity on the interior."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 8, 8)), jnp.float32)
    w = jnp.zeros((3, 3, 3), jnp.float32).at[1, 1, 1].set(1.0)
    out = stencil27(x, w, tile=(2, 8), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x[1:-1, 1:-1, 1:-1]),
                               rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(
    z=st.sampled_from([2, 4]), y=st.sampled_from([2, 4, 6]),
    x=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**16),
)
def test_stencil_property(z, y, x, seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(z + 2, y + 2, x + 2)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 3, 3)), jnp.float32)
    got = stencil27(g, w, tile=(2, 8), interpret=True)
    want = stencil27_ref(g, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
