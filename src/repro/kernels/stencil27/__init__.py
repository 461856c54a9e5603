from repro.kernels.stencil27.stencil27 import stencil27
from repro.kernels.stencil27.ref import stencil27_ref, jacobi_weights

__all__ = ["stencil27", "stencil27_ref", "jacobi_weights"]
