"""Pure-jnp oracle for the Pallas kernels in this package.

``stencil3d`` kernels are validated against ``repro.core.lower_jnp``
(the Von-Neumann reference executes the same IR); this module re-exports
it for the per-kernel tests.
"""

from __future__ import annotations

from ..core import lower_jnp


def stencil_reference(program, fields, scalars=None, coeffs=None):
    """Oracle for kernels built by stencil3d.build_group_call."""
    return lower_jnp.lower(program, mode="naive")(fields, scalars or {},
                                                  coeffs or {})
