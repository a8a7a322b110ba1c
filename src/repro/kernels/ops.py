"""Public wrapper for the Pallas kernels: :func:`stencil_apply` compiles
and runs a stencil Program through the generated dataflow kernels (the
paper's main artifact)."""

from __future__ import annotations

from ..core import compile_program


def stencil_apply(program, grid, fields, scalars=None, coeffs=None,
                  *, strategy: str = "auto"):
    ex = compile_program(program, grid, backend="pallas", strategy=strategy)
    return ex(fields, scalars or {}, coeffs or {})
