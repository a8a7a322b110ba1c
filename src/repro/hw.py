"""Target-hardware constants (TPU v5e) used by planners and roofline analysis.

These numbers describe the *target* the plans, kernels and rooflines are
derived for, whatever backend the process runs on.  Sources: public TPU v5e
datasheet figure (819 GB/s HBM).
"""

from __future__ import annotations

import dataclasses

import jax
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    hbm_bandwidth: float        # bytes/s per chip
    vmem_bytes: int             # per-core VMEM
    sublanes: int = 8
    lanes: int = 128


TPU_V5E = ChipSpec(hbm_bandwidth=819e9, vmem_bytes=128 * 1024**2)

# Scoped-VMEM limit every generated Pallas kernel is compiled with (Mosaic's
# own default is 16 MiB, below what the planner's plans claim).
VMEM_LIMIT_BYTES = TPU_V5E.vmem_bytes // 2
# Budget the stencil planner prices a kernel instance against (windows,
# margin-extended temps and outputs, already doubled for the Pallas
# pipeline's two copies of every block): half the compiler's limit, leaving
# the other half for the values Mosaic materialises while evaluating the
# kernel body (concatenated windows, masks, spills).
VMEM_PLAN_BUDGET = VMEM_LIMIT_BYTES // 2

LANE = TPU_V5E.lanes
SUBLANE = TPU_V5E.sublanes

# field storage dtypes the planner/cost models understand
DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float64": 8}


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in the interpreter: compiled Mosaic on a
    TPU backend, the interpreter anywhere else (the CPU test suite)."""
    return jax.default_backend() != "tpu"


def pallas_compiler_params(dimension_semantics) -> object:
    """Mosaic compiler parameters of a generated kernel: the scoped-VMEM
    limit the planner's budget is derived from, and the semantics of each
    grid axis (``"parallel"`` for independent tiles, ``"arbitrary"`` for a
    sweep whose scratch carries state from one grid step to the next)."""
    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def align_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def align_down(x: int, m: int) -> int:
    return (x // m) * m
