"""jit'd public wrappers for the Pallas kernels.

* :func:`stencil_apply` — compile + run a stencil Program through the
  generated dataflow kernels (the paper's main artifact).
* :func:`sliding_window_attention` — SWA with GQA handling; drop-in for the
  jnp path in ``models.layers`` when running on TPU (or validating in
  interpret mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import compile_program
from .swa import swa_pallas


def stencil_apply(program, grid, fields, scalars=None, coeffs=None,
                  *, strategy: str = "auto"):
    ex = compile_program(program, grid, backend="pallas", strategy=strategy)
    return ex(fields, scalars or {}, coeffs or {})


@functools.partial(jax.jit, static_argnames=("window", "q_block"))
def sliding_window_attention(q, k, v, *, window: int, q_block: int = 128):
    """q: (B,S,H,D); k, v: (B,S,KV,D) — KV heads repeated here for GQA."""
    H, KV = q.shape[2], k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    return swa_pallas(q, k, v, window=window, q_block=q_block)
