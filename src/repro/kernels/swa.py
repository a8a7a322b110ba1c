"""Sliding-window attention as a Pallas stencil kernel.

The paper's shift buffer applied to the sequence dimension: a query tile of
``Bq`` positions attends to KV positions ``[q0 - w, q0 + Bq)`` — an
*overlapping window* over the KV sequence, exactly the Element-indexed halo
window the stencil backend uses over grid axes (halo_lo = window, halo_hi =
0).  Each KV element is fetched into VMEM once per query tile instead of
once per query — the same reuse the FPGA shift register buys.

Grid: (batch, heads, q_tiles).  Block layout keeps the head dim on lanes
(Dh is 64..256 on the assigned archs) and the window on sublanes.  Softmax
is computed tile-locally (the whole window is in VMEM — no running-max
pass needed, unlike global flash attention).

Validated in the Pallas interpreter against ``ref.swa_reference``; on TPU
the same code lowers through Mosaic (:func:`repro.hw.pallas_interpret`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from .. import hw


def _kv_spec(slab, D, index_map):
    return pl.BlockSpec((1, 1, pl.Element(slab), pl.Element(D)), index_map)


def swa_pallas(q, k, v, *, window: int, q_block: int = 128):
    """q, k, v: (B, S, H, D) with H already GQA-repeated.  Causal SWA.

    Returns (B, S, H, D).  ``window`` counts the current position, i.e.
    position i attends to (i-window, i].
    """
    B, S, H, D = q.shape
    w = int(window)
    Bq = min(q_block, S)
    if S % Bq:
        raise ValueError(f"S={S} not divisible by q_block={Bq}")
    nq = S // Bq
    slab = w + Bq                       # KV window per query tile
    scale = 1.0 / math.sqrt(D)

    # layout: (B, H, S, D) so the kernel tiles are (tile, D) matrices
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # halo-pad the KV sequence on the left (zero halo; masked anyway)
    kp = jnp.pad(kt, ((0, 0), (0, 0), (w, 0), (0, 0)))
    vp = jnp.pad(vt, ((0, 0), (0, 0), (w, 0), (0, 0)))

    def kernel(q_ref, k_ref, v_ref, o_ref):
        i = pl.program_id(2)
        qb = q_ref[0, 0].astype(jnp.float32)          # (Bq, D)
        kb = k_ref[0, 0].astype(jnp.float32)          # (slab, D)
        vb = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bq, slab)
        qpos = i * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, slab), 0)
        kpos = (i * Bq - w
                + jax.lax.broadcasted_iota(jnp.int32, (Bq, slab), 1))
        ok = (kpos <= qpos) & (kpos > qpos - w) & (kpos >= 0)
        logits = jnp.where(ok, logits, -1e30)
        m = logits.max(axis=1, keepdims=True)
        p = jnp.exp(logits - m)
        denom = p.sum(axis=1, keepdims=True)
        out = jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) / denom
        o_ref[0, 0] = out.astype(o_ref.dtype)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
            _kv_spec(slab, D, lambda b, h, i: (b, h, i * Bq, 0)),
            _kv_spec(slab, D, lambda b, h, i: (b, h, i * Bq, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Bq, D), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=hw.pallas_interpret(),
    )(qt, kp, vp)
    return out.transpose(0, 2, 1, 3)
