"""Generic Pallas stencil kernel builder (pl.pallas_call + BlockSpec).

This is the TPU materialisation of the paper's shift buffer + dataflow
structure, generated *from the IR* (nothing here is hand-specialised to a
particular stencil):

* **shift buffer**  -> each external input is fetched as an overlapping VMEM
  window (``Element``-indexed BlockSpec over a halo-padded HBM array).  The
  window holds *all* neighbourhood values an op may touch — the 3/9/27-value
  property of the paper's 1-/2-/3-D shift buffers (Fig. 2).
* **hls.dataflow stage concurrency** -> the Pallas grid pipeline: the DMA for
  grid step i+1 is in flight while step i computes and step i-1 stores
  (load_data / shift_buffer / compute / write_data overlap).
* **single load_data stage** -> every op in the fuse group slices the same
  VMEM windows; shared subtrees evaluate once (hash-consed memo).
* **per-field dataflow split** -> one output Ref per produced field; ops with
  in-group dependencies are recomputed on extended margins (overlapped
  tiling) exactly as planned by ``passes.infer_halo``.
* **small data -> BRAM** -> runtime scalars and the shard origin live in SMEM;
  1-D per-level coefficients ride in as lane-resident windows.
* **512-bit bursts** -> the planner lane-aligns the last block axis (x128).

Zero-halo semantics: margin-extended recompute is masked against the *global*
domain (the kernel receives the shard origin at runtime), so fused overlapped
tiling is bit-compatible with streamed per-field execution on any shard of a
distributed run.

Runs compiled through Mosaic on a TPU and in the Pallas interpreter
elsewhere (:func:`repro.hw.pallas_interpret` decides).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import hw
from ..core.expr_eval import evaluate
from ..core.ir import Access, Program
from ..core.passes import GroupHalo, infer_halo


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _window_spec(shape, index_map):
    """Overlapping VMEM window: the index map yields element offsets."""
    return pl.BlockSpec(tuple(pl.Element(s) for s in shape), index_map)


def build_group_call(p: Program, group: Sequence[int], block: Sequence[int],
                     grid_shape: Sequence[int], dtype=jnp.float32,
                     global_extent: Sequence[int] | None = None):
    """Build a callable(padded_inputs, scalars, coeffs, origin) -> outputs.

    ``padded_inputs`` must be padded by ``pad_lo``/``pad_hi`` (exposed on the
    returned callable).  ``origin`` gives the shard's global offset per axis
    (defaults to zeros); ``global_extent`` the global domain size (defaults
    to ``grid_shape``) — together they define the out-of-domain mask for
    margin-extended recompute.
    """
    ndim = p.ndim
    gh: GroupHalo = infer_halo(p, group)
    block = tuple(min(int(b), int(g)) for b, g in zip(block[:ndim], grid_shape))
    grid_shape = tuple(int(g) for g in grid_shape)
    if global_extent is None:
        global_extent = grid_shape
    global_extent = tuple(int(g) for g in global_extent)
    tiles = tuple(_cdiv(grid_shape[a], block[a]) for a in range(ndim))
    padded_out = tuple(tiles[a] * block[a] for a in range(ndim))
    halo_lo = tuple(int(gh.input_halo[a, 0]) for a in range(ndim))
    halo_hi = tuple(int(gh.input_halo[a, 1]) for a in range(ndim))
    align_hi = tuple(padded_out[a] - grid_shape[a] for a in range(ndim))
    win = tuple(block[a] + halo_lo[a] + halo_hi[a] for a in range(ndim))

    group = list(group)
    ops = [p.ops[i] for i in group]
    margins = {p.ops[i].out: gh.margins[i] for i in group}
    produced = {p.ops[i].out for i in group}
    n_scalars = len(p.scalars)
    scalar_index = {s: i for i, s in enumerate(p.scalars)}
    out_names = [op.out for op in ops if op.out in set(gh.group_outputs)]
    coeff_axis = {c: p.coeffs[c] for c in gh.group_coeffs}
    # a coefficient vector lies along its own axis (extent 1 on the others),
    # resident whole: the kernel slices it without a relayout, and a tile's
    # slice along a tiled (leading, untiled-layout) axis may start anywhere
    coeff_shape = {c: tuple(halo_lo[a] + padded_out[a] + halo_hi[a]
                            if a == ax else 1 for a in range(ndim))
                   for c, ax in coeff_axis.items()}
    # which ops need the zero-halo mask on margin-extended recompute: a
    # periodic op's wraparound windows make the recomputed values exact at
    # every position, so masking them to zero would be wrong; a zero-BC
    # op's out-of-domain values must read as 0 downstream
    masked = {op.out: (margins[op.out].any()
                       and p.fields[op.out].boundary != "periodic")
              for op in ops}

    def kernel(*refs):
        i = 0
        s_ref = refs[i]; i += 1                      # (1, n) scalars, SMEM
        org_ref = refs[i]; i += 1                    # (1, ndim) origin, SMEM
        in_refs = {f: refs[i + k] for k, f in enumerate(gh.group_inputs)}
        i += len(gh.group_inputs)
        coeff_refs = {c: refs[i + k] for k, c in enumerate(gh.group_coeffs)}
        i += len(gh.group_coeffs)
        out_refs = {f: refs[i + k] for k, f in enumerate(out_names)}

        # single load_data stage: every window loads exactly once
        windows = {f: r[...] for f, r in in_refs.items()}
        results: dict = {}
        memo: dict = {}

        def scalar(name: str):
            return s_ref[0, scalar_index[name]]

        for op in ops:
            m = margins[op.out]

            def coeff(c, m=m):
                ax = coeff_axis[c.coeff]
                start = int(gh.input_halo[ax, 0] - m[ax, 0] + c.offset)
                size = block[ax] + int(m[ax, 0]) + int(m[ax, 1])
                if tiles[ax] > 1:       # this tile's slice of the vector
                    start = pl.program_id(ax) * block[ax] + start
                return coeff_refs[c.coeff][tuple(
                    pl.ds(start, size) if a == ax else slice(None)
                    for a in range(ndim))]

            def access(a: Access, m=m):
                sl = []
                if a.field in produced:
                    src = results[a.field]
                    pm = margins[a.field]
                    for ax in range(ndim):
                        start = int(pm[ax, 0] - m[ax, 0] + a.offset[ax])
                        size = block[ax] + int(m[ax, 0]) + int(m[ax, 1])
                        sl.append(slice(start, start + size))
                else:
                    src = windows[a.field]
                    for ax in range(ndim):
                        start = int(gh.input_halo[ax, 0] - m[ax, 0] + a.offset[ax])
                        size = block[ax] + int(m[ax, 0]) + int(m[ax, 1])
                        sl.append(slice(start, start + size))
                return src[tuple(sl)]

            # memo shared across ops at the same margin (hash-consed CSE);
            # different margins slice different extents
            mkey = tuple(int(v) for v in m.flatten())
            op_memo = memo.setdefault(mkey, {})
            res = evaluate(op.expr, access, scalar, op_memo, coeff=coeff)
            ext = tuple(block[ax] + int(m[ax, 0]) + int(m[ax, 1])
                        for ax in range(ndim))
            res = jnp.broadcast_to(jnp.asarray(res, dtype=dtype), ext)
            if masked[op.out]:
                # zero-halo semantics: recomputed values OUTSIDE the global
                # domain must read as 0 to downstream consumers.
                mask = None
                for ax in range(ndim):
                    g0 = (org_ref[0, ax] + pl.program_id(ax) * block[ax]
                          - int(m[ax, 0]))
                    coord = g0 + jax.lax.broadcasted_iota(jnp.int32, ext, ax)
                    ok = (coord >= 0) & (coord < global_extent[ax])
                    mask = ok if mask is None else (mask & ok)
                res = jnp.where(mask, res, jnp.asarray(0, dtype=dtype))
            results[op.out] = res
            if op.out in out_refs:
                center = tuple(slice(int(m[ax, 0]), int(m[ax, 0]) + block[ax])
                               for ax in range(ndim))
                out_refs[op.out][...] = res[center]

    def window_map(*idx):
        return tuple(idx[a] * block[a] for a in range(ndim))

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),   # scalars
                pl.BlockSpec(memory_space=pltpu.SMEM)]   # origin
    for _ in gh.group_inputs:
        in_specs.append(_window_spec(
            tuple(win[a] for a in range(ndim)), window_map))
    for c in gh.group_coeffs:
        in_specs.append(pl.BlockSpec(coeff_shape[c],
                                     lambda *idx: (0,) * ndim))
    out_specs = tuple(pl.BlockSpec(block, lambda *idx: tuple(idx))
                      for _ in out_names)
    out_shape = tuple(jax.ShapeDtypeStruct(padded_out, dtype) for _ in out_names)

    call = pl.pallas_call(
        kernel,
        grid=tiles,
        in_specs=in_specs,
        out_specs=out_specs if len(out_names) > 1 else out_specs[0],
        out_shape=out_shape if len(out_names) > 1 else out_shape[0],
        compiler_params=hw.pallas_compiler_params(("parallel",) * ndim),
        interpret=hw.pallas_interpret(),
    )

    crop = tuple(slice(0, grid_shape[a]) for a in range(ndim))

    expect = tuple(halo_lo[a] + padded_out[a] + halo_hi[a]
                   for a in range(ndim))

    def run(padded_inputs: dict, scalars_vec=None,
            padded_coeffs: dict | None = None, origin=None,
            input_pad: dict | None = None):
        """``input_pad[f]`` gives the (ndim, 2) padding the provided array
        actually carries when it exceeds this group's window geometry —
        e.g. a fused time loop's carry-resident persistent buffer sized for
        the worst consuming group.  The window is sliced out statically; no
        reallocation or copy of the halo slabs happens here."""
        svec = (scalars_vec if scalars_vec is not None
                else jnp.zeros((max(n_scalars, 1),), jnp.float32))
        org = (origin if origin is not None
               else jnp.zeros((ndim,), jnp.int32))
        # scalars and origin are (1, n) rows, coefficients span only their
        # own axis: vmap then prepends the batch axis ahead of the last two,
        # where Mosaic's block rule allows it
        args = [svec.reshape(1, -1), org.reshape(1, -1)]
        for f in gh.group_inputs:
            x = padded_inputs[f]
            if input_pad is not None and f in input_pad:
                ip = input_pad[f]
                sl = tuple(slice(int(ip[a][0]) - halo_lo[a],
                                 int(ip[a][0]) - halo_lo[a] + expect[a])
                           for a in range(ndim))
                x = x[sl]
            args.append(x)
        for c in gh.group_coeffs:
            args.append(padded_coeffs[c].reshape(coeff_shape[c]))
        res = call(*args)
        if len(out_names) == 1:
            res = (res,)
        return {f: r[crop] for f, r in zip(out_names, res)}

    # geometry for orchestrators (lower_pallas pads with zeros; distribute
    # pads via halo exchange)
    run.group_inputs = gh.group_inputs
    run.group_outputs = out_names
    run.group_coeffs = gh.group_coeffs
    run.coeff_axis = coeff_axis
    run.block = block
    run.halo_lo = halo_lo
    run.halo_hi = halo_hi
    run.align_hi = align_hi
    run.pad_lo = halo_lo
    run.pad_hi = tuple(halo_hi[a] + align_hi[a] for a in range(ndim))
    run.window = win
    run.tiles = tiles
    run.vmem_window_bytes = int(np.prod(win)) * len(gh.group_inputs) * np.dtype(
        np.float32 if dtype == jnp.float32 else np.float16).itemsize
    return run
