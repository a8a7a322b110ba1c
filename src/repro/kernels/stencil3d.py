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

from .. import hw, obs
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
                     global_extent: Sequence[int] | None = None,
                     update=None, update_fields: Sequence[str] = (),
                     drop_outputs: Sequence[str] = (), carry_pad=None):
    """Build a callable(padded_inputs, scalars, coeffs, origin) -> outputs.

    ``padded_inputs`` must be padded by ``pad_lo``/``pad_hi`` (exposed on the
    returned callable).  ``origin`` gives the shard's global offset per axis
    (defaults to zeros); ``global_extent`` the global domain size (defaults
    to ``grid_shape``) — together they define the out-of-domain mask for
    margin-extended recompute.

    With ``update`` (the fused loop's rule cut to ``update_fields``:
    :meth:`~repro.core.lower_pallas.RuleTrace.tile_rule`) the kernel ends
    in an epilogue that applies it to its tile — the centres of its input
    windows, its outputs at margin 0 and the scalars in SMEM, as
    ``update.reads`` names them — and stores the new values of those
    persistent fields beside its outputs, returned under the fields'
    names.  The fused loop asks for that only where the group holds
    everything they read (``rebuild`` on the returned callable builds the
    same group again with other options).  ``drop_outputs`` names
    outputs only the rule reads, which the kernel then never stores.

    ``carry_pad`` maps stored names to the ``(ndim, 2)`` padding of a
    fused-loop carry buffer (``TimeLoopSpec.field_pad``, alignment slab
    included).  The kernel stores each such value straight into that
    layout: its tile's planes at their place along axis 0, and along every
    other axis the whole padded extent, a zero ring around the interior;
    planes past the grid on the last tile (hi halo and alignment slab) are
    stored as zeros.  The call then takes the buffer to write as
    ``back[name]``, aliased to the output and never read, and returns it
    whole; the planes it does not write, the axis-0 halo, keep what the
    buffer held.  That needs a leading axis 0 that alone is tiled
    (``writes_carry`` on the returned callable).
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
    # what the kernel stores: its outputs, less those only the update rule
    # read, then the fields the rule's epilogue computes
    store_names = ([f for f in out_names if f not in set(drop_outputs)]
                   + list(update_fields))
    # the carry's planes are a leading axis, the only one tiled: a tile
    # then holds whole planes of the untiled axes, ring included
    writes_carry = ndim >= 3 and all(t == 1 for t in tiles[1:])
    carry_pad = {f: np.asarray(v, dtype=np.int64)
                 for f, v in (carry_pad or {}).items()}
    carry_names = [f for f in store_names if f in carry_pad]
    if len(carry_names) != len(carry_pad) or (carry_pad and not writes_carry):
        raise ValueError(
            f"cannot store {sorted(carry_pad)} in a carry layout: the "
            f"group stores {store_names} on tiles {tiles}")
    carry_shape = {}
    for f, pad in carry_pad.items():
        if (pad < 0).any() or pad[0, 1] < align_hi[0]:
            raise ValueError(f"carry padding {pad.tolist()} of {f!r} holds "
                             f"no alignment slab of {align_hi[0]} planes")
        carry_shape[f] = tuple(grid_shape[a] + int(pad[a].sum())
                               for a in range(ndim))
    coeff_axis = {c: p.coeffs[c] for c in gh.group_coeffs}
    # a coefficient vector lies along its own axis (extent 1 on the others),
    # resident whole: the kernel slices it without a relayout, and a tile's
    # slice along a tiled (leading, untiled-layout) axis may start anywhere
    coeff_shape = {c: tuple(halo_lo[a] + padded_out[a] + halo_hi[a]
                            if a == ax else 1 for a in range(ndim))
                   for c, ax in coeff_axis.items()}
    # the axes along which each op needs the zero-halo mask on
    # margin-extended recompute: along a periodic axis the op's wraparound
    # windows make the recomputed values exact, so masking them to zero
    # would be wrong; along a zero axis its out-of-domain values must read
    # as 0 downstream
    kinds = p.axis_boundaries()
    masked = {op.out: (tuple(ax for ax in range(ndim)
                             if kinds[op.out][ax] == "zero")
                       if margins[op.out].any() else ())
              for op in ops}

    def centre(m):
        """The tile's own points in a value computed at margin ``m``."""
        return tuple(slice(int(m[ax, 0]), int(m[ax, 0]) + block[ax])
                     for ax in range(ndim))

    def kernel(*refs):
        i = 0
        s_ref = refs[i]; i += 1                      # (1, n) scalars, SMEM
        org_ref = refs[i]; i += 1                    # (1, ndim) origin, SMEM
        in_refs = {f: refs[i + k] for k, f in enumerate(gh.group_inputs)}
        i += len(gh.group_inputs)
        coeff_refs = {c: refs[i + k] for k, c in enumerate(gh.group_coeffs)}
        i += len(gh.group_coeffs) + len(carry_names)  # back buffers: unread
        out_refs = {f: refs[i + k] for k, f in enumerate(store_names)}

        def store(f, value):
            """Store the tile's value of ``f``, in its carry layout where
            ``carry_pad`` names it: a zero ring along the untiled axes, and
            zeros on the planes past the grid."""
            ref = out_refs[f]
            if f not in carry_pad:
                ref[...] = value
                return
            if align_hi[0]:
                plane = (pl.program_id(0) * block[0]
                         + jax.lax.broadcasted_iota(jnp.int32, block, 0))
                value = jnp.where(plane < grid_shape[0], value,
                                  jnp.asarray(0, dtype=dtype))
            lo = [int(carry_pad[f][a, 0]) for a in range(ndim)]
            ext = ref.shape
            for a in range(1, ndim):
                for start, stop in ((0, lo[a]), (lo[a] + block[a], ext[a])):
                    if stop > start:
                        ring = tuple(slice(start, stop) if b == a
                                     else slice(None) for b in range(ndim))
                        ref[ring] = jnp.zeros(
                            tuple(stop - start if b == a else ext[b]
                                  for b in range(ndim)), dtype)
            ref[(slice(None),) + tuple(slice(lo[a], lo[a] + block[a])
                                       for a in range(1, ndim))] = value

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
                # domain along a zero axis must read as 0 to downstream
                # consumers.
                mask = None
                for ax in masked[op.out]:
                    g0 = (org_ref[0, ax] + pl.program_id(ax) * block[ax]
                          - int(m[ax, 0]))
                    coord = g0 + jax.lax.broadcasted_iota(jnp.int32, ext, ax)
                    ok = (coord >= 0) & (coord < global_extent[ax])
                    mask = ok if mask is None else (mask & ok)
                res = jnp.where(mask, res, jnp.asarray(0, dtype=dtype))
            results[op.out] = res
            if op.out in out_refs:
                store(op.out, res[centre(m)])

        if update_fields:
            # the update rule on this tile: every value it reads for these
            # fields is here, at the tile's own points
            reads = update.reads
            held = {f: windows[f][centre(gh.input_halo)]
                    for f in reads["field"]}
            outs = {f: results[f][centre(margins[f])]
                    for f in reads["output"]}
            new = update(held, outs, {s: scalar(s) for s in reads["scalar"]},
                         block)
            for f in update_fields:
                store(f, jnp.broadcast_to(jnp.asarray(new[f], dtype=dtype),
                                          block))

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
    # the back buffers a carry layout is stored in, left in HBM
    aliases = {len(in_specs) + k: store_names.index(f)
               for k, f in enumerate(carry_names)}
    in_specs += [pl.BlockSpec(memory_space=pl.ANY) for _ in carry_names]

    def out_spec(f):
        if f not in carry_pad:
            return pl.BlockSpec(block, lambda *idx: tuple(idx))
        # the tile's planes at their carry offset, each whole; Mosaic
        # takes element offsets only on every axis of a block at once
        lo0 = int(carry_pad[f][0, 0])
        return pl.BlockSpec(
            (pl.Element(block[0]),) + tuple(pl.Element(s)
                                            for s in carry_shape[f][1:]),
            lambda *idx: (lo0 + idx[0] * block[0],) + (0,) * (ndim - 1))

    out_specs = tuple(out_spec(f) for f in store_names)
    out_shape = tuple(jax.ShapeDtypeStruct(carry_shape.get(f, padded_out),
                                           dtype)
                      for f in store_names)

    call = pl.pallas_call(
        kernel,
        grid=tiles,
        in_specs=in_specs,
        out_specs=out_specs if len(store_names) > 1 else out_specs[0],
        out_shape=out_shape if len(store_names) > 1 else out_shape[0],
        input_output_aliases=aliases,
        compiler_params=hw.pallas_compiler_params(("parallel",) * ndim),
        interpret=hw.pallas_interpret(),
        # the group's outputs name the kernel in the HLO and the trace
        name="blk_" + "_".join(out_names),
    )

    crop = tuple(slice(0, grid_shape[a]) for a in range(ndim))

    expect = tuple(halo_lo[a] + padded_out[a] + halo_hi[a]
                   for a in range(ndim))

    def run(padded_inputs: dict, scalars_vec=None,
            padded_coeffs: dict | None = None, origin=None,
            input_pad: dict | None = None, back: dict | None = None):
        """``input_pad[f]`` gives the (ndim, 2) padding the provided array
        actually carries when it exceeds this group's window geometry —
        e.g. a fused time loop's carry-resident persistent buffer sized for
        the worst consuming group.  The window is sliced out statically; no
        reallocation or copy of the halo slabs happens here.  ``back``
        holds the buffer each ``carry_pad`` name is stored into."""
        with obs.phase("window"):
            svec = (scalars_vec if scalars_vec is not None
                    else jnp.zeros((max(n_scalars, 1),), jnp.float32))
            org = (origin if origin is not None
                   else jnp.zeros((ndim,), jnp.int32))
            # scalars and origin are (1, n) rows, coefficients span only
            # their own axis: vmap then prepends the batch axis ahead of the
            # last two, where Mosaic's block rule allows it
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
            args += [back[f] for f in carry_names]
        res = call(*args)
        if len(store_names) == 1:
            res = (res,)
        with obs.phase("window"):
            return {f: r if f in carry_pad else r[crop]
                    for f, r in zip(store_names, res)}

    # geometry for orchestrators (lower_pallas pads with zeros; distribute
    # pads via halo exchange)
    run.group_inputs = gh.group_inputs
    run.group_outputs = out_names
    run.update_fields = tuple(update_fields)
    run.writes_carry = writes_carry
    run.carry_names = tuple(carry_names)
    options = dict(update=update, update_fields=update_fields,
                   drop_outputs=drop_outputs, carry_pad=carry_pad)
    run.rebuild = lambda **changes: build_group_call(
        p, group, block, grid_shape, dtype=dtype,
        global_extent=global_extent, **{**options, **changes})
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
