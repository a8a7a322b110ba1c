"""Stream backend: StreamGraph regions -> shift-register Pallas kernels.

This is the hardware materialisation of the dataflow layer
(:mod:`repro.core.dataflow`), the role the paper's HLS dialect plays for the
FPGA backends.  Per region, one ``pl.pallas_call`` whose **grid iterates
over the outer (stream) axis**, one step per plane:

* each external input field is DMA'd as exactly **one new plane per step**
  (BlockSpec of depth 1) — each input element is fetched from HBM once per
  sweep;
* the shift-register window buffers live in VMEM **scratch that persists
  across grid steps** (the kernel's carry): every step rolls each buffer
  one plane and appends the new plane, so the full stencil window along the
  stream axis is always resident without refetching (paper Fig. 2);
* in-region temps consumed at *past* planes keep a small ring buffer of
  their own recent planes — stream-axis dependencies cost storage, never
  recompute;
* the output plane trails the stream front by the region's lead: the output
  BlockSpec's index map clamps ``step - (lo+hi)`` so warm-up steps write
  (and later overwrite) plane 0, and every plane's final value is computed
  from a full window.

Boundary handling mirrors the block schedule: the orchestrator pre-pads the
stream axis (zero slabs or wraparound planes), non-stream margins are
masked against the global domain along each field's zero axes, and
ring-buffered temps store zeros for out-of-domain planes.

The produced callables expose the same geometry attributes as
``kernels.stencil3d.build_group_call`` (``group_inputs``/``pad_lo``/
``input_pad`` slicing/…), so the generic orchestrators in
:mod:`repro.core.lower_pallas` — including the fused ``lax.fori_loop`` time
loop with carry-resident persistent fields — drive stream and block kernels
identically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import hw, obs
from .dataflow import StreamGraph, StreamRegion, lower_to_dataflow
from .expr_eval import evaluate
from .ir import Access, Program
from .lower_pallas import _DTYPES, lower_from_calls, time_loop_from_calls
from .schedule import DataflowPlan, TimeLoopSpec, adapt_update


def build_stream_call(p: Program, region: StreamRegion, grid_shape,
                      dtype=jnp.float32, global_extent=None,
                      time_tile: int = 1, update=None,
                      stream_sharded: bool = False, plane_tile: int = 1):
    """Build a callable(padded_inputs, scalars, coeffs, origin) -> outputs
    streaming one region over the outer axis (see module docstring).

    ``padded_inputs`` must be padded by ``pad_lo``/``pad_hi`` (exposed on
    the returned callable); oversized persistent buffers ride in via the
    ``input_pad`` path exactly as for block kernels.

    With ``plane_tile = P > 1`` (spatial unrolling, the paper's parallel
    processing elements) each sweep grid step DMAs a **P-plane input
    block**, replays the single-plane pipeline for P consecutive *virtual*
    steps ``t = j*P .. j*P+P-1`` (all masking, ring and coefficient
    indexing keyed off ``t``, so per-plane semantics are bit-identical),
    shifts every window buffer by P planes at once, and stores all P
    completed output planes.  The sweep grid shrinks to
    ``ceil(n0/P) + ceil(span/P)`` steps: the input is rounded up with
    zero planes whose garbage outputs land past the domain and are sliced
    off, and when the warm-up span is not a P-multiple an ``r``-plane
    staging ring realigns completed planes to the P-plane output blocks
    (a trailing remainder therefore needs no separate shallow-tile
    epilogue kernel — scratch could not persist across calls anyway).

    With ``update`` (the already-normalised fused-loop rule) the kernel
    chains ``time_tile = T`` timestep *stages* per sweep step and returns
    the **updated persistent fields after T steps** instead of the stencil
    outputs: stage ``s`` completes interior plane ``c_s = t - lo -
    (s+1)*lead`` at sweep step ``t`` (each stage trails the previous by the
    region's stream lead), the update rule is applied plane-wise after
    every stage, and each later stage reads the *updated* fields out of
    per-stage VMEM rings instead of HBM — one plane fetched from HBM per T
    time steps.  Non-stream margins accumulate one halo step per remaining
    stage, so inputs arrive padded T-fold and stage extents shrink back to
    the grid by stage T-1, whose updated planes are stored.  The chain
    assumes an *element-wise* update rule (the fused-loop contract): it is
    applied per plane at each stage's working extent.

    ``stream_sharded`` marks the stream axis as domain-decomposed: the
    caller (the SPMD orchestrator) then pads the lo side of the stream axis
    with *exact* neighbour ghost planes — ``T x`` the region's (already
    ring-deepened) per-step lo halo, mirroring :func:`~repro.core.dataflow.
    chained_halo` — so every chain stage warms up on true values before the
    shard's first owned plane.  ``region.halo`` must come from a graph
    lowered with the same flag.  Unsharded sweeps keep the shallow lo pad;
    a 1x1 mesh therefore traces the identical kernel to a local compile.
    """
    ndim = p.ndim
    gh = region.halo
    T = max(1, int(time_tile))
    if T > 1 and update is None:
        raise ValueError("time_tile > 1 chains timestep stages in-kernel "
                         "and needs the fused-loop update rule")
    grid_shape = tuple(int(g) for g in grid_shape)
    if global_extent is None:
        global_extent = grid_shape
    global_extent = tuple(int(g) for g in global_extent)
    n0 = grid_shape[0]
    # per-step region halo (hl/hh) vs the T-chained outer padding (halo_lo/
    # halo_hi = what the caller pads: stream (lo, T*lead), non-stream T-fold)
    hl = tuple(int(gh.input_halo[a, 0]) for a in range(ndim))
    hh = tuple(int(gh.input_halo[a, 1]) for a in range(ndim))
    lead = hh[0]
    # lo-side stream pad: shallow locally (warm-up planes are masked
    # out-of-domain), chain-deepened exact ghosts under a sharded axis
    halo_lo = ((T * hl[0]) if stream_sharded else hl[0],) \
        + tuple(T * hl[a] for a in range(1, ndim))
    halo_hi = (T * lead,) + tuple(T * hh[a] for a in range(1, ndim))
    span = halo_lo[0] + halo_hi[0]    # stream reach of the whole chain
    n_steps = n0 + span               # padded planes = one *virtual* step each
    # spatial unrolling: P virtual steps per sweep grid step
    P = max(1, int(plane_tile))
    if P > n0:
        raise ValueError(
            f"plane_tile {P} exceeds the stream extent {n0}; "
            "dataflow.plane_split_reason should have demoted it")
    n_out = -(-n0 // P)          # P-plane output blocks
    K = -(-span // P)            # warm-up grid steps before block 0 is final
    stage_r = K * P - span       # staging planes realigning output to blocks
    n_tiles = n_out + K          # sweep grid steps
    pad_round = n_tiles * P - n_steps   # hi-side zero planes rounding the DMA
    # padded plane extents on the non-stream axes (group-uniform halo)
    plane_ext = tuple(grid_shape[a] + halo_lo[a] + halo_hi[a]
                      for a in range(1, ndim))
    # margin every remaining chain stage adds on the non-stream axes
    stage_add = np.zeros((ndim, 2), dtype=np.int64)
    for a in range(1, ndim):
        stage_add[a] = (hl[a], hh[a])

    ops = [p.ops[i] for i in region.ops]
    margins = {p.ops[i].out: gh.margins[i] for i in region.ops}
    produced = {op.out for op in ops}
    out_names = [op.out for op in ops if op.out in set(gh.group_outputs)]
    # with an update rule the sweep advances time in-kernel and the stored
    # arrays are the updated persistent fields, not the stencil outputs
    store_names = list(gh.group_inputs) if update is not None else out_names
    coeff_axis = {c: p.coeffs[c] for c in gh.group_coeffs}
    depths = {f: int(region.depths[f]) for f in gh.group_inputs}
    ring_depth = {t: int(r) for t, r in region.rings.items()}
    ring_names = [op.out for op in ops if op.out in ring_depth]
    n_scalars = len(p.scalars)
    scalar_index = {s: i for i, s in enumerate(p.scalars)}
    # stage s evaluates every op at its base margin plus (T-1-s) accumulated
    # halo steps (chained stages shrink back toward the grid); masking of a
    # stage's results follows the *stage* margins — non-stream recompute
    # needs the zero-halo mask along the axes where the field is zero
    # (wrapped planes are exact along its periodic axes); the stream axis
    # itself is handled by input padding + ring-store masking, never here
    kinds = p.axis_boundaries()
    stage_margins = [{out: m + (T - 1 - s) * stage_add
                      for out, m in margins.items()} for s in range(T)]
    # per-(stage, field) ring-plane extents: stage s reads updated fields
    # padded by (T-s) halo steps, exactly what stage s-1's update produced
    ring_plane_ext = [tuple(grid_shape[a] + (T - s) * (hl[a] + hh[a])
                            for a in range(1, ndim)) for s in range(T)]

    def plane_slices(src_lo, m, offset):
        """Non-stream-axes slice of a resident plane padded by ``src_lo``,
        evaluated at margin ``m`` with access ``offset``."""
        sl = []
        for ax in range(1, ndim):
            start = int(src_lo[ax] - m[ax, 0] + offset[ax])
            size = grid_shape[ax] + int(m[ax, 0]) + int(m[ax, 1])
            sl.append(slice(start, start + size))
        return tuple(sl)

    def kernel(*refs):
        i = 0
        s_ref = refs[i]; i += 1                      # (1, n) scalars, SMEM
        org_ref = refs[i]; i += 1                    # (1, ndim) origin, SMEM
        in_refs = {f: refs[i + k] for k, f in enumerate(gh.group_inputs)}
        i += len(gh.group_inputs)
        coeff_refs = {c: refs[i + k] for k, c in enumerate(gh.group_coeffs)}
        i += len(gh.group_coeffs)
        out_refs = {f: refs[i + k] for k, f in enumerate(store_names)}
        i += len(store_names)
        buf_refs = {f: refs[i + k] for k, f in enumerate(gh.group_inputs)}
        i += len(gh.group_inputs)
        # per-stage rings of the *updated* persistent fields (stages 1..T-1)
        field_refs = [None]
        for _ in range(1, T):
            field_refs.append({f: refs[i + k]
                               for k, f in enumerate(gh.group_inputs)})
            i += len(gh.group_inputs)
        # per-stage temp rings (each chain stage recomputes its own temps)
        stage_ring_refs = []
        for _ in range(T):
            stage_ring_refs.append({t: refs[i + k]
                                    for k, t in enumerate(ring_names)})
            i += len(ring_names)
        # output staging ring: realigns completed planes to P-plane blocks
        # when the warm-up span is not a P-multiple
        stage_out_refs = {}
        if stage_r > 0:
            stage_out_refs = {f: refs[i + k]
                              for k, f in enumerate(store_names)}
            i += len(store_names)

        j_step = pl.program_id(0)

        @pl.when(j_step == 0)
        def _init():                    # fresh sweep: clear the carry
            carried = list(buf_refs.values())
            for s in range(1, T):
                carried += list(field_refs[s].values())
            for s in range(T):
                carried += list(stage_ring_refs[s].values())
            carried += list(stage_out_refs.values())
            for r in carried:
                r[...] = jnp.zeros_like(r)

        # append the P newly DMA'd planes behind every window buffer (the
        # single HBM fetch per plane per sweep); virtual step k's window is
        # cats[f][k+1 : k+1+depth], and the buffers commit a P-plane shift
        # once at the end of the grid step
        cats = {}
        for f in gh.group_inputs:
            cats[f] = jnp.concatenate([buf_refs[f][...], in_refs[f][...]],
                                      axis=0)
        field_vals = [None] + [{f: field_refs[s][f][...]
                                for f in gh.group_inputs}
                               for s in range(1, T)]
        ring_vals_all = [{t: stage_ring_refs[s][t][...] for t in ring_names}
                         for s in range(T)]
        coeff_windows = {c: r[0] for c, r in coeff_refs.items()}

        def scalar(name: str):
            return s_ref[0, scalar_index[name]]

        sdict = {nm: s_ref[0, scalar_index[nm]] for nm in p.scalars}
        completed = {f: [] for f in store_names}

        for k_plane in range(P):
            # virtual step: replays the single-plane sweep semantics with
            # t = j*P + k, so masking/ring/coefficient indexing is
            # bit-identical to the P=1 kernel
            t_step = j_step * P + k_plane
            for s in range(T):
                acc = T - 1 - s
                margins_s = stage_margins[s]
                # the interior plane stage s completes this virtual step
                # (negative during warm-up; the out index map clamps, and
                # every ring store masks by stream validity)
                c_plane = t_step - halo_lo[0] - (s + 1) * lead
                ring_vals = ring_vals_all[s]
                results: dict = {}
                memo: dict = {}

                for op in ops:
                    m = margins_s[op.out]
                    ext = tuple(grid_shape[ax] + int(m[ax, 0])
                                + int(m[ax, 1]) for ax in range(1, ndim))

                    def coeff(cr, m=m, s=s, t_step=t_step):
                        ax = coeff_axis[cr.coeff]
                        cvec = coeff_windows[cr.coeff]
                        if ax == 0:
                            # per-plane scalar, read at the (clamped) global
                            # plane stage s is completing
                            idx = jnp.clip(
                                t_step - (s + 1) * lead + cr.offset,
                                0, cvec.shape[0] - 1)
                            v = jax.lax.dynamic_slice(cvec, (idx,), (1,))
                            return v.reshape((1,) * (ndim - 1))
                        start = int(halo_lo[ax] - m[ax, 0] + cr.offset)
                        size = grid_shape[ax] + int(m[ax, 0]) + int(m[ax, 1])
                        v = cvec[start:start + size]
                        shape = [1] * (ndim - 1)
                        shape[ax - 1] = size
                        return v.reshape(shape)

                    def access(a: Access, m=m, s=s, k_plane=k_plane,
                               margins_s=margins_s, ring_vals=ring_vals,
                               results=results):
                        o0 = int(a.offset[0])
                        if a.field in produced:
                            pm = margins_s[a.field]
                            if a.field in ring_depth:
                                # past (or current) plane out of the ring
                                plane = ring_vals[a.field][
                                    ring_depth[a.field] - 1 + o0]
                            else:
                                plane = results[a.field]  # this step's value
                            return plane[plane_slices(pm[:, 0], m, a.offset)]
                        # persistent field: stage 0 reads the shift register
                        # (raw HBM planes; virtual step k's window starts at
                        # cats[k+1]), later stages the previous stage's
                        # updated-field ring — same index, one window behind
                        # the stream front
                        idx = depths[a.field] - 1 - lead + o0
                        if s == 0:
                            plane = cats[a.field][k_plane + 1 + idx]
                            src_lo = halo_lo
                        else:
                            plane = field_vals[s][a.field][idx]
                            src_lo = tuple((T - s) * hl[ax]
                                           for ax in range(ndim))
                        return plane[plane_slices(src_lo, m, a.offset)]

                    mkey = tuple(int(v) for v in m.flatten())
                    op_memo = memo.setdefault(mkey, {})
                    res = evaluate(op.expr, access, scalar, op_memo,
                                   coeff=coeff)
                    res = jnp.broadcast_to(jnp.asarray(res, dtype=dtype),
                                           ext)
                    if m[1:].any():
                        mask = None
                        for ax in range(1, ndim):
                            if not m[ax].any() \
                                    or kinds[op.out][ax] == "periodic":
                                continue
                            g0 = org_ref[0, ax] - int(m[ax, 0])
                            coord = g0 + jax.lax.broadcasted_iota(
                                jnp.int32, ext, ax - 1)
                            ok = (coord >= 0) & (coord < global_extent[ax])
                            mask = ok if mask is None else (mask & ok)
                        if mask is not None:
                            res = jnp.where(mask, res,
                                            jnp.asarray(0, dtype=dtype))
                    results[op.out] = res
                    if op.out in ring_vals:
                        # ring planes must honour zero-halo semantics along
                        # the stream axis: out-of-domain planes store as
                        # zeros (periodic temps with back-references were
                        # legalised into splits).  Rings shift per *virtual*
                        # step in registers; the refs commit once per grid
                        # step below.
                        cg = org_ref[0, 0] + c_plane
                        ok = (cg >= 0) & (cg < global_extent[0])
                        stored = jnp.where(ok, res, jnp.zeros_like(res))
                        ring_vals[op.out] = jnp.concatenate(
                            [ring_vals[op.out][1:], stored[None]], axis=0)
                    if update is None and op.out in out_refs:
                        center = tuple(
                            slice(int(m[ax, 0]),
                                  int(m[ax, 0]) + grid_shape[ax])
                            for ax in range(1, ndim))
                        completed[op.out].append(res[center])

                if update is None:
                    break               # classic sweep: T == 1, no chaining
                # advance time: apply the fused-loop update rule plane-wise
                # at this stage's working extent.  Mid-chain the updated
                # planes feed stage s+1's rings (the next stage reads time
                # level s+1 without touching HBM); at stage T-1 they are
                # the stored result — the fields after T steps.
                ext_s = tuple(grid_shape[a] + acc * (hl[a] + hh[a])
                              for a in range(1, ndim))
                cur = {}
                for f in gh.group_inputs:
                    idx = depths[f] - 1 - lead
                    plane = (cats[f][k_plane + 1 + idx] if s == 0
                             else field_vals[s][f][idx])
                    # "in by one halo step": the source planes carry exactly
                    # one more accumulated halo than this stage's extent
                    cur[f] = plane[tuple(
                        slice(hl[ax], hl[ax] + ext_s[ax - 1])
                        for ax in range(1, ndim))]
                outs = {}
                for f in out_names:
                    m = margins[f]      # base margin; stage adds acc steps
                    outs[f] = results[f][tuple(
                        slice(int(m[ax, 0]), int(m[ax, 0]) + ext_s[ax - 1])
                        for ax in range(1, ndim))]
                merged = dict(cur)
                merged.update(update(cur, outs, sdict))
                if s == T - 1:
                    for f in gh.group_inputs:
                        completed[f].append(jnp.broadcast_to(
                            jnp.asarray(merged[f], dtype=dtype), ext_s))
                    break
                # re-impose zero-boundary semantics on the updated planes:
                # the rings stand in for the outer loop's re-padded carry,
                # so out-of-domain cells (non-stream margins and warm-up/
                # out-of-sweep planes) must store as zeros
                cg = org_ref[0, 0] + c_plane
                ok = (cg >= 0) & (cg < global_extent[0])
                mask = jnp.broadcast_to(ok, ext_s)
                for ax in range(1, ndim):
                    if acc * (hl[ax] + hh[ax]) == 0 and grid_shape[ax] == \
                            global_extent[ax]:
                        continue
                    g0 = org_ref[0, ax] - acc * hl[ax]
                    coord = g0 + jax.lax.broadcasted_iota(jnp.int32, ext_s,
                                                          ax - 1)
                    mask = mask & (coord >= 0) & (coord < global_extent[ax])
                for f in gh.group_inputs:
                    v = jnp.broadcast_to(jnp.asarray(merged[f], dtype=dtype),
                                         ext_s)
                    stored = jnp.where(mask, v,
                                       jnp.asarray(0, dtype=dtype))
                    field_vals[s + 1][f] = jnp.concatenate(
                        [field_vals[s + 1][f][1:], stored[None]], axis=0)

        # commit the carries once per grid step: window buffers shift by P
        # planes, per-stage field/temp rings take their end-of-step values
        for f in gh.group_inputs:
            buf_refs[f][...] = cats[f][P:]
        for s in range(1, T):
            for f in gh.group_inputs:
                field_refs[s][f][...] = field_vals[s][f]
        for s in range(T):
            for t in ring_names:
                stage_ring_refs[s][t][...] = ring_vals_all[s][t]
        # emit the P-plane output block, realigned through the staging ring
        # (block b is finally correct at grid step j = b + K; the clamped
        # warm-up writes of block 0 are overwritten)
        for f in store_names:
            planes = completed[f]
            if stage_r > 0:
                staged = stage_out_refs[f][...]
                block = jnp.concatenate(
                    [staged] + [q[None] for q in planes[:P - stage_r]],
                    axis=0)
                stage_out_refs[f][...] = jnp.concatenate(
                    [q[None] for q in planes[P - stage_r:]], axis=0)
            else:
                block = jnp.concatenate([q[None] for q in planes], axis=0)
            out_refs[f][...] = block

    zeros_tail = (0,) * (ndim - 1)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),   # scalars
                pl.BlockSpec(memory_space=pltpu.SMEM)]   # origin
    for _ in gh.group_inputs:
        # block index s covers element planes [s*P, (s+1)*P)
        in_specs.append(pl.BlockSpec((P,) + plane_ext,
                                     lambda s: (s,) + zeros_tail))
    for c in gh.group_coeffs:
        ax = coeff_axis[c]
        length = n_steps if ax == 0 else plane_ext[ax - 1]
        in_specs.append(pl.BlockSpec((1, length), lambda s: (0, 0)))

    out_block = (P,) + grid_shape[1:]
    out_specs = tuple(
        pl.BlockSpec(out_block,
                     lambda s: (jnp.minimum(jnp.maximum(s - K, 0),
                                            n_out - 1),) + zeros_tail)
        for _ in store_names)
    # oversized by the P-round-up; run() slices the true extent back out
    out_shape = tuple(
        jax.ShapeDtypeStruct((n_out * P,) + grid_shape[1:], dtype)
        for _ in store_names)

    scratch = [pltpu.VMEM((depths[f],) + plane_ext, dtype)
               for f in gh.group_inputs]
    for s in range(1, T):
        for f in gh.group_inputs:
            scratch.append(pltpu.VMEM((depths[f],) + ring_plane_ext[s],
                                      dtype))
    for s in range(T):
        for t in ring_names:
            pm = stage_margins[s][t]
            ext_t = tuple(grid_shape[a] + int(pm[a, 0]) + int(pm[a, 1])
                          for a in range(1, ndim))
            scratch.append(pltpu.VMEM((ring_depth[t],) + ext_t, dtype))
    if stage_r > 0:
        for _ in store_names:
            scratch.append(pltpu.VMEM((stage_r,) + grid_shape[1:], dtype))

    call = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=out_specs if len(store_names) > 1 else out_specs[0],
        out_shape=out_shape if len(store_names) > 1 else out_shape[0],
        scratch_shapes=scratch,
        # the window buffers and rings carry state from one sweep step to
        # the next, so the sweep axis must run in order on one core
        compiler_params=hw.pallas_compiler_params(("arbitrary",)),
        interpret=hw.pallas_interpret(),
        # the region's stored fields name the kernel in the HLO and the
        # trace; a chain also names its depth, so a remainder chain differs
        name=(f"chain{T}_" if update is not None else "str_")
        + "_".join(store_names),
    )

    expect = tuple(halo_lo[a] + grid_shape[a] + halo_hi[a]
                   for a in range(ndim))

    def run(padded_inputs: dict, scalars_vec=None,
            padded_coeffs: dict | None = None, origin=None,
            input_pad: dict | None = None):
        """Same contract as the block kernels: ``input_pad[f]`` gives the
        (ndim, 2) padding the provided array actually carries when it
        exceeds this region's window geometry (fused-loop carries); the
        expected window is sliced out statically."""
        with obs.phase("window"):
            svec = (scalars_vec if scalars_vec is not None
                    else jnp.zeros((max(n_scalars, 1),), jnp.float32))
            org = (origin if origin is not None
                   else jnp.zeros((ndim,), jnp.int32))
            # every small operand is a (1, n) row: vmap then prepends the
            # batch axis ahead of the last two, where Mosaic's block rule
            # allows it
            args = [svec.reshape(1, -1), org.reshape(1, -1)]
            for f in gh.group_inputs:
                x = padded_inputs[f]
                if input_pad is not None and f in input_pad:
                    ip = input_pad[f]
                    sl = tuple(slice(int(ip[a][0]) - halo_lo[a],
                                     int(ip[a][0]) - halo_lo[a] + expect[a])
                               for a in range(ndim))
                    x = x[sl]
                if pad_round:
                    # round the stream extent up to the P-plane DMA grid;
                    # the zero planes only feed virtual steps whose
                    # completed planes land past the domain and are sliced
                    # off below, so the public pad_lo/pad_hi geometry is
                    # untouched
                    x = jnp.pad(x, [(0, pad_round)] + [(0, 0)] * (ndim - 1))
                args.append(x)
            for c in gh.group_coeffs:
                args.append(padded_coeffs[c].reshape(1, -1))
        res = call(*args)
        if len(store_names) == 1:
            res = (res,)
        with obs.phase("window"):
            if n_out * P != n0:
                res = tuple(x[:n0] for x in res)
            return dict(zip(store_names, res))

    # geometry for the shared orchestrators (identical to build_group_call)
    run.group_inputs = gh.group_inputs
    run.group_outputs = store_names
    run.returns_fields = update is not None
    run.group_coeffs = gh.group_coeffs
    run.coeff_axis = coeff_axis
    run.block = (1,) + grid_shape[1:]
    run.halo_lo = halo_lo
    run.halo_hi = halo_hi
    run.align_hi = (0,) * ndim
    run.pad_lo = halo_lo
    run.pad_hi = halo_hi
    run.window = (span + 1,) + plane_ext
    run.tiles = (n_tiles,)
    run.stream_axis = 0
    run.depths = depths
    run.rings = dict(ring_depth)
    run.chain = T           # chained stages: T-1 in-kernel updates per sweep
    run.plane_tile = P      # virtual steps (planes advanced) per grid step
    run.vmem_window_bytes = sum(
        (depths[f] + P) * int(np.prod(plane_ext)) for f in gh.group_inputs
    ) * np.dtype(np.float32 if dtype == jnp.float32 else np.float16).itemsize
    return run


def _build_calls(p: Program, plan: DataflowPlan, grid_shape,
                 graph: StreamGraph | None):
    dtype = _DTYPES[plan.dtype]
    if graph is None:
        graph = lower_to_dataflow(p, plan, grid_shape)
    calls = [build_stream_call(p, region, grid_shape, dtype=dtype,
                               plane_tile=getattr(graph, "plane_tile", 1))
             for region in graph.regions]
    return dtype, calls


def lower(p: Program, plan: DataflowPlan, grid_shape,
          graph: StreamGraph | None = None):
    """Return fn(fields, scalars, coeffs) -> outputs, one streamed sweep.

    Single-step execution never chains (there is no update rule to apply
    between stages), so any ``time_tile`` on the plan is ignored here;
    the graph's effective ``plane_tile`` applies — spatial unrolling needs
    no update rule."""
    if graph is None:
        graph = lower_to_dataflow(p, plan, grid_shape)
    dtype, calls = _build_calls(p, plan, grid_shape, graph)
    return lower_from_calls(p, dtype, calls)


def lower_time_loop(p: Program, plan: DataflowPlan, grid_shape,
                    spec: TimeLoopSpec, update,
                    graph: StreamGraph | None = None):
    """Fused ``lax.fori_loop`` time loop over streamed sweeps: the carry
    holds pre-padded persistent fields (no alignment slab — streams never
    tile), each step runs every region's shift-register sweep, and the
    update rule is traced into the loop once; fields it never changes stay
    in the carry untouched (:func:`~repro.core.lower_pallas.place_update`).

    With an effective ``time_tile = T > 1`` on the graph, each loop
    iteration runs ONE chained sweep that advances T full steps (all T
    updates applied in-kernel between chain stages; the call returns the
    new fields and the loop body just writes them back into the carry), so
    the loop runs ``spec.steps // T`` iterations; a ``spec.steps % T``
    remainder runs once after the loop through a second, shallower chain
    built from the same region."""
    dtype = _DTYPES[plan.dtype]
    if graph is None:
        graph = lower_to_dataflow(p, plan, grid_shape)
    T = int(getattr(graph, "time_tile", 1))
    P = int(getattr(graph, "plane_tile", 1))
    if T <= 1:
        _, calls = _build_calls(p, plan, grid_shape, graph)
        return time_loop_from_calls(p, dtype, grid_shape, spec, update,
                                    calls)
    region = graph.regions[0]       # chain legality implies a single region
    upd = adapt_update(update)
    calls = [build_stream_call(p, region, grid_shape, dtype=dtype,
                               time_tile=T,
                               update=upd, plane_tile=P)]
    rem = int(spec.steps) % T
    epilogue = None
    if rem:
        epilogue = [build_stream_call(
            p, region, grid_shape, dtype=dtype, time_tile=rem, update=upd,
            plane_tile=P)]
    return time_loop_from_calls(p, dtype, grid_shape, spec, update, calls,
                                chain=T, epilogue=epilogue)
