"""Distributed stencil execution: domain decomposition + halo exchange.

The TPU-cluster analogue of the paper's step 9 ("one AXI bundle / HBM bank
per field"): every chip owns a contiguous sub-domain in its own HBM, and the
inter-bank traffic becomes ``lax.ppermute`` halo exchange over ICI.

This module is the *sharded lowering* consumed by
:func:`repro.core.pipeline.compile_program` — the same planner output
(:class:`DataflowPlan` + :class:`ShardSpec` + :class:`TimeLoopSpec`) that
drives the local backends drives the SPMD ones:

* :func:`lower_sharded` — one program step under ``shard_map``.  Per fuse
  group, every group input is halo-exchanged axis-by-axis (the slab sent
  along axis k carries the halos already attached for axes < k, so corners
  are correct for diagonal offsets), then the group runs on the local
  padded block with the shard origin so the global-domain mask is exact.

* :func:`lower_sharded_time_loop` — the whole time loop in one dispatch:
  a ``lax.fori_loop`` *inside* ``shard_map`` whose carry holds one
  pre-padded local buffer per persistent field.  Each step refreshes the
  halo slabs by ``ppermute`` straight from the carry (no host round trip),
  runs the fuse groups against the refreshed buffers (the kernels slice
  their windows via ``input_pad``), and writes the new interiors back.
  One exchange per field per step serves every consuming group, because
  the carry is padded to the worst group's halo (``TimeLoopSpec.field_pad``;
  ``ShardSpec.field_halo`` records the same per-field halos for the
  plan-time single-hop validation).

Boundaries follow each field's IR declaration (:mod:`repro.core.boundary`),
axis by axis: along a ``"zero"`` axis partial ``ppermute`` rings leave the
unreceiving edge shards zero-filled — the zero-halo convention with no
special code — while along a ``"periodic"`` axis the ring closes (or the
block wraps locally where the axis is unsharded), so the same program
runs a torus, or a domain cyclic along some axes only, across any mesh.
XLA schedules the per-axis permutes of different fields independently,
so halo traffic overlaps with the compute of earlier groups (dataflow
concurrency at cluster scale).

All three backends lower here: ``pallas`` runs the generated group kernels
on local blocks; the jnp backends route temp accesses through
:func:`lower_jnp.lower`'s ``shift_fn`` hook (ppermute shifts) and slice
replicated coefficient arrays at the shard origin via ``coeff_fn``.

:func:`make_sharded_executor` — the original standalone entry point — is
deprecated; it now simply forwards to ``compile_program(..., mesh=...)``.
"""

from __future__ import annotations

import warnings
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .. import obs
from ..kernels.stencil3d import build_group_call
from . import boundary as bc
from .dataflow import STREAM_AXIS, lower_to_dataflow
from .ir import Program
from .lower_jnp import lower as lower_jnp_step
from .lower_pallas import _pad_coeffs, _run_groups
from .lower_stream import build_stream_call
from .schedule import DataflowPlan, ShardSpec, TimeLoopSpec, adapt_update

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64}


def _exchange_axis(x: jnp.ndarray, ax: int, lo: int, hi: int, align: int,
                   axis_name, n: int, periodic: bool) -> jnp.ndarray:
    """Pad ``x`` along one axis with neighbour halos, wrap, or zeros.

    Sharded axes (``axis_name`` with ``n > 1``) fetch the slabs by
    ``ppermute`` (ring closed iff periodic); unsharded axes wrap locally
    (periodic) or zero-fill.  ``align`` appends a zero alignment slab.
    """
    lo, hi, align = int(lo), int(hi), int(align)
    if lo == 0 and hi == 0 and align == 0:
        return x
    with obs.phase("halo"):
        sharded = axis_name is not None and n > 1
        size = x.shape[ax]
        pieces = []
        if lo > 0:
            if sharded:
                src = jax.lax.slice_in_dim(x, size - lo, size, axis=ax)
                pieces.append(jax.lax.ppermute(
                    src, axis_name, bc.ring_perms(n, +1, periodic)))
            elif periodic:
                pieces.append(jax.lax.slice_in_dim(x, size - lo, size, axis=ax))
            else:
                shp = list(x.shape); shp[ax] = lo
                pieces.append(jnp.zeros(shp, x.dtype))
        pieces.append(x)
        if hi > 0:
            if sharded:
                src = jax.lax.slice_in_dim(x, 0, hi, axis=ax)
                pieces.append(jax.lax.ppermute(
                    src, axis_name, bc.ring_perms(n, -1, periodic)))
            elif periodic:
                pieces.append(jax.lax.slice_in_dim(x, 0, hi, axis=ax))
            else:
                shp = list(x.shape); shp[ax] = hi
                pieces.append(jnp.zeros(shp, x.dtype))
        if align > 0:
            shp = list(x.shape); shp[ax] = align
            pieces.append(jnp.zeros(shp, x.dtype))
        return jnp.concatenate(pieces, axis=ax) if len(pieces) > 1 else pieces[0]


def halo_exchange_pad(x: jnp.ndarray, lo: Sequence[int], hi: Sequence[int],
                      align_hi: Sequence[int], mesh_axes: Sequence,
                      axis_sizes: Mapping | None = None,
                      boundary="zero") -> jnp.ndarray:
    """Pad a local block with neighbour halos (sharded axes), wraparound
    (periodic unsharded axes), or zeros, axis by axis per ``boundary`` (a
    kind, or a per-axis sequence of kinds).

    ``axis_sizes`` maps mesh-axis name -> size (static, from the mesh); the
    trace environment has no portable size query across jax versions."""
    axis_sizes = axis_sizes or {}
    kinds = bc.per_axis(boundary, x.ndim)
    for ax in range(x.ndim):
        a = mesh_axes[ax] if ax < len(mesh_axes) else None
        n = 1 if a is None else int(axis_sizes[a])
        al = int(align_hi[ax]) if ax < len(align_hi) else 0
        x = _exchange_axis(x, ax, int(lo[ax]), int(hi[ax]), al, a, n,
                           kinds[ax] == "periodic")
    return x


# --------------------------------------------------------------------------
# SPMD plumbing shared by the single-step and fused-loop lowerings
# --------------------------------------------------------------------------

def _smap(fn, mesh: Mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _origin_inputs(shard: ShardSpec):
    """(host arrays, in_specs) feeding each shard its global grid offset.

    One 1-D int32 array per grid axis, sharded along that axis's mesh
    dimension, so every shard reads its own offset as element 0 of its
    slice.  This deliberately avoids ``lax.axis_index``: its partition-id
    lowering is rejected by XLA:CPU's SPMD partitioner when it feeds a
    ``fori_loop`` body, and a data-fed origin also constant-folds a
    degenerate 1x..x1 mesh to the exact single-device graph."""
    arrs, specs = [], []
    for ax, name in enumerate(shard.mesh_axes):
        n = shard.axis_size(ax)
        arrs.append(jnp.arange(n, dtype=jnp.int32) * shard.local_grid[ax])
        specs.append(P(name))
    return tuple(arrs), tuple(specs)


def _origin(shard: ShardSpec, origs) -> jnp.ndarray:
    """The shard's global offset vector, from its _origin_inputs slices.

    Unsharded (size-1) axes contribute a *static* zero so a degenerate
    1x..x1 mesh constant-folds to the exact single-device graph."""
    return jnp.stack([origs[ax][0] if shard.axis_size(ax) > 1
                      else jnp.int32(0)
                      for ax in range(len(shard.mesh_axes))])


def _degenerate(shard: ShardSpec) -> bool:
    """True when no grid axis is actually sharded (a 1x..x1 mesh): the
    distributed access hooks then degrade to the plain local paths, so the
    compiled graph — and its floating-point rounding — is bit-identical to
    the single-device lowering."""
    return all(shard.axis_size(ax) == 1 for ax in range(len(shard.mesh_axes)))


def _coeff_reach(p: Program, shard: ShardSpec) -> dict:
    """coeff name -> (lo, hi) extension covering every CoeffRef offset."""
    reach = {c: [0, 0] for c in p.coeffs}
    if _degenerate(shard):
        return reach       # no origin slicing: coeffs pass through raw
    for op in p.ops:
        for c in op.coeff_refs():
            reach[c.coeff][0] = max(reach[c.coeff][0], -int(c.offset))
            reach[c.coeff][1] = max(reach[c.coeff][1], int(c.offset))
    return reach


def _jnp_step_hooks(p: Program, shard: ShardSpec, origin, reach: dict):
    """(shift_fn, coeff_fn) routing jnp-backend accesses across the mesh.

    Both are None on a degenerate mesh — :func:`lower_jnp.lower` then uses
    its local boundary-aware defaults, keeping the graph bit-identical to
    the single-device compile."""
    if _degenerate(shard):
        return None, None
    ndim = p.ndim

    def shift(x, offset, boundary):
        kinds = bc.per_axis(boundary, ndim)
        for ax in range(ndim):
            o = int(offset[ax])
            if o == 0:
                continue
            n_loc = shard.local_grid[ax]
            if abs(o) > n_loc:
                raise ValueError(
                    f"offset {o} on axis {ax} exceeds the local extent "
                    f"{n_loc} (halo exchange is single-hop)")
            lo, hi = max(0, -o), max(0, o)
            xp = _exchange_axis(x, ax, lo, hi, 0, shard.mesh_axes[ax],
                                shard.axis_size(ax),
                                kinds[ax] == "periodic")
            x = jax.lax.slice_in_dim(xp, lo + o, lo + o + n_loc, axis=ax)
        return x

    def coeff(cref, coeffs):
        # coeffs arrive replicated and pre-extended by ``reach`` on the
        # host; the shard slices its local window at the global origin
        ax = p.coeffs[cref.coeff]
        start = origin[ax] + reach[cref.coeff][0] + int(cref.offset)
        v = jax.lax.dynamic_slice(coeffs[cref.coeff], (start,),
                                  (shard.local_grid[ax],))
        shape = [1] * ndim
        shape[ax] = shard.local_grid[ax]
        return v.reshape(shape)

    return shift, coeff


def _in_specs(p: Program, shard: ShardSpec, origin_specs, scal_spec) -> tuple:
    """shard_map input specs: (scalars, fields, coeffs, origin arrays)."""
    field_spec = P(*shard.mesh_axes)
    return (scal_spec,
            {f: field_spec for f in p.input_fields()},
            {c: P() for c in p.coeffs},
            origin_specs)


def _scalar_io(p: Program, backend: str):
    """(replicated spec, packer) for the runtime scalars.

    The pallas kernels take one packed SMEM vector; the jnp lowerings take
    the plain name->value dict — keeping each backend's scalar plumbing
    identical to its local lowering, so a degenerate mesh bit-matches."""
    if backend == "pallas":
        def pack(scalars):
            return (jnp.asarray([scalars[s] for s in p.scalars],
                                dtype=jnp.float32)
                    if p.scalars else jnp.zeros((1,), jnp.float32))
        return P(), pack

    def pack(scalars):
        return {s: scalars[s] for s in p.scalars}
    return {s: P() for s in p.scalars}, pack


def _host_coeffs(p: Program, coeffs: Mapping, jdtype, reach: dict) -> dict:
    """Replicated coefficient arrays, pre-extended by ``reach`` so any shard
    can slice its piece ('small data' lives on every chip, paper step 8)."""
    return {c: bc.pad_coeff(jnp.asarray(coeffs[c], dtype=jdtype),
                            reach[c][0], reach[c][1],
                            bc.coeff_mode(p, p.coeffs[c]))
            for c in p.coeffs}


def _pallas_coeff_windows(p: Program, calls, coeffs, origin,
                          shard: ShardSpec, reach: dict) -> list:
    """Per-call local coefficient windows, sliced at the shard origin."""
    out = []
    for call in calls:
        pc = {}
        for c in call.group_coeffs:
            ax = call.coeff_axis[c]
            start = origin[ax] + reach[c][0] - call.pad_lo[ax]
            pc[c] = jax.lax.dynamic_slice(
                coeffs[c], (start,),
                (shard.local_grid[ax] + call.pad_lo[ax] + call.pad_hi[ax],))
        out.append(pc)
    return out


def _pallas_reach(calls, p: Program) -> dict:
    reach = {c: [0, 0] for c in p.coeffs}
    for call in calls:
        for c in call.group_coeffs:
            ax = call.coeff_axis[c]
            reach[c][0] = max(reach[c][0], call.pad_lo[ax])
            reach[c][1] = max(reach[c][1], call.pad_hi[ax])
    return reach


def _stream_graph(p: Program, plan: DataflowPlan, shard: ShardSpec, graph):
    """The plan's dataflow graph, lowered for this shard's topology.

    A sharded stream axis needs *exact* neighbour ghost planes (the region
    halos carry the ring-chain-propagated lo reach), so a graph built
    without the flag must not drive a sharded sweep — rebuild unless the
    caller handed one down from the pipeline."""
    if plan.schedule != "stream":
        return None
    ss = shard.axis_size(STREAM_AXIS) > 1
    if graph is None or bool(graph.stream_sharded) != ss:
        graph = lower_to_dataflow(p, plan, shard.local_grid,
                                  stream_sharded=ss)
    return graph


def _pallas_calls(p: Program, plan: DataflowPlan, local_grid, global_grid,
                  jdtype, graph, time_tile: int = 1, update=None):
    """The plan's kernel calls on the shard-local block.

    Block and stream kernels expose the same geometry contract
    (``group_inputs``/``halo_lo``/``input_pad`` slicing/``origin=``), so
    the SPMD orchestrators below drive either schedule identically; a
    stream sweep additionally chains ``time_tile`` timestep stages when
    the fused-loop ``update`` rule rides in-kernel, and advances the
    graph's effective ``plane_tile`` planes per grid step (demoted against
    the *shard-local* stream extent by ``lower_to_dataflow``)."""
    if plan.schedule == "stream":
        return [build_stream_call(p, region, local_grid, dtype=jdtype,
                                  global_extent=global_grid,
                                  time_tile=time_tile, update=update,
                                  plane_tile=getattr(graph, "plane_tile", 1),
                                  stream_sharded=graph.stream_sharded)
                for region in graph.regions]
    return [build_group_call(p, grp, plan.block, local_grid, dtype=jdtype,
                             global_extent=global_grid)
            for grp in plan.groups]


# --------------------------------------------------------------------------
# single program step under shard_map
# --------------------------------------------------------------------------

def lower_sharded(p: Program, plan: DataflowPlan, global_grid,
                  shard: ShardSpec, mesh: Mesh, graph=None):
    """Return fn(fields, scalars, coeffs) running one program step SPMD.

    Schedule-agnostic: ``plan.schedule`` picks block-tiled group kernels or
    plane-sweeping stream kernels per shard (``graph`` optionally hands
    down the pipeline's already-lowered dataflow graph)."""
    global_grid = tuple(int(g) for g in global_grid)
    jdtype = _DTYPES[plan.dtype]
    bnd = p.boundaries()
    backend = plan.backend
    mesh_axes, axis_sizes = shard.mesh_axes, shard.axis_sizes
    out_names = p.output_fields()
    origin_arrs, origin_specs = _origin_inputs(shard)
    scal_spec, pack_scalars = _scalar_io(p, backend)
    in_specs = _in_specs(p, shard, origin_specs, scal_spec)
    out_specs = tuple(P(*mesh_axes) for _ in out_names)
    reach = _coeff_reach(p, shard)

    degen = _degenerate(shard)
    if backend == "pallas":
        graph = _stream_graph(p, plan, shard, graph)
        calls = _pallas_calls(p, plan, shard.local_grid, global_grid,
                              jdtype, graph)
        if not degen:
            reach = _pallas_reach(calls, p)

        def local_fn(svec, fields, coeffs, origs):
            with obs.phase("entry"):
                origin = _origin(shard, origs)
                # degenerate mesh: the local pad path, so the graph (and
                # its rounding) bit-matches the single-device lowering
                pc_per_call = (_pad_coeffs(p, calls, coeffs, jdtype) if degen
                               else _pallas_coeff_windows(
                                   p, calls, coeffs, origin, shard, reach))

            def resolve(call, f, env):
                x = env[f] if f in env else fields[f]
                with obs.phase("group_pad"):
                    if degen:
                        return bc.pad_field(x, call.halo_lo, call.halo_hi,
                                            bnd[f],
                                            align_hi=call.align_hi), None
                    return halo_exchange_pad(
                        x, call.halo_lo, call.halo_hi, call.align_hi,
                        mesh_axes, axis_sizes,
                        boundary=bnd[f]), None

            outputs = _run_groups(p, calls, svec, pc_per_call, resolve,
                                  origin=origin)
            return tuple(outputs[f] for f in out_names)
    elif backend in ("jnp_fused", "jnp_naive"):
        mode = backend.removeprefix("jnp_")

        def local_fn(scal, fields, coeffs, origs):
            origin = _origin(shard, origs)
            shift, coeff = _jnp_step_hooks(p, shard, origin, reach)
            step = lower_jnp_step(p, mode, shift_fn=shift, coeff_fn=coeff)
            outputs = step(fields, scal, coeffs)
            return tuple(outputs[f] for f in out_names)
    else:
        raise ValueError(f"unknown backend {backend!r}")

    smapped = _smap(local_fn, mesh, in_specs, out_specs)

    def run(fields: Mapping, scalars: Mapping | None = None,
            coeffs: Mapping | None = None):
        scalars = scalars or {}
        coeffs = coeffs or {}
        with obs.phase("entry"):
            fdict = {f: jnp.asarray(fields[f], dtype=jdtype)
                     for f in p.input_fields()}
            cdict = _host_coeffs(p, coeffs, jdtype, reach)
            svec = pack_scalars(scalars)
        res = smapped(svec, fdict, cdict, origin_arrs)
        return dict(zip(out_names, res))

    return run


# --------------------------------------------------------------------------
# fused time loop under shard_map (carry-resident halo exchange)
# --------------------------------------------------------------------------

def lower_sharded_time_loop(p: Program, plan: DataflowPlan, global_grid,
                            spec: TimeLoopSpec, update, mesh: Mesh,
                            graph=None):
    """Return fn(fields, scalars, coeffs) -> final fields after
    ``spec.steps`` distributed iterations — ONE jitted dispatch.

    Structure (all inside ``shard_map``, so it traces once per compile):

        carry = per-field local buffers padded to the worst-group halo
        fori_loop body:
            refresh halo slabs from the carry interiors (ppermute rings /
                local wrap / zeros, axis by axis so corners are exact)
            run the plan's kernels against the refreshed buffers
            trace ``update`` once; write the new interiors back

    The final interiors are sliced out after the loop; no per-step host
    sync, no per-step re-dispatch, no re-tracing of ``update``.

    Schedule-agnostic: ``plan.schedule = "stream"`` swaps the block-tiled
    group kernels for per-shard plane-sweeping stream kernels behind the
    same refresh-then-compute contract — still one exchange per field per
    step.  With an effective ``time_tile = T > 1`` on the dataflow graph,
    each loop iteration runs ONE chained sweep advancing T steps (all T
    updates applied in-kernel; the carry padding covers the chain's
    accumulated halos, so still one exchange per field per *chain*), the
    loop runs ``spec.steps // T`` iterations, and a ``steps % T``
    remainder runs once after it through a shallower chain.  ``graph``
    optionally hands down the pipeline's already-lowered dataflow graph.
    """
    shard = spec.shard
    if shard is None:
        raise ValueError("spec has no ShardSpec; use the local lowerings")
    update = adapt_update(update)
    global_grid = tuple(int(g) for g in global_grid)
    ndim = p.ndim
    jdtype = _DTYPES[plan.dtype]
    bnd = p.boundaries()
    backend = plan.backend
    mesh_axes, axis_sizes = shard.mesh_axes, shard.axis_sizes
    local_grid = shard.local_grid
    fpad = spec.field_pad
    align = spec.align_hi or (0,) * ndim
    interior = {f: tuple(slice(int(fpad[f][a, 0]),
                               int(fpad[f][a, 0]) + local_grid[a])
                         for a in range(ndim))
                for f in spec.persistent}
    carry_pads = {f: tuple((int(fpad[f][a, 0]), int(fpad[f][a, 1]))
                           for a in range(ndim))
                  for f in spec.persistent}

    kinds = p.axis_boundaries()

    def _needs_refresh(f) -> bool:
        # a field's carry halos go stale each step only if they hold
        # wraparound values (periodic axis) or neighbour data (sharded axis);
        # zero halos on unsharded axes are invariant — skipping their
        # rebuild also lets a degenerate 1x..x1 mesh fold to the exact
        # single-device graph
        for a in range(ndim):
            lo = int(fpad[f][a, 0])
            hi = int(fpad[f][a, 1]) - int(align[a])
            if lo == 0 and hi == 0:
                continue
            if kinds[f][a] == "periodic" or shard.axis_size(a) > 1:
                return True
        return False

    refreshed = {f for f in spec.persistent if _needs_refresh(f)}

    def refresh(f, carry_f):
        # carry-resident halo refresh: lo/hi halos per the field's
        # boundary, zero lane-alignment slab on the hi side
        if f not in refreshed:
            return carry_f
        with obs.phase("halo"):
            return halo_exchange_pad(
                carry_f[interior[f]], fpad[f][:, 0],
                [int(fpad[f][a, 1]) - int(align[a]) for a in range(ndim)],
                align, mesh_axes, axis_sizes, boundary=bnd[f])

    origin_arrs, origin_specs = _origin_inputs(shard)
    scal_spec, pack_scalars = _scalar_io(p, backend)
    in_specs = _in_specs(p, shard, origin_specs, scal_spec)
    out_specs = tuple(P(*mesh_axes) for _ in spec.persistent)

    degen = _degenerate(shard)
    chain = 1
    epilogue_calls = None
    if backend == "pallas":
        graph = _stream_graph(p, plan, shard, graph)
        T = int(getattr(graph, "time_tile", 1)) if graph is not None else 1
        if T > 1:
            # temporally-blocked chain: legality implies a single region
            # (see dataflow.chain_split_reason); one chained sweep per loop
            # iteration advances T steps, updates applied in-kernel
            chain = T
            calls = _pallas_calls(p, plan, local_grid, global_grid, jdtype,
                                  graph, time_tile=T, update=update)
            rem = int(spec.steps) % T
            if rem:
                epilogue_calls = _pallas_calls(p, plan, local_grid,
                                               global_grid, jdtype, graph,
                                               time_tile=rem, update=update)
        else:
            calls = _pallas_calls(p, plan, local_grid, global_grid, jdtype,
                                  graph)
        reach = (_coeff_reach(p, shard) if degen
                 else _pallas_reach(calls + (epilogue_calls or []), p))

        def make_step(origin, coeffs, calls_):
            # degenerate mesh: the local pad path, so the graph (and its
            # rounding) bit-matches the single-device fused loop
            pc_per_call = (_pad_coeffs(p, calls_, coeffs, jdtype) if degen
                           else _pallas_coeff_windows(p, calls_, coeffs,
                                                      origin, shard, reach))

            if getattr(calls_[0], "returns_fields", False):
                # chained stream sweep: ONE call advances every persistent
                # field by its full chain depth and returns the new fields
                call = calls_[0]

                def step(fresh, svec):
                    padded = {f: fresh[f] for f in call.group_inputs}
                    return call(padded, svec, pc_per_call[0], origin=origin,
                                input_pad={f: fpad[f]
                                           for f in call.group_inputs})

                step.returns_fields = True
                return step

            def step(fresh, svec):
                def resolve(call, f, env):
                    if f in fresh:      # persistent: window from the carry
                        return fresh[f], fpad[f]
                    # transient inter-group: exchange to the call's geometry
                    with obs.phase("group_pad"):
                        if degen:
                            return bc.pad_field(env[f], call.halo_lo,
                                                call.halo_hi, bnd[f],
                                                align_hi=call.align_hi), None
                        return halo_exchange_pad(
                            env[f], call.halo_lo, call.halo_hi,
                            call.align_hi, mesh_axes, axis_sizes,
                            boundary=bnd[f]), None

                return _run_groups(p, calls_, svec, pc_per_call, resolve,
                                   origin=origin)

            step.returns_fields = False
            return step
    elif backend in ("jnp_fused", "jnp_naive"):
        mode = backend.removeprefix("jnp_")
        calls = [None]
        reach = _coeff_reach(p, shard)

        def make_step(origin, coeffs, calls_):
            shift, coeff = _jnp_step_hooks(p, shard, origin, reach)
            raw = lower_jnp_step(p, mode, prepad=fpad, shift_fn=shift,
                                 coeff_fn=coeff)

            def step(fresh, scal):
                return raw(fresh, scal, coeffs)

            step.returns_fields = False
            return step
    else:
        raise ValueError(f"unknown backend {backend!r}")

    def local_fn(scal, fields, coeffs, origs):
        with obs.phase("entry"):
            origin = _origin(shard, origs)
            step = make_step(origin, coeffs, calls)
            step_epi = (make_step(origin, coeffs, epilogue_calls)
                        if epilogue_calls is not None else None)
            # initial carry: zero-padded; the loop body refreshes halos
            # before the first compute, so the fill value is never observed
            carry = {f: jnp.pad(fields[f], carry_pads[f])
                     for f in spec.persistent}

        def advance(carry, stepfn):
            fresh = {f: refresh(f, carry[f]) for f in spec.persistent}
            if stepfn.returns_fields:
                # chained sweep: the kernel already applied every update
                new = stepfn(fresh, scal)
            else:
                outputs = stepfn(fresh, scal)
                with obs.phase("update"):
                    cur = {f: fresh[f][interior[f]] for f in spec.persistent}
                    new = dict(cur)
                    # the packed pallas scalar vector unpacks back to the
                    # name->value dict the update rule sees everywhere else
                    sdict = ({s: scal[i] for i, s in enumerate(p.scalars)}
                             if backend == "pallas" else scal)
                    if getattr(update, "_takes_origin", False) and not degen:
                        # shard-aware rules (the serving bucket refresh)
                        # mask in global coordinates; the degenerate mesh
                        # keeps the local form so its graph stays
                        # bit-identical
                        new.update(update(cur, outputs, sdict,
                                          origin=origin))
                    else:
                        new.update(update(cur, outputs, sdict))
            out = {}
            with obs.phase("carry_write"):
                for f in spec.persistent:
                    if spec.carry_write == "inplace":
                        out[f] = fresh[f].at[interior[f]].set(
                            jnp.asarray(new[f], dtype=jdtype))
                    else:   # "repad": halos are rebuilt next iteration
                        out[f] = jnp.pad(jnp.asarray(new[f], dtype=jdtype),
                                         carry_pads[f])
            return out

        carry = jax.lax.fori_loop(0, int(spec.steps) // chain,
                                  lambda _, c: advance(c, step), carry)
        if step_epi is not None:
            carry = advance(carry, step_epi)
        with obs.phase("exit"):
            return tuple(carry[f][interior[f]] for f in spec.persistent)

    smapped = _smap(local_fn, mesh, in_specs, out_specs)

    def run(fields: Mapping, scalars: Mapping | None = None,
            coeffs: Mapping | None = None):
        scalars = scalars or {}
        coeffs = coeffs or {}
        with obs.phase("entry"):
            fdict = {f: jnp.asarray(fields[f], dtype=jdtype)
                     for f in p.input_fields()}
            cdict = _host_coeffs(p, coeffs, jdtype, reach)
            svec = pack_scalars(scalars)
        res = smapped(svec, fdict, cdict, origin_arrs)
        return dict(zip(spec.persistent, res))

    return run


def make_auto_mesh(shape, axes, **kwargs):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    kwargs.setdefault("axis_types", (jax.sharding.AxisType.Auto,) * len(axes))
    return jax.make_mesh(tuple(shape), tuple(axes), **kwargs)


# --------------------------------------------------------------------------
# deprecated standalone entry point
# --------------------------------------------------------------------------

def make_sharded_executor(p: Program, global_grid, mesh: Mesh,
                          mesh_axes: Sequence, *,
                          plan: DataflowPlan | None = None,
                          backend: str = "pallas", dtype: str = "float32"):
    """Deprecated: use ``compile_program(p, grid, mesh=..., mesh_axes=...)``.

    Kept as a thin forwarding wrapper so existing callers keep working;
    the returned executable is a :class:`CompiledStencil` with the legacy
    ``local_grid`` / ``mesh_axes`` / ``field_spec`` attributes attached.
    """
    warnings.warn(
        "make_sharded_executor is deprecated; call "
        "compile_program(p, grid, mesh=..., mesh_axes=...) instead",
        DeprecationWarning, stacklevel=2)
    from .pipeline import CompileOptions, compile_program
    ex = compile_program(p, global_grid, options=CompileOptions(
        backend=backend, plan=plan, dtype=dtype,
        mesh=mesh, mesh_axes=mesh_axes))
    ex.local_grid = ex.shard.local_grid
    ex.mesh_axes = ex.shard.mesh_axes
    ex.field_spec = P(*ex.shard.mesh_axes)
    return ex
