"""DataflowPlan — the HLS-dialect analogue (paper §3.1).

Where the paper's HLS dialect records FPGA decisions (streams, pipeline II,
unroll, array_partition, AXI bundles), the plan records their TPU analogues:

  hls.create_stream / dataflow  ->  fuse-group boundaries + Pallas pipeline
  hls.pipeline(II)              ->  grid/block shape (VMEM tiling)
  hls.unroll                    ->  in-tile vectorisation (VPU lanes; implicit)
  hls.array_partition           ->  window layout (halo), lane alignment
  hls.interface / bundles       ->  PartitionSpec per field (chips = banks)

A plan is pure data: both backends and the distributed executor consume it,
the auto-tuner (:mod:`repro.core.tune`) searches over it by measurement, and
:func:`plan_to_dict` / :func:`plan_from_dict` round-trip it through the
tuner's persistent JSON plan cache.
"""

from __future__ import annotations

import dataclasses
import hashlib
import inspect
from typing import Sequence

import numpy as np

from .. import hw
from .ir import FieldRole, Program
from .passes import _zeros, infer_halo, stage_split


SCHEDULES = ("block", "stream")


@dataclasses.dataclass
class StreamSpec:
    """Shift-register geometry of a ``schedule="stream"`` plan (the paper's
    HLS-dialect window buffers, §3.2 Fig. 2).

    Derived from the stencil IR by :func:`repro.core.dataflow.
    lower_to_dataflow` and carried on the plan so the tuner's JSON cache
    round-trips the full streaming decision:

    * ``regions`` — the *legalised* fuse groups: plan groups split wherever
      an in-group temp is read at a positive stream offset (would need the
      future) or a periodic temp at a negative one (wraparound is not yet
      resident).
    * ``depths`` — per region, each input field's rolling window-buffer
      depth in planes: the field's reach behind the newest plane plus the
      region's lead plus one (``lo + lead + 1``); every input plane is
      fetched from HBM exactly once and reused across the full depth.
    * ``rings`` — per region, ring-buffer depths for temps consumed at past
      planes (``1 + max back-reference``); streamed dependencies replace
      the block schedule's overlapped-tiling recompute.
    * ``leads`` — per region, how many planes ahead of the output plane the
      stream front runs (the hi-side stream halo).
    * ``time_tile`` — the *effective* temporal-blocking depth: how many time
      steps one sweep actually chains (the paper's pipelined timestep compute
      regions).  The plan's ``time_tile`` records the request; legalisation
      (:func:`repro.core.dataflow.chain_split_reason`) demotes it to 1 here
      when the chain cannot stream in one sweep (multiple regions, periodic
      wraparound, non-persistent inputs).
    * ``plane_tile`` — the *effective* spatial-unroll width: how many
      consecutive planes one sweep grid step DMAs and computes (the paper's
      parallel processing elements consuming multiple contiguous points per
      cycle).  The plan's ``plane_tile`` records the request;
      :func:`repro.core.dataflow.plane_split_reason` demotes it to 1 here
      when a P-plane step would overrun the (shard-local) stream extent.
    """

    axis: int = 0
    regions: tuple = ()
    depths: tuple = ()
    rings: tuple = ()
    leads: tuple = ()
    time_tile: int = 1
    plane_tile: int = 1

    def __post_init__(self):
        self.regions = tuple(tuple(int(i) for i in r) for r in self.regions)
        self.depths = tuple({str(f): int(d) for f, d in d.items()}
                            for d in self.depths)
        self.rings = tuple({str(f): int(d) for f, d in d.items()}
                           for d in self.rings)
        self.leads = tuple(int(v) for v in self.leads)
        self.time_tile = max(1, int(self.time_tile))
        self.plane_tile = max(1, int(self.plane_tile))


def stream_spec_to_dict(s: StreamSpec | None) -> dict | None:
    if s is None:
        return None
    return {
        "axis": int(s.axis),
        "regions": [list(r) for r in s.regions],
        "depths": [dict(d) for d in s.depths],
        "rings": [dict(d) for d in s.rings],
        "leads": list(s.leads),
        "time_tile": int(s.time_tile),
        "plane_tile": int(s.plane_tile),
    }


def stream_spec_from_dict(d: dict | None) -> StreamSpec | None:
    if d is None:
        return None
    return StreamSpec(axis=int(d.get("axis", 0)),
                      regions=d.get("regions", ()),
                      depths=d.get("depths", ()),
                      rings=d.get("rings", ()),
                      leads=d.get("leads", ()),
                      time_tile=int(d.get("time_tile", 1)),
                      plane_tile=int(d.get("plane_tile", 1)))


@dataclasses.dataclass
class DataflowPlan:
    # fuse groups: ordered list of lists of op indices
    groups: list
    # output tile shape per axis (the VMEM block)
    block: tuple
    # dtype for field storage/compute
    dtype: str = "float32"
    # backend: "pallas" | "jnp_fused" | "jnp_naive"
    backend: str = "pallas"
    # distributed layout: mesh axis name per grid axis (None entry =
    # unsharded axis).  ``None`` means fully unsharded; stored tuples are
    # normalised to the program's ndim via :meth:`mesh_axes_for` rather than
    # assuming 3-D (2-D programs get 2-tuples).
    mesh_axes: tuple | None = None
    # exchange halos every k steps with k-wide halos (comm amortisation)
    halo_every: int = 1
    # pallas iteration schedule: "block" tiles the output and fetches
    # overlapping VMEM windows per tile; "stream" iterates the grid over
    # the outer axis and keeps rolling shift-register window buffers in the
    # kernel carry (each input plane fetched once, the paper's headline)
    schedule: str = "block"
    # shift-register geometry when schedule == "stream" (None = derive at
    # compile time from the fuse groups)
    stream: StreamSpec | None = None
    # temporal blocking: pipeline T time steps through one stream sweep
    # (window-buffer depths and halo margins accumulate per chained step;
    # the fused loop advances steps // T outer iterations).  Requested
    # depth; the legalised effective depth lives on ``stream.time_tile``.
    time_tile: int = 1
    # spatial unrolling: DMA + compute P consecutive planes per stream
    # sweep grid step (the grid shrinks to ceil(n_steps / P)).  Requested
    # width; the effective width lives on ``stream.plane_tile``.
    plane_tile: int = 1

    def __post_init__(self):
        if self.mesh_axes is not None:
            self.mesh_axes = tuple(self.mesh_axes)
        self.block = tuple(self.block)
        if self.schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {self.schedule!r}; valid: "
                             + ", ".join(repr(s) for s in SCHEDULES))
        self.time_tile = int(self.time_tile)
        if self.time_tile < 1:
            raise ValueError(f"time_tile must be >= 1, got {self.time_tile}")
        if self.time_tile > 1 and self.schedule != "stream":
            raise ValueError(
                "time_tile > 1 is temporal blocking through the stream "
                "sweep; it requires schedule='stream' (the block schedule "
                f"has no chained lowering), got schedule={self.schedule!r}")
        self.plane_tile = int(self.plane_tile)
        if self.plane_tile < 1:
            raise ValueError(
                f"plane_tile must be >= 1, got {self.plane_tile}")
        if self.plane_tile > 1 and self.schedule != "stream":
            raise ValueError(
                "plane_tile > 1 is spatial unrolling of the stream sweep; "
                "it requires schedule='stream' (the block schedule has no "
                f"multi-plane sweep), got schedule={self.schedule!r}")

    def mesh_axes_for(self, ndim: int) -> tuple:
        """Mesh axis names normalised to ``ndim`` entries (None = unsharded)."""
        return normalize_mesh_axes(self.mesh_axes, ndim)

    def describe(self) -> str:
        g = ", ".join("{" + ",".join(map(str, grp)) + "}" for grp in self.groups)
        ma = self.mesh_axes_for(len(self.block))
        tt = f", time_tile={self.time_tile}" if self.time_tile > 1 else ""
        pt = f", plane_tile={self.plane_tile}" if self.plane_tile > 1 else ""
        return (f"plan(groups=[{g}], block={self.block}, backend={self.backend}, "
                f"schedule={self.schedule}{tt}{pt}, mesh_axes={ma})")


# --------------------------------------------------------------------------
# Plan serialisation + program fingerprinting (the tuner's cache layer)
# --------------------------------------------------------------------------

#: Version of the serialised plan layout.  Bumped whenever a field is added
#: or its meaning changes (v2: ``schedule`` + ``StreamSpec``; v3: temporal
#: blocking — ``time_tile`` on the plan and the effective depth on the
#: stream spec; v4: spatial unrolling — ``plane_tile`` on the plan and the
#: effective width on the stream spec).  Deserialising is tolerant —
#: unknown keys are ignored, missing new keys get their defaults — so the
#: version mainly lets cache layers treat *stale* records as misses rather
#: than guessing at their semantics.
PLAN_SCHEMA_VERSION = 4


def plan_to_dict(plan: DataflowPlan) -> dict:
    """JSON-safe encoding of a plan (round-trips via :func:`plan_from_dict`)."""
    return {
        "schema": PLAN_SCHEMA_VERSION,
        "groups": [[int(i) for i in grp] for grp in plan.groups],
        "block": [int(b) for b in plan.block],
        "dtype": plan.dtype,
        "backend": plan.backend,
        "mesh_axes": (None if plan.mesh_axes is None
                      else list(plan.mesh_axes)),
        "halo_every": int(plan.halo_every),
        "schedule": plan.schedule,
        "stream": stream_spec_to_dict(plan.stream),
        "time_tile": int(plan.time_tile),
        "plane_tile": int(plan.plane_tile),
    }


def plan_from_dict(d: dict) -> DataflowPlan:
    """Tolerant decoding: only the keys this version knows are read (future
    extras are ignored), and keys a past version never wrote fall back to
    the field defaults — a pre-``schedule`` record deserialises as a
    ``"block"`` plan instead of crashing."""
    ma = d.get("mesh_axes")
    return DataflowPlan(
        groups=[list(grp) for grp in d["groups"]],
        block=tuple(d["block"]),
        dtype=d.get("dtype", "float32"),
        backend=d.get("backend", "pallas"),
        mesh_axes=None if ma is None else tuple(ma),
        halo_every=int(d.get("halo_every", 1)),
        schedule=d.get("schedule", "block"),
        stream=stream_spec_from_dict(d.get("stream")),
        time_tile=int(d.get("time_tile", 1)),
        plane_tile=int(d.get("plane_tile", 1)),
    )


def program_fingerprint(p: Program) -> str:
    """Stable content hash of a program's *semantics* (ops, fields, scalars,
    coefficient axes, field dtypes) — the tuner's cache key component.  Two
    programs with the same fingerprint lower identically, so a tuned plan is
    transferable between them."""
    parts = [p.to_text()]
    parts += [f"field:{n}:{f.role.value}:{f.dtype}:{f.boundary}"
              for n, f in sorted(p.fields.items())]
    parts += [f"coeff:{c}:{ax}" for c, ax in sorted(p.coeffs.items())]
    parts.append(f"scalars:{','.join(p.scalars)}")
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()[:16]


# --------------------------------------------------------------------------
# Grid bucketing (the serving layer's shape quantisation)
# --------------------------------------------------------------------------

def program_reach(p: Program) -> np.ndarray:
    """Transitive stencil reach of ``p`` as an ``(ndim, 2)`` array: how far
    any output cell's value depends on input cells, through every
    producer->consumer chain.  This is the halo a serving bucket must keep
    between a request's true grid and the bucket edge so that no in-domain
    read ever observes the bucket boundary."""
    return np.array(infer_halo(p, range(len(p.ops))).input_halo)


def quantize_extent(n: int, *, lane_axis: bool = False,
                    lane: int = hw.LANE) -> int:
    """Round one grid extent up to its bucket quantum.

    Small extents round to the next power of two (few buckets, bounded
    padding waste); extents at or beyond the lane width round to lane
    multiples on the lane axis (the 512-bit-burst analogue) and to
    32-multiples elsewhere — so arbitrarily varied request grids land on a
    small, hardware-aligned set of compiled shapes.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"extent must be >= 1, got {n}")
    quantum = lane if lane_axis else 32
    if n >= quantum:
        return hw.align_up(n, quantum)
    b = 1
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Placement of one request grid inside a quantised serving bucket.

    The request's true ``grid`` sits at ``offset`` (the program's lo-side
    reach) inside ``bucket``; the slab below the offset and everything past
    ``offset + grid`` is boundary extension the serving layer fills (zeros
    or wraparound) and re-normalises every fused step, so in-domain reads
    never observe the bucket edge.
    """

    grid: tuple
    bucket: tuple
    offset: tuple

    def interior(self) -> tuple:
        """Slices selecting the true grid out of a bucket-shaped array."""
        return tuple(slice(o, o + g) for o, g in zip(self.offset, self.grid))


def bucket_for(p: Program, grid: Sequence[int], *,
               lane: int = hw.LANE) -> BucketSpec:
    """Quantised serving bucket for ``grid``: true extent plus the program's
    lo/hi reach, rounded up per :func:`quantize_extent`.  Requests whose
    grids share a bucket share one compiled executor."""
    grid = tuple(int(g) for g in grid)
    if len(grid) != p.ndim:
        raise ValueError(f"grid rank {len(grid)} != program ndim {p.ndim}")
    reach = program_reach(p)
    bucket, offset = [], []
    for a, g in enumerate(grid):
        lo, hi = int(reach[a, 0]), int(reach[a, 1])
        bucket.append(quantize_extent(g + lo + hi,
                                      lane_axis=(a == p.ndim - 1), lane=lane))
        offset.append(lo)
    return BucketSpec(grid=grid, bucket=tuple(bucket), offset=tuple(offset))


def mesh_fingerprint(mesh, mesh_axes) -> str:
    """Stable encoding of a mesh topology for cache keys.

    Two topologies of the same device count (2x4 vs 4x2, or different
    grid-axis assignments) shard different local blocks and measure
    different collectives — plans and executors compiled under one must
    never serve the other.  ``"none"`` = unsharded/local."""
    if mesh is None:
        return "none"
    axes = tuple(mesh_axes if mesh_axes is not None else mesh.axis_names)
    return ",".join(f"{a or '-'}:{1 if a is None else int(mesh.shape[a])}"
                    for a in axes)


def bucket_fingerprint(p: Program, bucket: Sequence[int], *,
                       backend: str, dtype: str = "float32",
                       schedule: str | None = None,
                       steps: int | None = None,
                       mesh=None, mesh_axes=None,
                       plane_tile: int | None = None) -> str:
    """Cache key of one serving-bucket executor: program semantics
    (boundaries included, via :func:`program_fingerprint`), bucket shape,
    backend/compile options, fused depth, requested sweep unroll width
    (``plane_tile`` — executors with different sweep geometry never share
    a slot), mesh topology (:func:`mesh_fingerprint` — a sharded executor
    must never serve a local request or a different topology), and the
    plan schema version — a record written by another plan layout must
    read as a miss, never as a silently misdecoded plan."""
    return "|".join([
        "serve",
        program_fingerprint(p),
        "bucket=" + "x".join(str(int(b)) for b in bucket),
        f"backend={backend}",
        f"dtype={dtype}",
        f"interpret={int(hw.pallas_interpret())}",
        f"schedule={schedule or 'plan'}",
        f"steps={'single' if steps is None else int(steps)}",
        f"plane_tile={'plan' if plane_tile is None else int(plane_tile)}",
        f"mesh={mesh_fingerprint(mesh, mesh_axes)}",
        f"schema={PLAN_SCHEMA_VERSION}",
    ])


# --------------------------------------------------------------------------
# Time-loop update-rule normalisation
# --------------------------------------------------------------------------

#: The accepted update-rule signatures, for error messages and docs.
UPDATE_SIGNATURES = ("update(fields, outputs)",
                     "update(fields, outputs, scalars)")


def plane_local(update) -> bool:
    """Whether ``update`` keeps the element-wise contract of
    :func:`adapt_update`, so a kernel may apply it to the part of the grid
    it holds: true unless the rule says ``_plane_local = False``."""
    return getattr(update, "_plane_local", True)


def adapt_update(update):
    """Normalise a time-loop update rule to ``fn(fields, outputs, scalars)``.

    This is the update-rule *contract* of every fused time loop
    (``compile_program(..., steps=N, update=...)``), on all backends, local
    and sharded.  Two forms are accepted:

    * ``update(fields, outputs) -> fields`` — the historical rule: maps the
      current persistent fields and this step's program outputs to the next
      step's fields (e.g. a forward-Euler ``u + dt * su``);
    * ``update(fields, outputs, scalars) -> fields`` — additionally receives
      the runtime scalars mapping, for rules that need traced values inside
      the loop (a traced ``dt``, the serving layer's bucket-size scalars).

    The rule is *element-wise*: a field's new value at a point depends on
    the fields and outputs at that point alone (and on scalars).  That
    contract lets the stream chain apply it plane by plane and the Pallas
    fused loop apply it in a block kernel's epilogue, tile by tile.  A rule
    that breaks it says so with ``_plane_local = False`` (the serving
    layer's bucket refresh, which gathers across whole axes), which
    :func:`plane_local` reads for both: such a rule is always traced into
    the loop body on XLA, over the whole interiors.

    Every time-loop lowering routes the rule through here, so both
    signatures work everywhere.  Idempotent: adapting an already-adapted
    rule returns it unchanged.  A callable matching *neither* form — wrong
    arity for both — raises a :class:`TypeError` naming the accepted
    signatures here, at compile time, instead of a bare arity error from
    deep inside the traced loop body.
    """
    if update is None or getattr(update, "_takes_scalars", False):
        return update
    if not callable(update):
        raise TypeError(
            f"update rule must be callable, got {type(update).__name__}; "
            "accepted signatures: " + " or ".join(UPDATE_SIGNATURES))
    try:
        params = list(inspect.signature(update).parameters.values())
    except (TypeError, ValueError):
        params = None            # builtins/C callables: assume the 2-form
    if params is None:
        takes3 = False
    else:
        pos = [q for q in params if q.kind in (q.POSITIONAL_ONLY,
                                               q.POSITIONAL_OR_KEYWORD)]
        required = [q for q in pos if q.default is q.empty]
        var_pos = any(q.kind == q.VAR_POSITIONAL for q in params)
        # can the callable be invoked with exactly 2 / exactly 3 positional
        # arguments?  (keyword-only params with defaults don't matter)
        fits2 = len(required) <= 2 and (len(pos) >= 2 or var_pos)
        fits3 = len(required) <= 3 and (len(pos) >= 3 or var_pos)
        if not fits2 and not fits3:
            raise TypeError(
                f"update rule {getattr(update, '__name__', update)!r} takes "
                f"{len(required)} required positional argument(s); a fused "
                "time-loop update rule must accept one of: "
                + " or ".join(UPDATE_SIGNATURES))
        takes3 = fits3
    if takes3:
        def fn(fields, outputs, scalars, _u=update):
            return _u(fields, outputs, scalars)
    else:
        def fn(fields, outputs, scalars, _u=update):
            return _u(fields, outputs)
    fn._takes_scalars = True
    return fn


@dataclasses.dataclass
class ShardSpec:
    """Distributed layout of one compiled executable (paper step 9: one AXI
    bundle / HBM bank per field; here one mesh shard per sub-domain).

    Derived by :func:`make_shard_spec` from the plan's fuse groups: each
    field's halo depth is the elementwise max over every consuming group's
    window halo, so one carry-resident exchange per field per step serves
    all groups (they slice their own window geometry out of the exchanged
    buffer).  The planner prices blocks against ``local_grid``, never the
    global domain.
    """

    # mesh axis name per grid axis (None = unsharded axis)
    mesh_axes: tuple
    # mesh axis name -> number of shards along it
    axis_sizes: dict
    local_grid: tuple
    global_grid: tuple
    # field -> (ndim, 2) halo depth of the worst consuming fuse group
    field_halo: dict
    # the plan's stream axis (schedule="stream"; None for block plans).
    # When this axis is itself sharded, the per-shard sweep needs exact,
    # chain-deepened lo-side ghost planes (see dataflow.stream_halo) — the
    # field halos above already price them.
    stream_axis: int | None = None

    def axis_size(self, ax: int) -> int:
        name = self.mesh_axes[ax]
        return 1 if name is None else int(self.axis_sizes[name])

    @property
    def stream_sharded(self) -> bool:
        """True when the plan streams over an axis the mesh decomposes."""
        return (self.stream_axis is not None
                and self.axis_size(self.stream_axis) > 1)

    def describe(self) -> str:
        parts = []
        for ax, name in enumerate(self.mesh_axes):
            parts.append(f"{name or '-'}:{self.axis_size(ax)}")
        stream = ("" if self.stream_axis is None
                  else f", stream_axis={self.stream_axis}"
                       f"{'/sharded' if self.stream_sharded else ''}")
        return (f"shard(mesh=[{','.join(parts)}], local={self.local_grid}, "
                f"global={self.global_grid}{stream})")


def normalize_mesh_axes(mesh_axes: Sequence, ndim: int) -> tuple:
    """Mesh axis names truncated/padded to ``ndim`` entries (None = unsharded)
    — the one normalization every layer (pipeline, tuner, shard spec) uses."""
    ma = tuple(mesh_axes or ())
    return ma[:ndim] + (None,) * (ndim - len(ma))


def shard_local_grid(global_grid: Sequence[int], mesh, mesh_axes: Sequence
                     ) -> tuple:
    """Per-shard sub-domain extents; validates mesh/grid divisibility."""
    global_grid = tuple(int(g) for g in global_grid)
    out = []
    for ax, g in enumerate(global_grid):
        name = mesh_axes[ax] if ax < len(mesh_axes) else None
        n = 1 if name is None else int(mesh.shape[name])
        if g % n:
            raise ValueError(f"grid axis {ax} ({g}) not divisible by mesh "
                             f"axis {name!r} ({n})")
        out.append(g // n)
    return tuple(out)


def make_shard_spec(p: Program, plan: DataflowPlan, global_grid: Sequence[int],
                    mesh, mesh_axes: Sequence,
                    group_halos: list | None = None,
                    stream_axis: int | None = None) -> ShardSpec:
    """Build the :class:`ShardSpec` for ``plan`` over ``mesh``.

    Halo exchange is single-hop (each shard talks to its immediate
    neighbours), so a field's halo may not exceed the local extent of a
    sharded axis — violations raise here, at plan time, not inside the
    traced loop.  Pass ``group_halos`` (one :func:`infer_halo` result per
    fuse group, or the stream graph's chain-accumulated region halos) to
    reuse halos the caller already computed.  ``stream_axis`` records the
    plan's sweep axis for stream plans: sharding it is supported — the
    ``group_halos`` must then carry the deepened ghost-plane reach, and a
    sweep (plus temporal chain) too deep for the local block fails the
    single-hop check here with the mesh/time_tile levers named.
    """
    ndim = p.ndim
    mesh_axes = normalize_mesh_axes(mesh_axes, ndim)
    local_grid = shard_local_grid(global_grid, mesh, mesh_axes)
    if group_halos is None:
        group_halos = plan_group_halos(p, plan)
    field_halo = {}
    for gh in group_halos:
        for f in gh.group_inputs:
            cur = field_halo.get(f)
            field_halo[f] = (np.array(gh.input_halo) if cur is None
                             else np.maximum(cur, gh.input_halo))
    axis_sizes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    for ax, name in enumerate(mesh_axes):
        if name is None or axis_sizes.get(str(name), 1) == 1:
            continue
        for f, h in field_halo.items():
            if max(int(h[ax, 0]), int(h[ax, 1])) > local_grid[ax]:
                lever = ("coarsen the mesh axis "
                         f"{name!r} or enlarge the grid")
                if ax == stream_axis:
                    lever = (f"coarsen the mesh axis {name!r}, shallow the "
                             "time_tile chain, or leave the stream axis "
                             "unsharded")
                raise ValueError(
                    f"halo of field {f!r} on axis {ax} "
                    f"({int(h[ax, 0])},{int(h[ax, 1])}) exceeds the local "
                    f"extent {local_grid[ax]}; {lever}")
    return ShardSpec(mesh_axes=mesh_axes, axis_sizes=axis_sizes,
                     local_grid=local_grid,
                     global_grid=tuple(int(g) for g in global_grid),
                     field_halo=field_halo, stream_axis=stream_axis)


@dataclasses.dataclass
class TimeLoopSpec:
    """Plan for a fused on-device time loop (the paper's device-resident
    inter-iteration dataflow, §3.3 step 3 applied to the *time* axis).

    The loop carry holds one persistent, halo-padded buffer per program
    input field; each step reads stencil windows straight out of the carry
    (no per-step ``jnp.pad``), and the new interior of each field the
    update rule changes is written back.  Where that rule runs is the
    lowering's choice, recorded in ``update_placement``: the Pallas loop
    leaves a field the rule returns unchanged in the carry, and has a
    block kernel compute a changed one in its epilogue wherever the rule
    keeps the element-wise contract of :func:`adapt_update` (as
    :func:`plane_local` reads its ``_plane_local`` flag) and one group
    holds what the field reads, tracing the rest into the loop body on
    XLA.  How each new value reaches the carry is recorded in
    ``carry_placement``: the Pallas loop has the block kernel that
    computes a field zero on every axis store it straight into the
    padded layout (``"kernel"``), and XLA writes the rest
    (``carry_write``).  ``double_buffer`` assigns a front/back slot pair
    per persistent field; a kernel-written field uses both, since a kernel
    cannot write the buffer it reads its windows from: it reads the front
    and writes the back, and the loop body runs two steps, so the two
    swap back and each stays in its own slot.  A field XLA writes keeps
    one slot, its buffer handed on by donation of the loop carry.
    """

    steps: int
    # fields carried across steps (the program's external inputs)
    persistent: list
    # field -> (ndim, 2) carry padding [halo + tile alignment on the hi side]
    field_pad: dict
    # field -> (front_slot, back_slot) logical buffer ids
    double_buffer: dict
    # per fuse group: {field: (ndim,) int start offsets of the group's
    # expected window inside the carry buffer} (0 for transient inputs)
    group_offsets: list
    # how XLA writes a new value the kernels do not store in the carry:
    #   "repad"   — rebuild interior + constant zero halo in one fused write
    #               (zero-halo slabs are constants; fastest on XLA:CPU, which
    #               lowers the in-place form to a full read-modify-write)
    #   "inplace" — scatter the new interior into the carry
    #               (dynamic-update-slice; aliases on TPU)
    carry_write: str = "repad"
    # hi-side lane-tile alignment slab per axis, already folded into
    # field_pad[:, 1]; kept separately so halo refresh (periodic wrap,
    # distributed ppermute) can treat it as a plain zero slab
    align_hi: tuple = ()
    # distributed layout when the loop runs under shard_map; None = local.
    # With a shard, every extent in this spec is per-shard (local_grid).
    shard: ShardSpec | None = None
    # where the lowering computes each field's next value, as it reports
    # it (None where it does not): "kernel" (a kernel's epilogue or output),
    # "kept" (unchanged, left in the carry) or "xla" (the loop body)
    update_placement: dict | None = None
    # how the lowering writes each field's new value into its carry, as
    # it reports it: "kernel" (the producing block kernel stores it in the
    # padded back buffer), "refill" or "inplace" (XLA, as ``carry_write``
    # asks; a field periodic on any axis always refills) or "kept"
    carry_placement: dict | None = None

    def update_counts(self) -> dict:
        """How many persistent fields each place updates (all zero where
        the lowering reports no placement)."""
        where = list((self.update_placement or {}).values())
        return {k: where.count(k) for k in ("kernel", "kept", "xla")}

    def carry_counts(self) -> dict:
        """How many persistent fields each carry write takes (all zero
        where the lowering reports none)."""
        how = list((self.carry_placement or {}).values())
        return {k: how.count(k)
                for k in ("kernel", "refill", "inplace", "kept")}

    def describe(self) -> str:
        bufs = ", ".join(f"{f}:{a}/{b}" for f, (a, b)
                         in self.double_buffer.items())
        placed = ""
        if self.update_placement is not None:
            placed = ", update=[" + ", ".join(
                f"{k}:{n}" for k, n in self.update_counts().items()) + "]"
        if self.carry_placement is not None:
            placed += ", carry_write=[" + ", ".join(
                f"{k}:{n}" for k, n in self.carry_counts().items()) + "]"
        return (f"time_loop(steps={self.steps}, "
                f"persistent=[{','.join(self.persistent)}], "
                f"double_buffer=[{bufs}]{placed})")


def plan_time_loop(p: Program, plan: DataflowPlan, grid: Sequence[int],
                   steps: int, carry_write: str = "repad",
                   group_halos: list | None = None,
                   shard: ShardSpec | None = None) -> TimeLoopSpec:
    """Size the carry buffers for a fused time loop.

    For the Pallas backend a field's carry padding is the elementwise max of
    the window halos of every fuse group consuming it, plus the lane-tile
    alignment padding on the hi side (so any group can slice its expected
    window geometry out of the carry without reallocating).  The jnp
    backends share the same spec minus alignment.

    With ``shard``, ``grid`` must be the shard's *local* grid and the spec
    describes the per-shard carry; the distributed executor refreshes the
    halo slabs by ``ppermute`` inside the loop body.
    """
    grid = tuple(int(g) for g in grid)
    ndim = p.ndim
    steps = int(steps)
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    persistent = p.input_fields()

    align_hi = np.zeros(ndim, dtype=np.int64)
    if plan.backend == "pallas" and plan.schedule != "stream":
        # mirror build_group_call's tile geometry exactly (the stream
        # schedule never tiles, so its carries carry no alignment slab)
        block = tuple(min(int(b), g) for b, g in zip(plan.block[:ndim], grid))
        tiles = tuple(-(-grid[a] // block[a]) for a in range(ndim))
        align_hi = np.asarray([tiles[a] * block[a] - grid[a]
                               for a in range(ndim)], dtype=np.int64)

    field_pad = {f: _zeros(ndim) for f in persistent}
    if group_halos is None:
        group_halos = plan_group_halos(p, plan)
    for gh in group_halos:
        for f in gh.group_inputs:
            if f in field_pad:
                field_pad[f] = np.maximum(field_pad[f], gh.input_halo)
    # the jnp lowerings evaluate every op (no DCE), so their carry must also
    # cover raw access offsets from ops outside the live fuse groups; the
    # pallas backend only runs the planned (live) groups, so widening its
    # carry for dead ops would over-allocate every persistent buffer
    if plan.backend != "pallas":
        for op in p.ops:
            for a in op.accesses():
                m = field_pad.get(a.field)
                if m is None:
                    continue
                for ax in range(ndim):
                    o = int(a.offset[ax])
                    m[ax, 0] = max(m[ax, 0], -o)
                    m[ax, 1] = max(m[ax, 1], o)
    for f in persistent:
        field_pad[f][:, 1] += align_hi

    double_buffer = {f: (2 * i, 2 * i + 1) for i, f in enumerate(persistent)}
    group_offsets = []
    for gh in group_halos:
        offs = {}
        for f in gh.group_inputs:
            if f in field_pad:
                offs[f] = tuple(int(field_pad[f][a, 0] - gh.input_halo[a, 0])
                                for a in range(ndim))
            else:
                offs[f] = (0,) * ndim
        group_offsets.append(offs)
    if carry_write not in ("repad", "inplace"):
        raise ValueError(f"unknown carry_write {carry_write!r}")
    return TimeLoopSpec(steps=steps, persistent=persistent,
                        field_pad=field_pad, double_buffer=double_buffer,
                        group_offsets=group_offsets, carry_write=carry_write,
                        align_hi=tuple(int(a) for a in align_hi),
                        shard=shard)


def plan_group_halos(p: Program, plan: DataflowPlan,
                     stream_sharded: bool = False) -> list:
    """One :class:`~repro.core.passes.GroupHalo` per executed kernel of
    ``plan`` — block-schedule fuse groups via :func:`infer_halo`, stream
    regions (post-legalisation, with shift-register stream-axis halos, and
    reach accumulated over the chained steps when ``time_tile > 1``) via
    the dataflow layer.  ``stream_sharded`` deepens the stream-axis lo
    halos for a mesh that decomposes the sweep axis.  Every carry/shard
    sizing goes through here so the padding always matches what the
    lowered kernels will slice."""
    if plan.schedule == "stream":
        from .dataflow import lower_to_dataflow
        return lower_to_dataflow(
            p, plan, stream_sharded=stream_sharded).group_halos()
    return [infer_halo(p, grp) for grp in plan.groups]


def _dtype_bytes(dtype: str) -> int:
    return hw.DTYPE_BYTES[dtype]


def vmem_cost(p: Program, plan: DataflowPlan, grid: Sequence[int],
              steps: int | None = None, graph=None) -> int:
    """Bytes of VMEM one kernel instance of the *largest* group claims.

    window bytes x live inputs + margin-extended temps + output tiles,
    times 2 for the Pallas double-buffered pipeline.

    With ``steps`` (fused time loop), persistent inputs are windows sliced
    out of the loop *carry*, whose padding — the max halo over every
    consuming group plus the lane-tile ``align_hi`` slab sized by
    :func:`plan_time_loop` — can exceed this group's own halo, enlarging the
    window the ``input_pad`` path claims.  A plan that fits the budget
    single-step can therefore exceed it under ``steps=N``; the tuner prunes
    with this corrected cost.  The loop also has a kernel store a field
    zero on every axis straight into its carry, whole planes of the
    untiled axes, ring included, where only axis 0 is tiled
    (:func:`~repro.core.lower_pallas.place_carry`, which reads the update
    rule the plan never sees): each output no later group reads is priced
    at the largest such plane.
    """
    bs = _dtype_bytes(plan.dtype)
    grid = tuple(int(g) for g in grid)
    if plan.schedule == "stream":
        return _vmem_cost_stream(p, plan, grid, bs, graph=graph)
    group_halos = [infer_halo(p, grp) for grp in plan.groups]
    carry_pad = (plan_time_loop(p, plan, grid, steps,
                                group_halos=group_halos).field_pad
                 if steps is not None else {})
    blk = np.minimum(np.asarray(plan.block[:p.ndim]), np.asarray(grid))
    kinds = p.axis_boundaries()
    planes = [int(np.prod(np.asarray(grid[1:]) + pad[1:].sum(axis=1)))
              for f, pad in carry_pad.items() if set(kinds[f]) == {"zero"}]
    ring = 0
    if planes and p.ndim >= 3 and (blk[1:] == np.asarray(grid[1:])).all():
        ring = int(blk[0]) * max(planes) - int(np.prod(blk))
    worst = 0
    for k, (grp, gh) in enumerate(zip(plan.groups, group_halos)):
        later = {f for h in group_halos[k + 1:] for f in h.group_inputs}
        total = 0
        for f in gh.group_inputs:
            pad = gh.input_halo
            if f in carry_pad:
                pad = np.maximum(pad, carry_pad[f])
            win = blk + pad[:, 0] + pad[:, 1]
            total += int(np.prod(win)) * bs
        for i in grp:
            m = gh.margins[i]
            ext = blk + m[:, 0] + m[:, 1]
            total += int(np.prod(ext)) * bs
            out = p.ops[i].out
            if (out in gh.group_outputs and out not in later
                    and p.fields[out].role == FieldRole.OUTPUT):
                total += ring * bs
        worst = max(worst, total)
    return 2 * worst  # double buffering


def _vmem_cost_stream(p: Program, plan: DataflowPlan, grid: tuple,
                      bs: int, graph=None) -> int:
    """VMEM one stream region claims: the rolling window buffers (depth x
    padded plane per input), temp ring buffers, one margin-extended result
    plane per op, and the output planes in flight.  Unlike the block path
    there is no tile geometry — the non-stream axes are resident whole, so
    a carry's ``input_pad`` slicing never enlarges the kernel windows.

    With temporal blocking (effective ``time_tile = T > 1``) the chained
    kernel claims strictly more scratch, and the tuner's pruning must see
    it: the external plane buffers widen to the T-fold accumulated halo,
    every later chain stage keeps a window-depth ring of each persistent
    field at its own (shrinking) stage extent, and each stage's op planes
    carry the stage's accumulated margin.  Pricing only the T=1 geometry
    here would admit chained plans that overflow scratch at run time.

    With spatial unrolling (effective ``plane_tile = P > 1``) each sweep
    grid step stages a P-plane DMA block next to every window buffer
    (``depth + P`` planes live during the shift) and the output side holds
    the P-plane out block plus the up-to-``P-1``-plane staging ring that
    realigns completed planes to the block grid.
    """
    if graph is None:
        from .dataflow import lower_to_dataflow
        graph = lower_to_dataflow(p, plan)
    ndim = p.ndim
    T = getattr(graph, "time_tile", 1)
    P = getattr(graph, "plane_tile", 1)
    worst = 0
    for region in graph.regions:
        gh = region.halo
        hl = [int(gh.input_halo[a, 0]) for a in range(ndim)]
        hh = [int(gh.input_halo[a, 1]) for a in range(ndim)]
        # stage-s working extent on a non-stream axis: grid + margins +
        # (T-1-s) accumulated halo steps; stage 0 reads the full T-fold
        # padded external planes (plus the P-plane DMA block mid-shift)
        plane = [grid[a] + T * (hl[a] + hh[a]) for a in range(1, ndim)]
        total = 0
        for f in gh.group_inputs:
            total += (region.depths[f] + P) * int(np.prod(plane)) * bs
        for s in range(1, T):
            ext_s = [grid[a] + (T - s) * (hl[a] + hh[a])
                     for a in range(1, ndim)]
            for f in gh.group_inputs:
                total += region.depths[f] * int(np.prod(ext_s)) * bs
        for s in range(T):
            acc = T - 1 - s
            for i in region.ops:
                m = gh.margins[i]
                ext = [grid[a] + int(m[a, 0]) + int(m[a, 1])
                       + acc * (hl[a] + hh[a]) for a in range(1, ndim)]
                planes = 1 + region.rings.get(p.ops[i].out, 0)
                total += planes * int(np.prod(ext)) * bs
        out_planes = P + (P - 1 if P > 1 else 0)
        total += (len(gh.group_outputs) * out_planes
                  * int(np.prod(grid[1:])) * bs)
        worst = max(worst, total)
    return 2 * worst  # double-buffered pipeline, as in the block schedule


#: Grid points one block-schedule tile starts from: a 32x32x128 tile's
#: worth, or 4 planes of a 256x128 grid.  Mosaic compiles a PW-advection
#: kernel over 16 such planes for a v5e in ~30 s of host CPU, over 4 in
#: ~5 s.
TILE_POINTS = 32 * 32 * 128


def cap_tile(block: Sequence[int], grid: Sequence[int]) -> tuple:
    """The block-schedule tile shape: ``block`` on the leading axes (clipped
    to ``grid``) and the whole grid on the last two, with the leading axes
    halved, outermost first, until the tile holds at most
    :data:`TILE_POINTS` points (or every leading extent is 1).  Mosaic
    unrolls the kernel body over the whole tile, so its compile time grows
    with the tile."""
    n_tiled = max(0, len(grid) - 2)
    blk = ([max(1, min(int(block[ax]), int(grid[ax])))
            for ax in range(n_tiled)] + [int(g) for g in grid[n_tiled:]])
    for ax in range(n_tiled):
        while blk[ax] > 1 and int(np.prod(blk)) > TILE_POINTS:
            blk[ax] //= 2
    return tuple(blk)


def auto_plan(p: Program, grid: Sequence[int], *, backend: str = "pallas",
              strategy: str = "auto",
              dtype: str = "float32",
              vmem_budget: int = hw.VMEM_PLAN_BUDGET,
              steps: int | None = None,
              schedule: str = "block",
              time_tile: int = 1,
              plane_tile: int = 1) -> DataflowPlan:
    """Pick fuse groups and a block shape that fits VMEM.

    Mirrors the paper's auto-optimisation: the planner, not the programmer,
    chooses the dataflow structure.  Only the leading axes tile; the last
    two stay whole.  Mosaic takes a block whose last two extents match the
    array's or its (8, 128) tiling, and an overlapping window (tile plus
    halo) matches neither unless it spans the whole padded axis — whole
    rows along the lane axis are also the 512-bit-burst analogue.

    With ``steps`` (the plan will drive a fused time loop) the budget check
    uses the carry-aware :func:`vmem_cost`, so blocks whose loop-carry
    padding enlarges the kernel windows past the budget are shrunk here
    rather than discovered over budget at run time.

    Where the groups do not fit even at the smallest tile,
    :func:`budget_split` cuts them into contiguous runs that do.
    """
    grid = tuple(int(g) for g in grid)
    ndim = p.ndim
    groups = stage_split(p, strategy)
    if schedule == "stream":
        return _auto_plan_stream(p, grid, groups, backend=backend,
                                 dtype=dtype,
                                 vmem_budget=vmem_budget,
                                 time_tile=time_tile,
                                 plane_tile=plane_tile)
    if time_tile > 1:
        raise ValueError("time_tile > 1 requires schedule='stream' "
                         "(temporal blocking chains the stream sweep)")
    if plane_tile > 1:
        raise ValueError("plane_tile > 1 requires schedule='stream' "
                         "(spatial unrolling widens the stream sweep)")

    # start from a tile of at most TILE_POINTS, then shrink to fit the
    # VMEM budget
    n_tiled = max(0, ndim - 2)
    blk = list(cap_tile([32 if ndim == 3 else 256] * n_tiled, grid))

    def fits(gs):
        plan = DataflowPlan(groups=gs, block=tuple(blk), dtype=dtype,
                            backend=backend, mesh_axes=(None,) * ndim)
        return vmem_cost(p, plan, grid, steps=steps) <= vmem_budget

    # halve the leading axes, outermost first
    for ax in range(n_tiled):
        while blk[ax] > 1 and not fits(groups):
            blk[ax] //= 2
    if not fits(groups):
        groups = budget_split(groups, fits)
    return DataflowPlan(groups=groups, block=tuple(blk), dtype=dtype,
                        backend=backend, mesh_axes=(None,) * ndim)


def budget_split(groups: list, fits) -> list:
    """Cut each fuse group into contiguous runs, in program order, that
    ``fits``.

    A run grows op by op while the whole plan, the runs so far plus every
    op not yet placed as a group of its own, still ``fits``; the next op
    that would break it starts a new run.  The plan priced at each step
    holds every group, so a later group's deeper halo, which widens the
    loop carry and with it an earlier group's windows, is priced too.
    Where no two ops fit together the result is one op per group, as
    ``stage_split(p, "per_field")``; no run crosses a cut of ``groups``.
    """
    ops = [i for g in groups for i in g]
    starts = {g[0] for g in groups if g}
    out: list = []
    cur: list = []
    for k, i in enumerate(ops):
        if cur and (i in starts or not fits(
                out + [cur + [i]] + [[j] for j in ops[k + 1:]])):
            out.append(cur)
            cur = []
        cur.append(i)
    return out + [cur] if cur else out


def fuse_split(p: Program, plan: DataflowPlan, strategy: str = "auto") -> str:
    """How :func:`auto_plan` split ``strategy``'s fuse groups into
    ``plan``'s: ``"whole"`` (kept as :func:`stage_split` made them),
    ``"per_field"`` (one op per group) or ``"budget"`` (cut by
    :func:`budget_split` into larger runs)."""
    if plan.groups == stage_split(p, strategy):
        return "whole"
    if all(len(g) == 1 for g in plan.groups):
        return "per_field"
    return "budget"


def _auto_plan_stream(p: Program, grid: tuple, groups: list, *,
                      backend: str, dtype: str,
                      vmem_budget: int, time_tile: int = 1,
                      plane_tile: int = 1) -> DataflowPlan:
    """Stream-scheduled plan: one rolling-window sweep over the outer axis
    per (legalised) region, non-stream axes resident whole.  The ``block``
    field records the degenerate one-plane tile for display/cost purposes.
    If the full-slab window buffers blow the VMEM budget the levers are,
    in order: a narrower plane unroll (``plane_tile`` halves toward 1),
    then a shallower temporal chain (``time_tile`` halves toward 1),
    then a finer region split (intermediates stream through HBM)."""
    if backend != "pallas":
        raise ValueError(
            f"schedule='stream' is a pallas dataflow schedule; backend "
            f"{backend!r} has no streaming lowering")
    from .dataflow import lower_to_dataflow
    ndim = p.ndim
    block = (1,) + grid[1:]

    def build(groups, tile, ptile):
        plan = DataflowPlan(groups=groups, block=block, dtype=dtype,
                            backend=backend, mesh_axes=(None,) * ndim,
                            schedule="stream",
                            time_tile=tile, plane_tile=ptile)
        graph = lower_to_dataflow(p, plan, grid)
        plan.stream = graph.spec()
        return plan, graph

    tile = max(1, int(time_tile))
    ptile = max(1, int(plane_tile))
    plan, graph = build(groups, tile, ptile)
    while (vmem_cost(p, plan, grid, graph=graph) > vmem_budget
           and ptile > 1):
        ptile //= 2              # P-plane blocks too wide: narrower unroll
        plan, graph = build(groups, tile, ptile)
    while (vmem_cost(p, plan, grid, graph=graph) > vmem_budget
           and tile > 1):
        tile //= 2               # chained buffers too deep: shallower chain
        plan, graph = build(groups, tile, ptile)
    if (vmem_cost(p, plan, grid, graph=graph) > vmem_budget
            and any(len(g) > 1 for g in groups)):
        plan, _ = build(stage_split(p, "per_field"), tile, ptile)
    return plan
