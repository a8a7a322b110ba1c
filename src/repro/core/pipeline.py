"""End-to-end driver: Program -> DataflowPlan -> compiled executable.

The user-facing API (the role PSyclone's code-generation entry point plays):

    prog = pw_advection()
    ex = compile_program(prog, (64, 64, 128), options=CompileOptions(
             backend="pallas"))
    out = ex(fields, scalars, coeffs)          # dict of output arrays

``CompileOptions`` is a frozen dataclass — build a new value per
configuration (``dataclasses.replace`` to vary one knob) rather than
mutating; loose kwargs (``compile_program(prog, grid, backend="pallas")``)
remain accepted and normalise to the same object.

Backends:
    "pallas"     generated Pallas dataflow kernels (the paper's contribution)
    "jnp_fused"  XLA-fused full-array execution  (DaCe-role baseline)
    "jnp_naive"  op-at-a-time full-array execution (unoptimised-HLS role)
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Mapping

import jax

from ..obs.events import PlanChosen
from ..obs.metrics import global_metrics
from ..obs.trace import resolve_tracer
from . import boundary as bc
from . import dataflow, distribute, lower_jnp, lower_pallas, lower_stream
from .ir import Program
from .passes import infer_halo
from .schedule import (DataflowPlan, ShardSpec, TimeLoopSpec, auto_plan,
                       fuse_split, make_shard_spec, normalize_mesh_axes,
                       plan_time_loop, plane_local, shard_local_grid)

_BACKENDS = ("pallas", "jnp_fused", "jnp_naive")


class TileDemotionWarning(UserWarning):
    """An explicitly requested ``time_tile``/``plane_tile`` was demoted by
    stream legalisation — the compile still succeeds, at the effective
    depth/width recorded on ``plan.stream`` (the structured reason is in
    the message and in the ``ChainDemoted``/``PlaneDemoted`` trace event)."""


@dataclasses.dataclass(frozen=True)
class CompileOptions:
    """Every compile-time knob of :func:`compile_program`, as one frozen
    value object — the canonical way to configure a compile:

        ex = compile_program(p, grid, options=CompileOptions(
                 schedule="stream", steps=16, update=rule, time_tile=4))

    Loose keyword arguments remain accepted (``compile_program(p, grid,
    steps=16, ...)``) and are normalised into a ``CompileOptions``
    internally, so both spellings hit the same validation; passing a knob
    *both* ways with different values is an error, never a silent pick.
    Being frozen, an options value can be shared between compiles (the
    serving engine, the tuner, benchmarks) without copy-on-write concerns.

    ``time_tile`` is the temporal-blocking depth: pipeline that many time
    steps through one stream sweep (requires ``schedule="stream"`` and a
    fused loop, i.e. ``steps``/``update``).  ``None`` defers to the plan
    (heuristic and tuned plans carry their own depth); an integer forces
    the requested depth, which stream legalisation may still demote to 1
    (see ``StreamSpec.time_tile``).

    ``plane_tile`` is the spatial-unroll width: DMA + compute that many
    consecutive planes per stream sweep grid step (requires
    ``schedule="stream"``; unlike ``time_tile`` it needs no fused loop —
    single-step sweeps unroll too).  ``None`` defers to the plan; an
    integer forces the requested width, which geometry may still demote
    to 1 (see ``StreamSpec.plane_tile``).

    ``trace`` enables structured tracing for this compile: a
    :class:`repro.obs.Tracer` (or ``True`` to install a fresh process
    tracer).  ``None`` defers to the ambient tracer — the process-wide
    no-op unless one was installed via ``repro.obs.set_tracer`` or
    ``REPRO_TRACE=path`` — so tracing is off by default with branch-only
    overhead.
    """

    backend: str = "pallas"
    plan: DataflowPlan | None = None
    jit: bool = True
    dtype: str = "float32"
    strategy: str = "auto"
    steps: int | None = None
    update: object = None
    carry_write: str | None = None
    tune_config: object = None
    plan_cache: object = None
    mesh: object = None
    mesh_axes: tuple | None = None
    boundary: object = None
    schedule: str | None = None
    time_tile: int | None = None
    plane_tile: int | None = None
    trace: object = None


_OPTION_DEFAULTS = {f.name: f.default
                    for f in dataclasses.fields(CompileOptions)}


def _resolve_options(options, kwargs) -> CompileOptions:
    """Merge the ``options=`` object and loose kwargs into one validated
    :class:`CompileOptions` (the single normalisation point)."""
    unknown = set(kwargs) - set(_OPTION_DEFAULTS)
    if unknown:
        raise TypeError(
            "unknown compile option(s) "
            + ", ".join(sorted(repr(k) for k in unknown))
            + "; valid options: "
            + ", ".join(sorted(_OPTION_DEFAULTS)))
    if options is None:
        return CompileOptions(**kwargs)
    if not isinstance(options, CompileOptions):
        raise TypeError(
            f"options= must be a CompileOptions, got "
            f"{type(options).__name__}")
    if not kwargs:
        return options
    for k, v in kwargs.items():
        cur = getattr(options, k)
        if cur is v or cur == _OPTION_DEFAULTS[k]:
            continue            # kwarg refines a knob the options left alone
        try:
            same = bool(v == cur)
        except Exception:
            same = False
        if not same:
            raise ValueError(
                f"compile option {k!r} passed both in options= ({cur!r}) "
                f"and as a keyword ({v!r}); set it one way, not both")
    return dataclasses.replace(options, **kwargs)


def _check_schedule(backend: str, schedule: str | None) -> None:
    """THE capability gate for schedule x backend x mesh combinations.

    Every compile path funnels through here — the explicitly requested
    ``schedule=`` before planning, and the plan-carried schedule after
    retargeting — so an unsupported combination fails fast with one
    message, never deep inside a lowering.  Valid combinations:

    * ``schedule="block"``  — any backend; local or ``mesh=``; single-step
      or fused ``steps=``;
    * ``schedule="stream"`` — ``backend="pallas"`` only; local or
      ``mesh=`` (the stream axis may itself be sharded), single-step or
      fused ``steps=``, ``time_tile >= 1``.
    """
    if schedule == "stream" and backend != "pallas":
        raise ValueError(
            "schedule='stream' is a pallas dataflow schedule; backend "
            f"{backend!r} has no streaming lowering. Valid combinations: "
            "schedule='block' with any backend (local or mesh=), or "
            "schedule='stream' with backend='pallas' (local or mesh=, "
            "time_tile >= 1)")


@dataclasses.dataclass
class CompiledStencil:
    program: Program
    plan: DataflowPlan
    grid: tuple
    _fn: object
    jitted: bool
    # fused time loop (``steps=N``): the executable returns the *final
    # fields* after N on-device iterations instead of one step's outputs
    time_spec: TimeLoopSpec | None = None
    # SPMD compile (``mesh=...``): the distributed layout; None = local
    shard: ShardSpec | None = None

    def __call__(self, fields: Mapping, scalars: Mapping | None = None,
                 coeffs: Mapping | None = None) -> dict:
        return self._fn(dict(fields), dict(scalars or {}), dict(coeffs or {}))

    def lower(self, fields: Mapping, scalars: Mapping | None = None,
              coeffs: Mapping | None = None):
        """Ahead-of-time lowering (``jax.stages.Lowered``) for these
        arguments — arrays or ``jax.ShapeDtypeStruct`` — so a caller can
        time the compile apart from the run, or read the program's HLO.
        The returned stage's ``compile()`` is called with the same three
        dicts."""
        if not self.jitted:
            raise ValueError("lower() needs an executable compiled with "
                             "jit=True")
        return self._fn.lower(dict(fields), dict(scalars or {}),
                              dict(coeffs or {}))


def compile_program(p: Program, grid, *,
                    options: CompileOptions | None = None,
                    **kwargs) -> CompiledStencil:
    """Compile ``p`` for ``grid`` — local or SPMD, single-step or fused loop.

    Configuration rides in a :class:`CompileOptions` (``options=``), or as
    loose keyword arguments with the same names — both are normalised into
    one validated ``CompileOptions`` before any work happens, and passing
    the same knob both ways with different values raises.

    With ``steps=N`` and an ``update(fields, outputs) -> fields`` rule, the
    whole time loop is lowered into the compiled program (one ``jax.jit``
    dispatch per call): the loop carry keeps the input fields resident and
    pre-padded on device, and ``update`` is traced into the loop body.  The
    executable then maps initial fields to the fields after N steps —
    exactly N iterations of :func:`run_time_loop`, without N dispatches,
    N ``jnp.pad`` rounds, or N host round trips.

    With ``mesh=`` (a ``jax.sharding.Mesh``) and ``mesh_axes=`` (mesh axis
    name per grid axis, None entries unsharded), the same program compiles
    SPMD: fields are domain-decomposed ``P(*mesh_axes)``, halos travel by
    ``ppermute``, and the plan is priced against the per-shard *local*
    block.  Combined with ``steps=N`` the halo exchange moves inside the
    fused loop carry — N distributed steps in one dispatch (see
    :func:`repro.core.distribute.lower_sharded_time_loop`).

    ``boundary=`` overrides the program's per-field boundary declarations
    before compiling: a single kind (``"zero"`` / ``"periodic"`` for a
    torus), a per-axis list (``["periodic", "zero", "zero"]``: cyclic along
    axis 0 only) or a ``{field: kind}`` mapping (see
    ``Program.with_boundary``).  A program declared with per-axis
    boundaries needs no override: the boundary is part of the program.

    ``schedule=`` selects the Pallas iteration schedule: ``"block"``
    (tiled output, overlapping VMEM windows per tile) or ``"stream"`` (the
    paper's shift-register dataflow: the kernel grid sweeps the outer axis
    plane-by-plane with rolling window buffers in the kernel carry, so each
    input element is fetched from HBM once per sweep — see
    :mod:`repro.core.dataflow` / :mod:`repro.core.lower_stream`).  ``None``
    keeps the plan's schedule (``"block"`` for heuristic plans; tuned plans
    carry whichever schedule measured fastest).  Streaming is pallas-only
    and composes with ``mesh=``: each shard sweeps the stream axis over
    its local block, halo refresh stays inside the fused-loop carry, and a
    sharded stream axis gets exact (chain-deepened) neighbour ghost planes
    (see :func:`_check_schedule` for the supported combinations).

    ``strategy="tuned"`` replaces the ``auto_plan`` heuristic with the
    measured search of :mod:`repro.core.tune`: the persistent plan cache is
    consulted first (a hit compiles the stored plan with zero timed runs);
    on a miss the tuner measures model-pruned candidates and persists the
    winner.  ``tune_config`` (:class:`~repro.core.tune.TuneConfig`) and
    ``plan_cache`` (:class:`~repro.core.tune.PlanCache`) override the search
    knobs and cache location.  ``carry_write=None`` defers to the tuned
    style (or ``"repad"`` under any other strategy).

    ``time_tile=T`` (temporal blocking, stream schedule only) pipelines T
    time steps through every sweep: the fused loop then runs ``steps // T``
    chained sweeps plus one remainder sweep, and each input plane is
    fetched from HBM once per T steps.  Requires ``steps``/``update``; the
    stream legaliser may demote the *effective* depth to 1 (recorded on
    ``plan.stream.time_tile``) when the program cannot chain.

    ``plane_tile=P`` (spatial unrolling, stream schedule only) advances P
    consecutive planes per sweep grid step: the sweep grid shrinks to
    ``ceil(n_steps / P)`` and window buffers shift by P planes at a time.
    Composes with ``time_tile`` (a P×T tile) and needs no fused loop; the
    legaliser demotes the *effective* width to 1 (recorded on
    ``plan.stream.plane_tile``) when P exceeds the shard-local extent.
    """
    o = _resolve_options(options, kwargs)
    tracer = resolve_tracer(o.trace)
    with tracer.active(), tracer.span(
            "compile", program=p.name,
            grid="x".join(str(int(g)) for g in grid),
            backend=o.backend, strategy=o.strategy) as sp:
        return _compile(p, grid, o, tracer, sp)


def _compile(p: Program, grid, o: CompileOptions, tracer,
             sp) -> CompiledStencil:
    """The compile body, running inside ``compile_program``'s span (with
    ``tracer`` installed as the ambient one, so the layers below — plan
    legalisation, tuning, sharded/stream lowering — emit into it without
    threading a tracer argument everywhere)."""
    backend, plan, jit = o.backend, o.plan, o.jit
    dtype, strategy, steps, update = o.dtype, o.strategy, o.steps, o.update
    carry_write, tune_config = o.carry_write, o.tune_config
    plan_cache, mesh, mesh_axes = o.plan_cache, o.mesh, o.mesh_axes
    boundary, schedule, time_tile = o.boundary, o.schedule, o.time_tile
    plane_tile = o.plane_tile
    metrics = global_metrics()
    metrics.counter("compile.compiles").inc()

    grid = tuple(int(g) for g in grid)
    if len(grid) != p.ndim:
        raise ValueError(f"grid rank {len(grid)} != program ndim {p.ndim}")
    if backend not in _BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    _check_schedule(backend, schedule)
    if time_tile is not None:
        time_tile = int(time_tile)
        if time_tile < 1:
            raise ValueError(f"time_tile must be >= 1, got {time_tile}")
        if time_tile > 1 and steps is None:
            raise ValueError(
                "time_tile > 1 pipelines T time steps through one stream "
                "sweep, which applies the update rule in-kernel — it needs "
                "the fused loop: pass steps=N and update=")
    if plane_tile is not None:
        plane_tile = int(plane_tile)
        if plane_tile < 1:
            raise ValueError(
                f"plane_tile must be >= 1, got {plane_tile}")
    if boundary is not None:
        p = p.with_boundary(boundary)
    # the compile's (field, axis) pairs by boundary kind
    kinds = [k for ks in p.axis_boundaries().values() for k in ks]
    for kind in bc.BOUNDARIES:
        metrics.counter(f"compile.halo_axes.{kind}").inc(kinds.count(kind))

    ndim = p.ndim
    if mesh is not None:
        if mesh_axes is None:
            mesh_axes = tuple(mesh.axis_names)
        mesh_axes = normalize_mesh_axes(mesh_axes, ndim)
        # the planner prices VMEM blocks against the per-shard local grid
        plan_grid = shard_local_grid(grid, mesh, mesh_axes)
    elif mesh_axes is not None:
        raise ValueError("mesh_axes requires mesh=")
    else:
        plan_grid = grid

    tuned_cw = None
    tuned_rec = None
    if plan is None:
        if strategy == "tuned":
            from . import tune
            res = tune.get_tuned_plan(p, grid, backend=backend, dtype=dtype,
                                      update=update, config=tune_config,
                                      cache=plan_cache,
                                      mesh=mesh, mesh_axes=mesh_axes)
            plan, tuned_cw = res.plan, res.carry_write
            tuned_rec = res.record
        else:
            plan = auto_plan(p, plan_grid, backend=backend, dtype=dtype,
                             strategy=strategy, steps=steps,
                             schedule=schedule or "block",
                             time_tile=time_tile or 1,
                             plane_tile=plane_tile or 1)
            # how the planner cut the strategy's fuse groups to fit VMEM
            metrics.counter(
                f"compile.fuse_split.{fuse_split(p, plan, strategy)}").inc()
            metrics.counter("compile.fuse_groups").inc(len(plan.groups))
    # plans can be shared (PlanCache entries, caller-held objects): the
    # compiled executable always gets its own deep copy, retargeted to the
    # requested backend/mesh, so no compile ever mutates another's plan
    overrides = {}
    if plan.backend != backend:
        overrides["backend"] = backend
    if mesh is not None and plan.mesh_axes_for(ndim) != mesh_axes:
        overrides["mesh_axes"] = mesh_axes
    if time_tile is not None and plan.time_tile != time_tile:
        overrides["time_tile"] = time_tile
    if plane_tile is not None and plan.plane_tile != plane_tile:
        overrides["plane_tile"] = plane_tile
    if schedule is not None and plan.schedule != schedule:
        # retargeting the schedule invalidates any cached stream geometry;
        # a stream plan's block is a degenerate one-plane placeholder, so
        # converting to "block" re-derives a real tile from the heuristic
        # (and drops any temporal chain — it is stream-only)
        overrides.update(schedule=schedule, stream=None)
        if schedule == "block" and plan.schedule == "stream":
            overrides.setdefault("time_tile", 1)
            overrides.setdefault("plane_tile", 1)
            overrides["block"] = auto_plan(
                p, plan_grid, backend=backend, dtype=plan.dtype,
                steps=steps).block
    plan = dataclasses.replace(plan, groups=[list(g) for g in plan.groups],
                               **overrides)
    if carry_write is None:
        carry_write = tuned_cw or "repad"

    graph = None
    group_halos = None
    stream_axis = None
    if plan.schedule == "stream":
        _check_schedule(backend, plan.schedule)
        metrics.counter("compile.stream_lowerings").inc()
        update_demote = None
        if plan.time_tile > 1 and not plane_local(update):
            # chained stages run the update inside the kernel on resident
            # planes; an update that reads the whole grid (e.g. the serving
            # layer's bucket refresh) has no plane-local form, so the chain
            # demotes to 1 — the step-level analog of chain_split_reason
            update_demote = ("update rule is not plane-local (it reads "
                             "beyond the resident planes), so chained "
                             "stages cannot apply it in-kernel")
            plan = dataclasses.replace(plan, time_tile=1)
        stream_axis = dataflow.STREAM_AXIS
        # a mesh that decomposes the sweep axis needs exact, chain-deepened
        # ghost planes on the lo side — the dataflow graph carries that
        stream_sharded = (
            mesh is not None
            and mesh_axes[stream_axis] is not None
            and int(mesh.shape[mesh_axes[stream_axis]]) > 1)
        # legalise fusion + size the shift registers once; carry sizing,
        # the shard spec, the plan's cached StreamSpec and the kernels all
        # share it
        graph = dataflow.lower_to_dataflow(p, plan, plan_grid,
                                           stream_sharded=stream_sharded)
        plan = dataclasses.replace(plan, stream=graph.spec())
        # an *explicitly requested* tile depth/width that legalisation
        # demoted warns (once per compile): non-tracing users must not
        # silently lose what they asked for.  Plan-carried requests (tuner
        # candidates, cached plans) stay quiet here — the dataflow layer
        # emits the ChainDemoted/PlaneDemoted trace events for those.
        if (time_tile is not None and time_tile > 1
                and graph.time_tile < time_tile):
            reason = update_demote or dataflow.chain_split_reason(
                p, [list(r.ops) for r in graph.regions])
            warnings.warn(
                f"time_tile={time_tile} demoted to effective "
                f"{graph.time_tile} for {p.name!r}: {reason}",
                TileDemotionWarning, stacklevel=4)
        if (plane_tile is not None and plane_tile > 1
                and graph.plane_tile < plane_tile):
            reason = dataflow.plane_split_reason(p, plane_tile, plan_grid)
            warnings.warn(
                f"plane_tile={plane_tile} demoted to effective "
                f"{graph.plane_tile} for {p.name!r}: {reason}",
                TileDemotionWarning, stacklevel=4)
        # chain-accumulated when the graph temporal-blocks: the fused-loop
        # carry must cover what the chained kernels slice per sweep
        group_halos = graph.group_halos()

    shard = None
    if mesh is not None:
        # halo inference per kernel is shared by the shard spec and the
        # time-loop carry sizing — compute it once (stream plans produced
        # theirs above, ghost-exact and chain-deepened)
        if group_halos is None:
            group_halos = [infer_halo(p, grp) for grp in plan.groups]
        shard = make_shard_spec(p, plan, grid, mesh, mesh_axes,
                                group_halos=group_halos,
                                stream_axis=stream_axis)

    time_spec = None
    if steps is not None:
        if update is None:
            raise ValueError("steps=N requires an update(fields, outputs) "
                             "rule to close the time loop")
        time_spec = plan_time_loop(p, plan, plan_grid, steps,
                                   carry_write=carry_write, shard=shard,
                                   group_halos=group_halos)
        if mesh is not None:
            raw = distribute.lower_sharded_time_loop(p, plan, grid,
                                                     time_spec, update, mesh,
                                                     graph=graph)
        elif plan.schedule == "stream":
            raw = lower_stream.lower_time_loop(p, plan, grid, time_spec,
                                               update, graph=graph)
        elif backend == "pallas":
            raw = lower_pallas.lower_time_loop(p, plan, grid, time_spec,
                                               update)
        else:
            raw = lower_jnp.lower_time_loop(p, backend.removeprefix("jnp_"),
                                            time_spec, update)
    elif mesh is not None:
        raw = distribute.lower_sharded(p, plan, grid, shard, mesh,
                                       graph=graph)
    elif plan.schedule == "stream":
        raw = lower_stream.lower(p, plan, grid, graph=graph)
    elif backend == "pallas":
        raw = lower_pallas.lower(p, plan, grid)
    else:
        raw = lower_jnp.lower(p, mode=backend.removeprefix("jnp_"))

    fn = jax.jit(raw) if jit else raw
    if steps is not None:
        metrics.counter("compile.fused_loops").inc()
        placement = getattr(raw, "update_placement", None)
        if placement is not None:
            # where the loop computes each field's next value, and how it
            # writes it into the carry (the sharded and jnp lowerings
            # update and refill every field on XLA and do not say)
            time_spec = dataclasses.replace(
                time_spec, update_placement=placement,
                carry_placement=raw.carry_placement)
            for where, n in time_spec.update_counts().items():
                metrics.counter(f"compile.update_fields.{where}").inc(n)
            for how, n in time_spec.carry_counts().items():
                metrics.counter(f"compile.carry_write.{how}").inc(n)
    if tracer.enabled:
        eff_tt = (plan.stream.time_tile if plan.stream is not None
                  else plan.time_tile)
        eff_pt = (plan.stream.plane_tile if plan.stream is not None
                  else plan.plane_tile)
        sp.set(schedule=plan.schedule, time_tile=int(eff_tt),
               plane_tile=int(eff_pt), steps=steps,
               mesh=None if mesh is None else dict(mesh.shape))
        if o.plan is None:
            # this compile *chose* a plan (heuristic or tuned); compiles
            # handed an explicit plan= (tuner candidates, cached serving
            # plans) did not decide anything worth announcing
            rec = tuned_rec or {}
            tracer.emit(PlanChosen(
                program=p.name, backend=backend, schedule=plan.schedule,
                strategy=strategy, label=rec.get("label", "auto_plan"),
                time_tile=int(eff_tt), plane_tile=int(eff_pt),
                modeled_us=rec.get("modeled_us"),
                measured_us=rec.get("us_fused") or rec.get("us_single")))
    return CompiledStencil(program=p, plan=plan, grid=grid, _fn=fn,
                           jitted=jit, time_spec=time_spec, shard=shard)


def run_time_loop(ex: CompiledStencil, fields: dict, scalars: dict,
                  coeffs: dict, steps: int, update) -> dict:
    """Simple host-side time loop; ``update(fields, outputs) -> fields``."""
    for _ in range(steps):
        out = ex(fields, scalars, coeffs)
        fields = update(fields, out)
    return fields
