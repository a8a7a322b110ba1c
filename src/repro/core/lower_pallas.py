"""Pallas backend orchestrator: Program + DataflowPlan -> executable.

Runs the plan's fuse groups in order.  Fields crossing a group boundary are
materialised in HBM — the TPU equivalent of the paper's inter-stage streams —
and re-padded for the consuming group's windows.  Inside a group everything
flows through the generated kernel's VMEM windows (see kernels/stencil3d.py).

The XLA ops around the kernels carry a ``repro_phase`` tag
(:func:`repro.obs.phase`): ``entry`` and ``exit`` around the fused loop,
``group_pad`` for the pads that feed a group, ``update`` (the rule's
fields left on XLA) and ``carry_write`` (the new values XLA, not a
kernel, writes into the carry) in each step.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from .. import obs
from ..kernels.stencil3d import build_group_call
from . import boundary as bc
from .ir import FieldRole, Program
from .schedule import DataflowPlan, TimeLoopSpec, adapt_update, plane_local

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64}


def _pad_coeffs(p: Program, calls, coeffs, dtype):
    """Per-call padded coefficient windows ('small data', paper step 8)."""
    out = []
    for call in calls:
        pc = {}
        for c in call.group_coeffs:
            ax = call.coeff_axis[c]
            pc[c] = bc.pad_coeff(jnp.asarray(coeffs[c], dtype=dtype),
                                 call.pad_lo[ax], call.pad_hi[ax],
                                 bc.coeff_mode(p, ax))
        out.append(pc)
    return out


def _run_groups(p: Program, calls, svec, pc_per_call, resolve_input,
                origin=None, back=None):
    """Run the fuse groups in order, materialising inter-group fields.

    ``resolve_input(call, f, env) -> (array, actual_pad | None)`` supplies
    each group input: either freshly padded to the call's window geometry
    (pad None) or an oversized persistent buffer with its actual padding,
    which the kernel slices its window out of via ``input_pad``.
    ``origin`` is the shard's global offset under a mesh (None locally).
    ``back`` holds the buffer of each value a call stores in a carry
    layout (its ``carry_names``).
    Returns the program outputs, and the new persistent fields of any call
    that computes them in an update epilogue.
    """
    env: dict = {}
    outputs: dict = {}
    for call, pc in zip(calls, pc_per_call):
        padded, ipad = {}, {}
        for f in call.group_inputs:
            padded[f], actual = resolve_input(call, f, env)
            if actual is not None:
                ipad[f] = actual
        kw = {}
        if getattr(call, "carry_names", ()):
            kw["back"] = {f: back[f] for f in call.carry_names}
        res = call(padded, svec, pc, input_pad=ipad or None, origin=origin,
                   **kw)
        for f, v in res.items():
            role = p.fields[f].role
            if role != FieldRole.INPUT:     # a new field is no group's input
                env[f] = v
            if role != FieldRole.TEMP:      # outputs, and new fields
                outputs[f] = v
    return outputs


def _sub_jaxprs(params):
    """The jaxprs an op calls, each with its constants."""
    for v in params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr, x.consts
            elif isinstance(x, jcore.Jaxpr):
                yield x, ()


def _on_tile(eqn, shape) -> bool:
    """Whether ``eqn`` can run on one tile of arrays traced at ``shape``:
    every value it touches is a scalar or ``shape``-sized, it shrinks no
    array to a scalar, the only shape it names is a broadcast's, and what
    it calls (``jit``, custom derivatives) is one such jaxpr taking its
    operands as they are and closing over nothing.  Whether the op is
    element-wise is the rule's contract (:func:`~.schedule.plane_local`),
    not checked here."""
    ins = [v.aval.shape for v in eqn.invars]
    outs = [v.aval.shape for v in eqn.outvars]
    if any(s not in ((), shape) for s in ins + outs):
        return False
    if () in outs and shape in ins:
        return False
    subs = list(_sub_jaxprs(eqn.params))
    if subs:
        if len(subs) != 1:
            return False
        (sub, consts), = subs
        return (not consts and not sub.constvars
                and [v.aval for v in sub.invars]
                == [v.aval for v in eqn.invars]
                and all(_on_tile(e, shape) for e in sub.eqns))
    return (eqn.primitive.name == "broadcast_in_dim"
            or not any(isinstance(v, tuple) and tuple(v) == shape
                       for v in eqn.params.values()))


def _eval_on_tile(eqns, env, outvars, tile):
    """Evaluate ``eqns`` (each :func:`_on_tile`) on tile-shaped values:
    a broadcast goes to ``tile``, a call is evaluated inline."""
    def read(v):
        return v.val if isinstance(v, jcore.Literal) else env[v]

    for e in eqns:
        ins = [read(v) for v in e.invars]
        subs = list(_sub_jaxprs(e.params))
        if subs:
            sub, _ = subs[0]
            outs = _eval_on_tile(sub.eqns, dict(zip(sub.invars, ins)),
                                 sub.outvars, tile)
        elif e.primitive.name == "broadcast_in_dim":
            outs = [jnp.broadcast_to(ins[0], tile)]
        else:
            outs = e.primitive.bind(*ins, **e.params)
            outs = outs if e.primitive.multiple_results else [outs]
        env.update(zip(e.outvars, outs))
    return [read(v) for v in outvars]


@dataclasses.dataclass(frozen=True)
class FieldUpdate:
    """What the update rule's new value of one persistent field reads."""
    fields: frozenset       # persistent fields, at this step
    outputs: frozenset      # program outputs of this step
    alias: str | None       # the output the new value is, exactly
    on_tile: bool           # a tile can compute it (every op _on_tile)


class RuleTrace:
    """One trace of an adapted update rule on interior-shaped stand-ins:
    ``kept``, the persistent fields it returns as themselves (or not at
    all), and ``changed``, a :class:`FieldUpdate` per other field.
    :meth:`tile_rule` hands a kernel epilogue the ops of some of those."""

    def __init__(self, update, persistent, outputs, scalars, shape, dtype):
        persistent, outputs = list(persistent), list(outputs)
        scalars = list(scalars)
        shape = tuple(int(g) for g in shape)
        unknown, returned = [], []

        class Scalars(dict):
            # a scalar that is not the program's rides in only at run
            # time: no kernel has it, so every change then stays on XLA
            def __missing__(self, name):
                unknown.append(name)
                return jnp.zeros((), jnp.float32)

            def get(self, name, default=None):
                return self[name]

        def flat(f, o, s):
            res = update(dict(zip(persistent, f)), dict(zip(outputs, o)),
                         Scalars(zip(scalars, s)))
            returned[:] = [k for k in persistent if k in res]
            return [jnp.asarray(res[k]) for k in returned]

        arr = jax.ShapeDtypeStruct(shape, dtype)
        jaxpr = jax.make_jaxpr(flat)(
            [arr] * len(persistent), [arr] * len(outputs),
            [jax.ShapeDtypeStruct((), jnp.float32)] * len(scalars)).jaxpr
        self._jaxpr = jaxpr
        self._names = dict(zip(jaxpr.invars,
                               [("field", f) for f in persistent]
                               + [("output", o) for o in outputs]
                               + [("scalar", s) for s in scalars]))
        self._out = dict(zip(returned, jaxpr.outvars))
        producer = {v: i for i, e in enumerate(jaxpr.eqns)
                    for v in e.outvars}
        self._ops, self.changed = {}, {}
        for f, out in self._out.items():
            kind, name = (self._names.get(out, (None, None))
                          if isinstance(out, jcore.Var) else (None, None))
            if (kind, name) == ("field", f):
                continue                # returned as itself: kept
            reads = {"field": set(), "output": set(), "scalar": set()}
            ops, stack, seen = set(), [out], set()
            ok = not unknown and out.aval.shape == shape
            while stack:
                v = stack.pop()
                if not isinstance(v, jcore.Var) or v in seen:
                    continue
                seen.add(v)
                if v in self._names:
                    reads[self._names[v][0]].add(self._names[v][1])
                elif v in producer:
                    e = jaxpr.eqns[producer[v]]
                    ok = ok and _on_tile(e, shape)
                    ops.add(producer[v])
                    stack.extend(e.invars)
                else:                   # an array the rule closes over
                    ok = False
            self._ops[f] = ops
            self.changed[f] = FieldUpdate(
                fields=frozenset(reads["field"]),
                outputs=frozenset(reads["output"]),
                alias=name if kind == "output" else None, on_tile=ok)
        self.kept = [f for f in persistent if f not in self.changed]

    def tile_rule(self, fields):
        """``rule(held, outputs, scalars, tile) -> {field: value}``: the
        rule's ops for ``fields`` alone, on one tile.  ``rule.reads``
        names the persistent fields, outputs and scalars they take, which
        the caller hands in at the tile's own points."""
        fields = list(fields)
        ops = sorted(set().union(*(self._ops[f] for f in fields)))
        eqns = [self._jaxpr.eqns[i] for i in ops]
        outvars = [self._out[f] for f in fields]
        used = {v for v in [*outvars, *(v for e in eqns for v in e.invars)]
                if isinstance(v, jcore.Var)}
        takes = {v: kn for v, kn in self._names.items() if v in used}

        def rule(held, outputs, scalars, tile):
            given = {"field": held, "output": outputs, "scalar": scalars}
            env = {v: given[k][n] for v, (k, n) in takes.items()}
            return dict(zip(fields, _eval_on_tile(eqns, env, outvars, tile)))

        rule.reads = {k: sorted(n for kk, n in takes.values() if kk == k)
                      for k in ("field", "output", "scalar")}
        return rule


def place_update(p: Program, calls, persistent, grid_shape, dtype, update,
                 local: bool = True):
    """Decide where the fused loop computes each persistent field's next
    value, from one trace of the adapted ``update`` (:class:`RuleTrace`),
    whether it keeps the element-wise contract (``local``, from
    :func:`~.schedule.plane_local`) and the fuse groups.

    Returns ``(placement, alias, calls)``.  ``placement[f]`` is ``"kept"``
    for a field the rule returns unchanged (it stays in the carry,
    untouched); ``"kernel"`` for one a kernel computes — the output it
    equals (``alias[f]``), or an update epilogue in the first block group
    that produces every output and holds every field its new value reads
    (that call is rebuilt with the update, running only the rule's ops
    for it); and ``"xla"`` for the rest, which the loop body computes by
    calling the rule on the whole interiors.  Where no field stays on XLA
    the rule reads no output in the loop body, so an output that only
    hosted updates read is no longer stored.  A rule that is not
    element-wise keeps every change on XLA.
    """
    produced = [o for c in calls for o in c.group_outputs
                if p.fields[o].role == FieldRole.OUTPUT]
    try:
        trace = RuleTrace(update, persistent, produced, p.scalars,
                          grid_shape, dtype)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError):
        # a rule that needs concrete values (a caller's jit=False loop
        # handed it Python scalars) is traced into the loop body alone
        return {f: "xla" for f in persistent}, {}, list(calls)
    placement = {f: "kept" for f in trace.kept}
    alias, hosted = {}, {}
    for f, u in trace.changed.items():
        placement[f] = "xla"
        if not local:
            continue
        if u.alias is not None:
            alias[f], placement[f] = u.alias, "kernel"
        elif u.on_tile:
            host = next((i for i, c in enumerate(calls)
                         if hasattr(c, "rebuild")
                         and u.outputs <= set(c.group_outputs)
                         and u.fields <= set(c.group_inputs)), None)
            if host is not None:
                hosted.setdefault(host, []).append(f)
                placement[f] = "kernel"
    # an output stays stored while a later group, an alias or the rule on
    # XLA (which reads every output) needs it
    needed = {f for c in calls for f in c.group_inputs} | set(alias.values())
    if "xla" in placement.values():
        needed |= set(produced)
    calls = list(calls)
    for i, fields in hosted.items():
        drop = [o for o in calls[i].group_outputs
                if p.fields[o].role == FieldRole.OUTPUT and o not in needed]
        calls[i] = calls[i].rebuild(update=trace.tile_rule(fields),
                                    update_fields=fields, drop_outputs=drop)
    return {f: placement[f] for f in persistent}, alias, calls


def place_carry(p: Program, calls, spec: TimeLoopSpec, placement, alias):
    """Decide how the fused loop writes each persistent field's new value
    into its carry buffer, from where :func:`place_update` put the update.

    Returns ``(carry_placement, calls)``.  ``carry_placement[f]`` is
    ``"kept"`` for a field that stays as it is; ``"kernel"`` for one the
    block kernel that computes it (an output it aliases, or its update
    epilogue) stores straight into the carry's padded layout, in the back
    buffer — that call is rebuilt with its ``carry_pad``; otherwise XLA
    writes the new interior: ``"inplace"`` (scattered, as
    ``spec.carry_write`` asks, for a field zero on every axis) or
    ``"refill"`` (padded anew).  A kernel writes the carry where the field
    is zero along every axis (its halo never changes, so the planes the
    kernel does not write stay as the entry left them), the kernel can
    (``writes_carry``: block calls tiled along axis 0 alone), and, for an
    output the field aliases, no later group, no other field and no rule
    on XLA reads that output.  Later groups read a field's current value
    from the front buffer, so an epilogue's new value is free to go.
    """
    kinds = p.axis_boundaries()
    rule_on_xla = "xla" in placement.values()
    targets = list(alias.values())
    carry_pad: dict = {}                # call index -> {stored name: pad}
    where = {}
    for f in spec.persistent:
        where[f] = ("kept" if placement[f] == "kept" else
                    "inplace" if (spec.carry_write == "inplace"
                                  and set(kinds[f]) == {"zero"})
                    else "refill")
        name = alias.get(f, f)
        i = next((i for i, c in enumerate(calls)
                  if name in c.group_outputs
                  or name in getattr(c, "update_fields", ())), None)
        if (placement[f] != "kernel" or i is None
                or not getattr(calls[i], "writes_carry", False)
                or set(kinds[f]) != {"zero"}
                or (f in alias and (
                    rule_on_xla or targets.count(name) > 1
                    or any(name in c.group_inputs for c in calls[i + 1:])))):
            continue
        carry_pad.setdefault(i, {})[name] = spec.field_pad[f]
        where[f] = "kernel"
    calls = list(calls)
    for i, pads in carry_pad.items():
        calls[i] = calls[i].rebuild(carry_pad=pads)
    return where, calls


def _scalar_vec(p: Program, scalars):
    return (jnp.asarray([scalars[s] for s in p.scalars], dtype=jnp.float32)
            if p.scalars else None)


def lower(p: Program, plan: DataflowPlan, grid_shape):
    """Return fn(fields, scalars) -> dict of output arrays."""
    dtype = _DTYPES[plan.dtype]
    grid_shape = tuple(int(g) for g in grid_shape)
    calls = [build_group_call(p, grp, plan.block, grid_shape, dtype=dtype)
             for grp in plan.groups]
    return lower_from_calls(p, dtype, calls)


def lower_from_calls(p: Program, dtype, calls):
    """Single-step orchestrator over prebuilt kernel calls (shared by the
    block schedule above and the stream schedule in lower_stream.py — any
    call exposing the build_group_call geometry attributes works)."""

    def run(fields: Mapping[str, jnp.ndarray],
            scalars: Mapping[str, jnp.ndarray] | None = None,
            coeffs: Mapping[str, jnp.ndarray] | None = None):
        scalars = scalars or {}
        coeffs = coeffs or {}
        bnd = p.boundaries()
        with obs.phase("entry"):
            ext = {k: jnp.asarray(v, dtype=dtype) for k, v in fields.items()}
            svec = _scalar_vec(p, scalars)
            pc_per_call = _pad_coeffs(p, calls, coeffs, dtype)

        def resolve(call, f, env):
            x = env[f] if f in env else ext[f]
            with obs.phase("group_pad"):
                return bc.pad_field(x, call.halo_lo, call.halo_hi, bnd[f],
                                    align_hi=call.align_hi), None

        return _run_groups(p, calls, svec, pc_per_call, resolve)

    return run


def lower_time_loop(p: Program, plan: DataflowPlan, grid_shape,
                    spec: TimeLoopSpec, update):
    """Return fn(fields, scalars, coeffs) -> final fields after ``spec.steps``
    fused iterations — one compiled program, no host round trips.

    The carry of a ``lax.fori_loop`` holds one *pre-padded* persistent buffer
    per program input field, sized by ``spec.field_pad`` so every consuming
    fuse group can slice its window geometry straight out of it (the kernel's
    ``input_pad`` path).  The update rule runs in the kernels' epilogues
    where it can, and those kernels store a field zero on every axis
    straight into the padded back buffer (see :func:`time_loop_from_calls`
    and :func:`place_carry`).  XLA writes the rest: zero slabs never
    change, so it either scatters the interior in place
    (``carry_write="inplace"``) or rebuilds it as one fused
    interior-plus-constant-halo write (``"repad"``, the default; see
    :class:`TimeLoopSpec`); a field periodic along any axis has its carry
    rebuilt from the new interior (the wraparound values change with it).
    Coefficients are loop-invariant and padded once, outside the loop.
    """
    dtype = _DTYPES[plan.dtype]
    grid_shape = tuple(int(g) for g in grid_shape)
    calls = [build_group_call(p, grp, plan.block, grid_shape, dtype=dtype)
             for grp in plan.groups]
    return time_loop_from_calls(p, dtype, grid_shape, spec, update, calls)


def time_loop_from_calls(p: Program, dtype, grid_shape, spec: TimeLoopSpec,
                         update, calls, chain: int = 1, epilogue=None):
    """Fused-loop orchestrator over prebuilt kernel calls (shared with the
    stream schedule, whose carries have no alignment slab).

    ``chain`` is how many time steps one pass over ``calls`` advances: 1
    for plain kernels (stencil outputs + one update here, per iteration),
    T for a temporally-blocked stream chain, which applies all T updates
    in-kernel and *returns the new fields* (``call.returns_fields``) — the
    loop body then only writes them back into the carry.  The loop runs
    ``spec.steps // chain`` iterations, and ``epilogue`` — a second call
    list advancing ``spec.steps % chain`` steps — runs once after it,
    slicing its (shallower) windows out of the same carry via
    ``input_pad``.

    With plain kernels the update rule runs where :func:`place_update`
    puts it, decided once at build time from one trace of the rule: a
    field the rule returns unchanged stays in the carry untouched (no
    interior slice, no re-pad); a field whose new value is a kernel's
    output, or which a block kernel can compute in its epilogue from what
    it holds (the rule's own ops for that field, on the kernel's tile),
    comes out of that kernel and is only written back; only the rest is
    computed by XLA on the whole interiors.  Computing on a tile is exact
    because the rule is element-wise (the contract of
    :func:`adapt_update`); a rule marked ``_plane_local = False`` (the
    serving layer's bucket refresh) breaks it, and :func:`plane_local`
    keeps all its changes on XLA.  Where each field went is exposed as
    ``update_placement`` on the returned function.

    How each new value reaches the carry is :func:`place_carry`'s choice,
    exposed as ``carry_placement``.  A field a block kernel stores in the
    carry's padded layout (``"kernel"``) holds two buffers, front and back
    (``spec.double_buffer``), made once on entry: the front padded from
    the field, the back all zeros.  The kernel reads its windows from the
    front and writes the back, which it never reads, through an aliased
    operand; the back's axis-0 halo planes are never written and stay
    zero.  XLA keeps each loop-carry slot in one
    buffer, so the body runs two steps, front to back and back to front,
    and each buffer stays in its own slot; an odd step runs once after
    the loop, and its result is read from the back.
    """
    raw_update, update = update, adapt_update(update)
    ndim = p.ndim
    fpad = spec.field_pad
    bnd = p.boundaries()
    align = spec.align_hi or (0,) * ndim
    chain = max(1, int(chain))
    outer = int(spec.steps) // chain
    if int(spec.steps) % chain and epilogue is None and chain > 1:
        raise ValueError(
            f"steps={spec.steps} is not a multiple of the chain depth "
            f"{chain} and no remainder epilogue was provided")
    interior = {f: tuple(slice(int(fpad[f][a, 0]),
                               int(fpad[f][a, 0]) + grid_shape[a])
                         for a in range(ndim))
                for f in spec.persistent}
    if getattr(calls[0], "returns_fields", False):
        # a temporally-blocked chain applies the whole rule in-kernel
        placement, alias = {f: "kernel" for f in spec.persistent}, {}
    else:
        placement, alias, calls = place_update(
            p, calls, spec.persistent, grid_shape, dtype, update,
            local=plane_local(raw_update))
    on_xla = [f for f in spec.persistent if placement[f] == "xla"]
    carry_place, calls = place_carry(p, calls, spec, placement, alias)
    # fields with a back buffer the kernels write: two steps per iteration
    paired = [f for f in spec.persistent if carry_place[f] == "kernel"]
    per_iter = 2 if paired else 1

    def refill(f, x):
        # halo slabs per the field's boundary; the lane-alignment slab
        # (inside fpad[:, 1]) is always zero — never read in-domain
        return bc.pad_field(x, fpad[f][:, 0],
                            [int(fpad[f][a, 1]) - int(align[a])
                             for a in range(ndim)],
                            bnd[f], align_hi=align)

    def run(fields: Mapping, scalars: Mapping | None = None,
            coeffs: Mapping | None = None):
        scalars = scalars or {}
        coeffs = coeffs or {}
        with obs.phase("entry"):
            svec = _scalar_vec(p, scalars)
            # coefficients never change across steps: pad per consuming
            # group once, before the loop ("small data" stays resident)
            pc_per_call = _pad_coeffs(p, calls, coeffs, dtype)
            pc_epilogue = (_pad_coeffs(p, epilogue, coeffs, dtype)
                           if epilogue is not None else None)
            # pad the persistent carry buffers exactly once; a back
            # buffer's interior is written before it is read, and its
            # halo is zero
            carry = {f: refill(f, jnp.asarray(fields[f], dtype=dtype))
                     for f in spec.persistent}
            back = {f: jnp.zeros_like(carry[f]) for f in paired}

        def advance(state, calls_, pc_):
            carry, back = state

            def resolve(call, f, env):
                if f in carry:              # persistent: window from carry
                    return carry[f], fpad[f]
                with obs.phase("group_pad"):
                    return bc.pad_field(env[f], call.halo_lo, call.halo_hi,
                                        bnd[f], align_hi=call.align_hi), None

            if getattr(calls_[0], "returns_fields", False):
                # temporally-blocked chain: one call advances every field
                # by its full chain depth, updates included
                call = calls_[0]
                padded = {f: carry[f] for f in call.group_inputs}
                new = call(padded, svec, pc_[0],
                           input_pad={f: fpad[f] for f in call.group_inputs})
            else:
                outputs = _run_groups(
                    p, calls_, svec, pc_, resolve,
                    back={alias.get(f, f): back[f] for f in paired})
                # kernel-computed fields come back among the outputs
                new = {f: outputs[alias.get(f, f)] for f in spec.persistent
                       if placement[f] == "kernel"}
                if on_xla:
                    with obs.phase("update"):
                        cur = {f: carry[f][interior[f]]
                               for f in spec.persistent}
                        merged = dict(cur)
                        merged.update(update(cur, outputs, scalars))
                        new.update({f: merged[f] for f in on_xla})
            out = {}
            with obs.phase("carry_write"):
                for f in spec.persistent:
                    how = carry_place[f]
                    if how == "kept":
                        out[f] = carry[f]   # unchanged: stays as it is
                    elif how == "kernel":
                        out[f] = new[f]     # the back buffer, written
                    elif how == "inplace":
                        # zero halos on every axis never change: scatter the
                        # interior only
                        out[f] = carry[f].at[interior[f]].set(
                            jnp.asarray(new[f], dtype=dtype))
                    else:
                        # one fused interior write + constant (zero) or
                        # refreshed (wraparound) halo slabs — no carry RMW
                        out[f] = refill(f, jnp.asarray(new[f], dtype=dtype))
            # the old front is the next step's back buffer
            return out, {f: carry[f] for f in paired}

        def body(_, state):
            for _ in range(per_iter):
                state = advance(state, calls, pc_per_call)
            return state

        state = jax.lax.fori_loop(0, outer // per_iter, body, (carry, back))
        for _ in range(outer % per_iter):
            state = advance(state, calls, pc_per_call)
        if epilogue is not None and int(spec.steps) % chain:
            state = advance(state, epilogue, pc_epilogue)
        carry, _ = state
        with obs.phase("exit"):
            return {f: carry[f][interior[f]] for f in spec.persistent}

    run.update_placement = placement
    run.carry_placement = carry_place
    return run
