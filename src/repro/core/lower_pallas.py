"""Pallas backend orchestrator: Program + DataflowPlan -> executable.

Runs the plan's fuse groups in order.  Fields crossing a group boundary are
materialised in HBM — the TPU equivalent of the paper's inter-stage streams —
and re-padded for the consuming group's windows.  Inside a group everything
flows through the generated kernel's VMEM windows (see kernels/stencil3d.py).
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from ..kernels.stencil3d import build_group_call
from . import boundary as bc
from .ir import Program
from .schedule import DataflowPlan, TimeLoopSpec, adapt_update

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64}


def _pad_coeffs(p: Program, calls, coeffs, dtype):
    """Per-call padded coefficient windows ('small data', paper step 8)."""
    cmode = bc.coeff_mode(p)
    out = []
    for call in calls:
        pc = {}
        for c in call.group_coeffs:
            ax = call.coeff_axis[c]
            pc[c] = bc.pad_coeff(jnp.asarray(coeffs[c], dtype=dtype),
                                 call.pad_lo[ax], call.pad_hi[ax], cmode)
        out.append(pc)
    return out


def _run_groups(p: Program, calls, svec, pc_per_call, resolve_input,
                origin=None):
    """Run the fuse groups in order, materialising inter-group fields.

    ``resolve_input(call, f, env) -> (array, actual_pad | None)`` supplies
    each group input: either freshly padded to the call's window geometry
    (pad None) or an oversized persistent buffer with its actual padding,
    which the kernel slices its window out of via ``input_pad``.
    ``origin`` is the shard's global offset under a mesh (None locally).
    """
    env: dict = {}
    outputs: dict = {}
    for call, pc in zip(calls, pc_per_call):
        padded, ipad = {}, {}
        for f in call.group_inputs:
            padded[f], actual = resolve_input(call, f, env)
            if actual is not None:
                ipad[f] = actual
        res = call(padded, svec, pc, input_pad=ipad or None, origin=origin)
        env.update(res)
        for f, v in res.items():
            if p.fields[f].role.value == "output":
                outputs[f] = v
    return outputs


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
        ext = {k: jnp.asarray(v, dtype=dtype) for k, v in fields.items()}
        bnd = p.boundaries()

        def resolve(call, f, env):
            x = env[f] if f in env else ext[f]
            return bc.pad_field(x, call.halo_lo, call.halo_hi, bnd[f],
                                align_hi=call.align_hi), None

        return _run_groups(p, calls, _scalar_vec(p, scalars),
                           _pad_coeffs(p, calls, coeffs, dtype), resolve)

    return run


def lower_time_loop(p: Program, plan: DataflowPlan, grid_shape,
                    spec: TimeLoopSpec, update):
    """Return fn(fields, scalars, coeffs) -> final fields after ``spec.steps``
    fused iterations — one compiled program, no host round trips.

    The carry of a ``lax.fori_loop`` holds one *pre-padded* persistent buffer
    per program input field, sized by ``spec.field_pad`` so every consuming
    fuse group can slice its window geometry straight out of it (the kernel's
    ``input_pad`` path).  Halo slabs follow each field's boundary: zero
    slabs never change, so writing the back buffer each step touches only
    the interior — either scattered in place (``carry_write="inplace"``) or
    rebuilt as one fused interior-plus-constant-halo write (``"repad"``,
    the default; see :class:`TimeLoopSpec`); periodic slabs are rebuilt
    from the new interior (the wraparound values change with it).  XLA
    donates the loop carry,
    giving the front/back buffer swap ``spec.double_buffer`` assigns.
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
    """
    update = adapt_update(update)
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
        svec = _scalar_vec(p, scalars)
        # coefficients never change across steps: pad per consuming group
        # once, before the loop ("small data" stays resident)
        pc_per_call = _pad_coeffs(p, calls, coeffs, dtype)
        pc_epilogue = (_pad_coeffs(p, epilogue, coeffs, dtype)
                       if epilogue is not None else None)
        # pad the persistent carry buffers exactly once
        carry = {f: refill(f, jnp.asarray(fields[f], dtype=dtype))
                 for f in spec.persistent}

        def advance(carry, calls_, pc_):
            def resolve(call, f, env):
                if f in carry:              # persistent: window from carry
                    return carry[f], fpad[f]
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
                outputs = _run_groups(p, calls_, svec, pc_, resolve)
                cur = {f: carry[f][interior[f]] for f in spec.persistent}
                new = dict(cur)
                new.update(update(cur, outputs, scalars))
            out = {}
            for f in spec.persistent:
                if spec.carry_write == "inplace" and bnd[f] == "zero":
                    # zero halos never change: scatter the interior only
                    out[f] = carry[f].at[interior[f]].set(
                        jnp.asarray(new[f], dtype=dtype))
                else:
                    # one fused interior write + constant (zero) or
                    # refreshed (wraparound) halo slabs — no carry RMW
                    out[f] = refill(f, jnp.asarray(new[f], dtype=dtype))
            return out

        def body(_, carry):
            return advance(carry, calls, pc_per_call)

        carry = jax.lax.fori_loop(0, outer, body, carry)
        if epilogue is not None and int(spec.steps) % chain:
            carry = advance(carry, epilogue, pc_epilogue)
        return {f: carry[f][interior[f]] for f in spec.persistent}

    return run
