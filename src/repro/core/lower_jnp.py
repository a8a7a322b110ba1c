"""jnp backends — the Von-Neumann reference lowerings.

Two variants, playing the roles of the paper's baselines:

* ``naive`` — each op traverses full arrays independently, every access is a
  fresh zero-padded shift (the role of unoptimised Vitis HLS / -O0: correct
  by construction, no reuse structure).
* ``fused`` — ops evaluated with one shared memo across the whole program,
  so repeated subtrees and repeated accesses evaluate once and XLA fuses the
  elementwise graph (the role DaCe plays in the paper: an optimising but
  non-stencil-specialised pipeline).

Both are also the *oracles* against which the Pallas backend is verified.
SSA discipline (every field written exactly once, enforced by the builder)
makes the shared memo sound: an Access never goes stale.
"""

from __future__ import annotations

from typing import Mapping

import jax.numpy as jnp

from . import boundary as bc
from .expr_eval import evaluate
from .ir import Access, FieldRole, Program


def lower(p: Program, mode: str = "fused", prepad: Mapping | None = None,
          shift_fn=None, coeff_fn=None):
    """Return fn(fields, scalars) -> dict of output arrays.

    With ``prepad`` (field name -> (ndim, 2) halo widths) the external input
    fields must arrive *already padded* by those amounts (halo slabs filled
    per the field's boundary by the caller); every Access then resolves to a
    static slice of the persistent padded buffer instead of a fresh pad —
    the access path the fused time loop uses for its carry-resident fields.
    Temps produced mid-program stay interior-shaped and keep the
    shift-on-access path, which honours each field's declared boundary
    (zero extension or torus wraparound).

    ``shift_fn(x, offset, boundary)`` overrides the shift-on-access path and
    ``coeff_fn(cref, coeffs)`` the coefficient read — the hooks the
    distributed executor uses to route accesses through ``ppermute`` and to
    slice replicated coefficient arrays at the shard origin.
    """
    if mode not in ("naive", "fused"):
        raise ValueError(mode)
    prepadded = set(prepad or {})
    bnd = p.boundaries()
    shift = shift_fn or bc.shift_field

    def run(fields: Mapping[str, jnp.ndarray],
            scalars: Mapping[str, jnp.ndarray] | None = None,
            coeffs: Mapping[str, jnp.ndarray] | None = None):
        scalars = scalars or {}
        coeffs = coeffs or {}
        env = dict(fields)
        outputs = {}
        shared_memo: dict = {}
        any_field = next(iter(fields.values()))
        if prepad is None:
            interior = any_field.shape
        else:
            fref = next(f for f in fields if f in prepadded)
            h = prepad[fref]
            interior = tuple(fields[fref].shape[ax]
                             - int(h[ax, 0]) - int(h[ax, 1])
                             for ax in range(p.ndim))

        def coeff(c):
            if coeff_fn is not None:
                return coeff_fn(c, coeffs)
            ax = p.coeffs[c.coeff]
            v = bc.shift_field(coeffs[c.coeff], (c.offset,),
                               bc.coeff_mode(p, ax))
            shape = [1] * p.ndim
            shape[ax] = v.shape[0]
            return v.reshape(shape)

        for op in p.ops:
            memo = shared_memo if mode == "fused" else {}

            def access(a: Access):
                if a.field in prepadded:
                    h = prepad[a.field]
                    sl = tuple(slice(int(h[ax, 0]) + int(a.offset[ax]),
                                     int(h[ax, 0]) + int(a.offset[ax])
                                     + interior[ax])
                               for ax in range(p.ndim))
                    return env[a.field][sl]
                return shift(env[a.field], a.offset, bnd[a.field])

            res = evaluate(op.expr, access, lambda n: scalars[n], memo,
                           coeff=coeff)
            res = jnp.broadcast_to(res, interior)
            env[op.out] = res
            if p.fields[op.out].role == FieldRole.OUTPUT:
                outputs[op.out] = res
        return outputs

    return run


def lower_time_loop(p: Program, mode: str, spec, update):
    """Return fn(fields, scalars, coeffs) -> final fields after
    ``spec.steps`` fused iterations (single compiled program).

    Mirrors the Pallas fused loop: the ``lax.fori_loop`` carry holds the
    persistent input fields pre-padded by ``spec.field_pad``; every step the
    step body reads windows out of the carry (static slices, no ``jnp.pad``)
    and the traced ``update(fields, outputs)`` writes the new interiors back
    in place.  Halo slabs follow each field's boundary: zero slabs stay
    zero throughout; a field periodic along any axis is re-padded from the
    new interior every step (the wraparound values change with it).
    """
    import jax

    from .schedule import adapt_update

    update = adapt_update(update)
    fpad = spec.field_pad
    bnd = p.boundaries()
    step_fn = lower(p, mode, prepad=fpad)

    def run(fields: Mapping, scalars: Mapping | None = None,
            coeffs: Mapping | None = None):
        scalars = dict(scalars or {})
        coeffs = dict(coeffs or {})
        ndim = p.ndim
        shape = next(iter(fields.values())).shape
        interior = {f: tuple(slice(int(fpad[f][a, 0]),
                                   int(fpad[f][a, 0]) + shape[a])
                             for a in range(ndim))
                    for f in spec.persistent}

        def refill(f, x):
            return bc.pad_field(x, fpad[f][:, 0], fpad[f][:, 1], bnd[f])

        carry = {f: refill(f, jnp.asarray(fields[f]))
                 for f in spec.persistent}

        def body(_, carry):
            outs = step_fn(carry, scalars, coeffs)
            cur = {f: carry[f][interior[f]] for f in spec.persistent}
            new = dict(cur)
            new.update(update(cur, outs, scalars))
            out = {}
            for f in spec.persistent:
                if spec.carry_write == "inplace" and bnd[f] == "zero":
                    # zero halos on every axis never change: scatter the
                    # interior only
                    out[f] = carry[f].at[interior[f]].set(new[f])
                else:
                    # one fused interior write + constant (zero) or
                    # refreshed (wraparound) halo slabs
                    out[f] = refill(f, new[f])
            return out

        carry = jax.lax.fori_loop(0, spec.steps, body, carry)
        return {f: carry[f][interior[f]] for f in spec.persistent}

    return run
