"""Shape bucketing for the serving layer — exact execution on padded grids.

The engine compiles each program once per *bucket* (a lane-quantised grid
shape) and runs every request whose grid rounds up to that bucket through
the same compiled executor.  Correctness does not come from masking the
final answer — ghost cells would contaminate the interior one halo per
fused step — but from an invariant maintained jointly by three pieces:

1. **Placement** (:func:`repro.core.schedule.bucket_for`): the real grid
   ``G`` sits at offset ``off = lo`` (the program's low reach) inside a
   bucket ``B >= G + lo + hi``, so no read issued *for an in-domain cell*
   ever crosses the bucket edge.  The compiled program's own boundary
   handling at bucket edges is therefore never observed by real cells.
2. **Embedding** (:func:`embed_field` / :func:`embed_coeff`): on request
   ingress every bucket cell — not just the reach ring — is filled with the
   value the real boundary dictates along each axis (0, or the wrap of the
   interior).
3. **Refresh** (:func:`make_refresh`, installed by :func:`wrap_update`):
   after every fused step the out-of-domain cells are rewritten from the
   new interior, restoring the embedding before the next step reads it.

Real grid sizes enter the compiled graph as *traced* scalar arguments
(``_srv_n0`` … appended to ``p.scalars`` by :func:`serving_program`), so
every grid that rounds to the same bucket shares one trace — the engine's
zero-retrace guarantee for warm requests — and the sizes can differ per
batch element under ``vmap``.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core import boundary as bc
from ..core.ir import Program
from ..core.schedule import BucketSpec, adapt_update, bucket_for  # noqa: F401

SIZE_SCALAR_PREFIX = "_srv_n"


def size_scalar_names(ndim: int) -> list:
    return [f"{SIZE_SCALAR_PREFIX}{a}" for a in range(ndim)]


def serving_program(p: Program) -> Program:
    """A copy of ``p`` with per-axis grid-size scalars appended.

    Appending (never inserting) keeps existing scalar indices stable for
    the Pallas backend's packed scalar vector.  Idempotent: a program that
    already carries the size scalars is returned unchanged.
    """
    names = size_scalar_names(p.ndim)
    if all(n in p.scalars for n in names):
        return p
    clash = [n for n in p.scalars if n.startswith(SIZE_SCALAR_PREFIX)]
    if clash:
        raise ValueError(f"program scalars {clash} collide with the "
                         f"serving size-scalar prefix {SIZE_SCALAR_PREFIX!r}")
    sp = Program(name=p.name, ndim=p.ndim, fields=dict(p.fields),
                 scalars=list(p.scalars) + names, ops=list(p.ops),
                 coeffs=dict(p.coeffs))
    sp.validate()
    return sp


def size_scalars(spec: BucketSpec) -> dict:
    return {f"{SIZE_SCALAR_PREFIX}{a}": float(g)
            for a, g in enumerate(spec.grid)}


# --------------------------------------------------------------------------
# Host-side embed / crop (request ingress and egress)
# --------------------------------------------------------------------------


def embed_field(x, spec: BucketSpec, boundary) -> np.ndarray:
    """Place a real-grid array into its bucket, filling every out-of-domain
    cell per the field's boundary, axis by axis: the wrap of ``x`` along
    its periodic axes first, then zeros along its zero axes."""
    x = np.asarray(x)
    if tuple(x.shape) != tuple(spec.grid):
        raise ValueError(f"field shape {x.shape} != request grid {spec.grid}")
    kinds = bc.per_axis(boundary, x.ndim)
    for a, (g, b, o) in enumerate(zip(spec.grid, spec.bucket, spec.offset)):
        if kinds[a] == "periodic":
            x = np.take(x, (np.arange(b) - o) % g, axis=a)
    if "zero" not in kinds:
        return x
    out = np.zeros(spec.bucket, dtype=x.dtype)
    out[tuple(slice(None) if k == "periodic" else s
              for k, s in zip(kinds, spec.interior()))] = x
    return out


def embed_coeff(c, axis: int, spec: BucketSpec, mode: str) -> np.ndarray:
    """Extend a per-axis coefficient array to bucket length.

    ``mode`` must match :func:`repro.core.boundary.coeff_mode` for the
    program and ``axis`` so the embedded values agree with what the
    exact-grid compile would read through its shifted-coefficient path.
    """
    c = np.asarray(c)
    g, b, o = spec.grid[axis], spec.bucket[axis], spec.offset[axis]
    if c.shape != (g,):
        raise ValueError(f"coeff shape {c.shape} != ({g},) on axis {axis}")
    if mode == "periodic":
        return c[(np.arange(b) - o) % g]
    out = np.zeros(b, dtype=c.dtype)
    out[o:o + g] = c
    return out


def crop(x, spec: BucketSpec):
    """Slice the real-grid interior back out of a bucket-shaped array."""
    return x[spec.interior()]


def embed_request(p: Program, spec: BucketSpec, fields, scalars=None,
                  coeffs=None):
    """Embed one request's arrays and attach the traced size scalars.

    Returns (fields, scalars, coeffs) dicts shaped for the bucket compile.
    """
    bnd = p.boundaries()
    efields = {f: embed_field(x, spec, bnd[f]) for f, x in fields.items()}
    escalars = dict(scalars or {})
    escalars.update(size_scalars(spec))
    ecoeffs = {c: embed_coeff(x, p.coeffs[c], spec,
                              bc.coeff_mode(p, p.coeffs[c]))
               for c, x in (coeffs or {}).items()}
    return efields, escalars, ecoeffs


# --------------------------------------------------------------------------
# Device-side refresh (re-establish the embedding after each fused step)
# --------------------------------------------------------------------------


def make_refresh(p: Program, spec: BucketSpec):
    """Build ``refresh(fields, scalars) -> fields`` rewriting out-of-domain
    bucket cells from the (possibly traced, per-request) grid sizes.

    Along a field's periodic axes the refresh gathers
    ``x[off + (i - off) mod n]``; along its zero axes it masks cells
    outside ``[off, off + n)``.  Sizes come from the ``_srv_n*`` scalars so
    the gather/mask shapes are static (bucket-sized) while the wrap length
    is traced — one trace covers every grid in the bucket, and ``vmap``
    batches requests with different sizes.

    Under ``shard_map`` the refresh sees *local* shards; ``origin`` (the
    shard's global offset vector) shifts the zero-axis masks into global
    coordinates.  The periodic gather is a whole-axis permutation with no
    shard-local form, so a field periodic along a sharded axis (one whose
    local extent is less than the bucket's) is rejected.
    """
    kinds = p.axis_boundaries()
    names = size_scalar_names(p.ndim)
    offs = tuple(int(o) for o in spec.offset)
    bucket = tuple(int(b) for b in spec.bucket)

    def refresh(fields, scalars, origin=None):
        ns = [jnp.asarray(scalars[nm]).astype(jnp.int32) for nm in names]
        out = {}
        for f, x in fields.items():
            kind = kinds.get(f, ("zero",) * p.ndim)
            for a in range(p.ndim):
                if kind[a] == "periodic":
                    if x.shape[a] != bucket[a]:
                        raise NotImplementedError(
                            f"field {f!r} is periodic along sharded axis "
                            f"{a}: the bucket refresh is a whole-axis torus "
                            "gather with no shard-local form; serve "
                            "periodic fused loops unsharded along it")
                    idx = offs[a] + (jnp.arange(bucket[a]) - offs[a]) % ns[a]
                    x = jnp.take(x, idx, axis=a)
            for a in range(p.ndim):
                if kind[a] == "zero":
                    i = jnp.arange(x.shape[a])
                    if origin is not None:
                        i = i + origin[a]
                    inb = (i >= offs[a]) & (i < offs[a] + ns[a])
                    shape = [1] * p.ndim
                    shape[a] = x.shape[a]
                    x = jnp.where(inb.reshape(shape), x, 0)
            out[f] = x
        return out

    return refresh


def wrap_update(p: Program, spec: BucketSpec, update, trace_counter=None):
    """Wrap a user update rule for bucketed fused-loop execution.

    The wrapped rule runs the user's update on the bucket-shaped fields,
    then refreshes the out-of-domain cells so step ``t+1`` reads the same
    embedding step ``t`` did.  ``trace_counter`` (a one-element list) is
    bumped at trace time — the engine's re-trace instrumentation.
    """
    user = adapt_update(update)
    refresh = make_refresh(p, spec)

    def wrapped(fields, outputs, scalars, origin=None):
        if trace_counter is not None:
            trace_counter[0] += 1
        new = dict(fields)
        new.update(user(fields, outputs, scalars))
        return refresh(new, scalars, origin)

    wrapped._takes_scalars = True
    # sharded time loops feed the shard's global offset so the refresh
    # masks in global coordinates
    wrapped._takes_origin = True
    # the refresh gathers across whole bucket axes — there is no plane-local
    # form, so stream compiles must not chain this update into the kernel
    wrapped._plane_local = False
    return wrapped
