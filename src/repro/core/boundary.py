"""Boundary-condition subsystem: one place that knows how halos are filled.

Every layer of the pipeline needs the same decision — what does an access
outside the domain read? — and before this module each backend hard-coded
the zero-halo convention.  Boundaries are declared per field on the IR
(:class:`~repro.core.ir.FieldDecl.boundary`), one kind per axis, and the
helpers here realise them uniformly:

* ``"zero"``      out-of-domain reads along the axis return 0 (the IR's
                  historical convention; ``jnp.pad`` zero slabs, partial
                  ``ppermute`` rings that leave edge shards zero-filled).
* ``"periodic"``  the axis wraps around (``jnp.roll`` / wrap-slices on a
                  single device, full-ring ``ppermute`` permutations across
                  a mesh).

A boundary is a per-axis tuple of kinds; a bare kind stands for every axis
(``"periodic"`` is the torus), and :func:`per_axis` turns one into the
other.  A field declared ``("periodic", "zero", "zero")`` is NEMO's
east-west cyclic domain, closed north-south and at the bottom;
``("periodic", "periodic", "zero")`` is an LES domain, doubly periodic
over a bounded vertical.  Where a read lies outside along several axes,
the wrap axes resolve first and the zero axes then read 0: a corner that
lies outside along any zero axis reads 0.

The same helpers serve the jnp lowerings (:func:`shift_field`), the Pallas
orchestrators (:func:`pad_field` builds carry/window buffers), the
distributed executor (:func:`ring_perms` builds the exchange permutation
of each axis), and the coefficient path (:func:`pad_coeff`), so a program
runs the same domain on all backends and any mesh.

Mixing boundaries inside one program is allowed with one validated rule,
per axis (:func:`validate_boundaries`): an op whose output is periodic
along an axis may only read fields periodic along that axis.  Without the
rule, overlapped-tiling recompute in fused Pallas groups could not
reproduce the wraparound value of a periodic temp built from
zero-extended inputs, and backends would disagree at the edges.  A 1-D
coefficient along axis ``c`` wraps only where every field is periodic
along ``c`` (:func:`coeff_mode`) and zero-extends otherwise, so an op
periodic along ``c`` may read it only then.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from .. import obs

BOUNDARIES = ("zero", "periodic")


def per_axis(boundary, ndim: int) -> tuple:
    """The per-axis tuple of kinds of ``boundary``: a bare kind stands for
    every axis, a sequence gives one kind per axis."""
    if isinstance(boundary, str):
        kinds = (boundary,) * ndim
    else:
        kinds = tuple(boundary)
        if len(kinds) != ndim:
            raise ValueError(f"boundary {boundary!r} gives {len(kinds)} "
                             f"kinds for {ndim} axes")
    for k in kinds:
        if k not in BOUNDARIES:
            raise ValueError(f"unknown boundary {k!r}; valid: "
                             + ", ".join(repr(b) for b in BOUNDARIES))
    return kinds


def compact(boundary):
    """The declared form of ``boundary``: the bare kind where every axis
    has the same one, else the per-axis tuple.  Equal domains then declare
    (and fingerprint) alike."""
    if isinstance(boundary, str):
        return boundary
    kinds = tuple(boundary)
    return kinds[0] if kinds and len(set(kinds)) == 1 else kinds


def validate_boundaries(p) -> None:
    """IR-level boundary checks (called from ``Program.validate``)."""
    kinds = {}
    for n, f in p.fields.items():
        try:
            kinds[n] = per_axis(f.boundary, p.ndim)
        except ValueError as e:
            raise ValueError(f"field {n!r}: {e}") from None
    for op in p.ops:
        out = kinds[op.out]
        for a in op.accesses():
            for ax in range(p.ndim):
                if out[ax] == "periodic" and kinds[a.field][ax] != "periodic":
                    raise ValueError(
                        f"op {op.name or op.out!r} produces {op.out!r}, "
                        f"periodic along axis {ax}, but reads {a.field!r}, "
                        f"zero-boundary along it; a periodic field's "
                        "wraparound values cannot be recomputed from "
                        "zero-extended inputs")
        for c in op.coeff_refs():
            ax = p.coeffs[c.coeff]
            if out[ax] == "periodic" and coeff_mode(p, ax) != "periodic":
                raise ValueError(
                    f"op {op.name or op.out!r} produces {op.out!r}, periodic "
                    f"along axis {ax}, and reads coefficient {c.coeff!r} "
                    "along it, but a coefficient wraps only where every "
                    "field is periodic along its axis (a torus along it)")


def coeff_mode(p, axis: int) -> str:
    """How 1-D coefficient arrays along ``axis`` extend beyond the domain:
    they wrap only where every field is periodic along ``axis``, and
    zero-extend otherwise."""
    return ("periodic" if all(per_axis(f.boundary, p.ndim)[axis] == "periodic"
                              for f in p.fields.values()) else "zero")


def pad_field(x: jnp.ndarray, lo: Sequence[int], hi: Sequence[int],
              boundary, align_hi: Sequence[int] | None = None
              ) -> jnp.ndarray:
    """Pad ``x`` with halo slabs per ``boundary`` plus a zero alignment slab.

    ``lo``/``hi`` are the per-axis halo widths; ``align_hi`` (optional) is
    extra hi-side tile-alignment padding, always zero-filled — alignment
    positions are never read by in-domain consumers, only cropped or
    masked, so they need no wraparound values.  Periodic axes are filled
    first, by slices and concatenations tagged ``wrap``
    (:func:`repro.obs.phase`), then zero axes by one ``jnp.pad``, so a
    corner outside along a zero axis reads 0.
    """
    ndim = x.ndim
    kinds = per_axis(boundary, ndim)
    align_hi = tuple(align_hi) if align_hi is not None else (0,) * ndim
    for ax in range(ndim):
        l, h, al = int(lo[ax]), int(hi[ax]), int(align_hi[ax])
        if kinds[ax] != "periodic" or (l == 0 and h == 0 and al == 0):
            continue
        n = x.shape[ax]
        if l > n or h > n:
            raise ValueError(
                f"periodic halo ({l},{h}) exceeds extent {n} on axis {ax}")
        with obs.phase("wrap"):
            pieces = []
            if l:
                pieces.append(jax.lax.slice_in_dim(x, n - l, n, axis=ax))
            pieces.append(x)
            if h:
                pieces.append(jax.lax.slice_in_dim(x, 0, h, axis=ax))
            if al:
                shp = list(x.shape)
                shp[ax] = al
                pieces.append(jnp.zeros(shp, x.dtype))
            x = jnp.concatenate(pieces, axis=ax)
    if "zero" not in kinds:
        return x
    pads = [(0, 0) if kinds[a] == "periodic"
            else (int(lo[a]), int(hi[a]) + int(align_hi[a]))
            for a in range(ndim)]
    return jnp.pad(x, pads)


def shift_field(x: jnp.ndarray, offset: Sequence[int], boundary
                ) -> jnp.ndarray:
    """``out[i] = x[i + offset]`` with out-of-domain reads per ``boundary``:
    periodic axes roll, then zero axes read 0 outside."""
    offset = tuple(int(o) for o in offset)
    if all(o == 0 for o in offset):
        return x
    kinds = per_axis(boundary, x.ndim)
    axes = tuple(ax for ax, o in enumerate(offset)
                 if o != 0 and kinds[ax] == "periodic")
    if axes:
        x = jnp.roll(x, shift=tuple(-offset[ax] for ax in axes), axis=axes)
        offset = tuple(0 if ax in axes else o for ax, o in enumerate(offset))
        if all(o == 0 for o in offset):
            return x
    h = max(abs(o) for o in offset)
    xp = jnp.pad(x, h)
    idx = tuple(slice(h + offset[ax], h + offset[ax] + x.shape[ax])
                for ax in range(x.ndim))
    return xp[idx]


def pad_coeff(c: jnp.ndarray, lo: int, hi: int, mode: str) -> jnp.ndarray:
    """Extend a replicated 1-D coefficient array by (lo, hi) per ``mode``.

    The wrap path gathers modular indices, so it stays correct even when
    the tile-alignment slab makes ``hi`` comparable to the array length.
    """
    lo, hi = int(lo), int(hi)
    if lo == 0 and hi == 0:
        return c
    if mode == "zero":
        return jnp.pad(c, (lo, hi))
    if mode != "periodic":
        raise ValueError(f"unknown boundary {mode!r}")
    n = c.shape[0]
    return c[jnp.arange(-lo, n + hi) % n]


def ring_perms(n: int, direction: int, periodic: bool) -> list:
    """``ppermute`` permutation shifting data by one shard.

    ``direction=+1`` sends each shard's slab to its right neighbour (fills
    *lo* halos), ``-1`` to its left (fills *hi* halos).  ``periodic`` is
    the kind of the axis the mesh axis shards: periodic closes the ring;
    zero leaves the edge shard unreceiving, which ``ppermute`` zero-fills —
    exactly the zero-halo convention at the global edge.
    """
    if direction not in (1, -1):
        raise ValueError(f"direction must be +1/-1, got {direction}")
    if periodic:
        return [(i, (i + direction) % n) for i in range(n)]
    if direction == 1:
        return [(i, i + 1) for i in range(n - 1)]
    return [(i + 1, i) for i in range(n - 1)]
