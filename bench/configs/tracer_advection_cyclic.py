"""Plain reference of the NEMO tracer-advection kernel (PSycloneBench's
MUSCL-style predictor-corrector) on an east-west cyclic domain, written in
``jax.numpy`` from the equations.

The boundary is NEMO's ``jperio = 1`` (``l_Iperio`` in NEMO 4.2): axis 0
is the zonal ``i`` (``un`` is the velocity along it) and wraps, so column
-1 is the last column and the column past the last is column 0; axes 1
(meridional, ``vn``)
and 2 (the levels, ``wn`` and ``ztfreez``) are closed, and every read
outside the grid along them is 0, for inputs and intermediates alike. A
read outside along axis 0 and along another axis is 0. ``rdt``, ``zeps``
are scalars. One step replaces the tracer ``t`` by the corrected tracer;
velocities, ``e3t`` and the mask stay as they are. It imports nothing of
the system under test.
"""

import jax
import jax.numpy as jnp

FIELDS = ("t", "un", "vn", "wn", "e3t", "msk")
_UNIT = ((1, 0, 0), (0, 1, 0), (0, 0, 1))


def _shift(x, off):
    """``out[i] = x[i + off]``: wrapped along axis 0, 0 outside the grid
    along axes 1 and 2."""
    x = jnp.roll(x, -off[0], axis=0)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1)))
    return xp[(slice(None),) + tuple(slice(1 + o, 1 + o + n)
                                     for o, n in zip(off[1:], x.shape[1:]))]


def _fwd(x, ax):
    return _shift(x, _UNIT[ax])


def _back(x, ax):
    return _shift(x, tuple(-o for o in _UNIT[ax]))


def _limit(d, ax):
    """Minmod of a slope and its upstream neighbour."""
    dm = _back(d, ax)
    return jnp.where(d * dm > 0.0,
                     jnp.sign(d) * jnp.minimum(jnp.abs(d), jnp.abs(dm)), 0.0)


def _flux(vel, tr, slope, ax):
    """Upwind flux of ``tr`` with a limited-slope reconstruction."""
    return jnp.where(vel > 0.0,
                     vel * (_back(tr, ax) + 0.5 * _back(slope, ax)),
                     vel * (tr - 0.5 * slope))


def step(state, scalars, coeffs):
    t, un, vn, wn, e3t, msk = (state[f] for f in FIELDS)
    rdt, zeps = scalars["rdt"], scalars["zeps"]
    ztfreez = coeffs["ztfreez"][None, None, :]
    vel = (un, vn, wn)
    depth = e3t + zeps

    slopes = [_limit((_fwd(t, ax) - t) * msk, ax) for ax in range(3)]
    fluxes = [_flux(vel[ax], t, slopes[ax], ax) for ax in range(3)]
    div = [(_fwd(fluxes[ax], ax) - fluxes[ax]) / depth for ax in range(3)]
    zta1 = jnp.maximum(t - rdt * (div[0] + div[1] + div[2]), ztfreez)

    slopes2 = [_limit((_fwd(zta1, ax) - zta1) * msk, ax) for ax in range(3)]
    fluxes2 = [_flux(vel[ax], zta1, slopes2[ax], ax) for ax in range(3)]
    div2 = sum(_fwd(fluxes2[ax], ax) - fluxes2[ax] for ax in range(3)) / depth
    ta = (0.5 * (t + zta1) - 0.5 * rdt * div2) * msk
    return dict(state, t=ta)


def run(state, scalars, coeffs, steps, update_args, dtype=jnp.float32):
    """The state after ``steps`` steps, computed in ``dtype``."""
    del update_args
    cast = lambda d: {k: jnp.asarray(x, dtype) for k, x in d.items()}
    state, scalars, coeffs = cast(state), cast(scalars), cast(coeffs)
    return jax.lax.fori_loop(
        0, steps, lambda _, s: step(s, scalars, coeffs), state)
