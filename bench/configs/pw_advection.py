"""Plain reference of MONC's Piacsek-Williams advection with its
forward-Euler wind update, written in ``jax.numpy`` from the equations.

Every read outside the grid is 0 (the zero boundary). ``tzc1``..``tzd2``
are per-level coefficients along the last axis; ``tcx``, ``tcy`` scalars.
One step computes the source terms ``su, sv, sw`` and adds ``dt`` times
them to ``u, v, w``. It imports nothing of the system under test.
"""

import jax
import jax.numpy as jnp

FIELDS = ("u", "v", "w")


def _reader(x):
    """``at(di, dj, dk)[i, j, k] = x[i+di, j+dj, k+dk]``, 0 outside."""
    n0, n1, n2 = x.shape
    xp = jnp.pad(x, 1)

    def at(di, dj, dk):
        return xp[1 + di:1 + di + n0, 1 + dj:1 + dj + n1, 1 + dk:1 + dk + n2]
    return at


def step(state, scalars, coeffs, dt):
    u, v, w = (state[f] for f in FIELDS)
    U, V, W = _reader(u), _reader(v), _reader(w)
    tcx, tcy = scalars["tcx"], scalars["tcy"]
    tzc1, tzc2, tzd1, tzd2 = (coeffs[c][None, None, :]
                              for c in ("tzc1", "tzc2", "tzd1", "tzd2"))
    su = (tcx * (U(-1, 0, 0) * (u + U(-1, 0, 0)) - u * (U(1, 0, 0) + u))
          + tcy * (U(0, -1, 0) * (V(0, -1, 0) + V(1, -1, 0))
                   - u * (v + V(1, 0, 0)))
          + tzc1 * U(0, 0, -1) * (W(0, 0, -1) + W(1, 0, -1))
          - tzc2 * u * (w + W(1, 0, 0)))
    sv = (tcx * (V(-1, 0, 0) * (U(-1, 0, 0) + U(-1, 1, 0))
                 - v * (u + U(0, 1, 0)))
          + tcy * (V(0, -1, 0) * (v + V(0, -1, 0)) - v * (V(0, 1, 0) + v))
          + tzc1 * V(0, 0, -1) * (W(0, 0, -1) + W(0, 1, -1))
          - tzc2 * v * (w + W(0, 1, 0)))
    sw = (tcx * (W(-1, 0, 0) * (U(-1, 0, 0) + U(-1, 0, 1))
                 - w * (u + U(0, 0, 1)))
          + tcy * (W(0, -1, 0) * (V(0, -1, 0) + V(0, -1, 1))
                   - w * (v + V(0, 0, 1)))
          + tzd1 * W(0, 0, -1) * (w + W(0, 0, -1))
          - tzd2 * w * (W(0, 0, 1) + w))
    return {"u": u + dt * su, "v": v + dt * sv, "w": w + dt * sw}


def run(state, scalars, coeffs, steps, update_args, dtype=jnp.float32):
    """The state after ``steps`` steps, computed in ``dtype``."""
    (dt,) = update_args
    cast = lambda d: {k: jnp.asarray(x, dtype) for k, x in d.items()}
    state, scalars, coeffs = cast(state), cast(scalars), cast(coeffs)
    dt = jnp.asarray(dt, dtype)
    return jax.lax.fori_loop(
        0, steps, lambda _, s: step(s, scalars, coeffs, dt), state)
