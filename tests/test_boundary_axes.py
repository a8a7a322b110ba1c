"""Per-axis boundaries: one kind per (field, axis).

Invariants:
* a per-axis boundary = ``np.roll`` along its periodic axes, then a zero
  pad along its zero axes (a corner outside along a zero axis reads 0);
* the validation rule holds axis by axis, coefficients included;
* NEMO's east-west cyclic tracer domain and MONC's doubly periodic LES
  domain agree across the jnp, block and stream lowerings and a mesh, and
  the fused loop agrees with the host loop and with the cyclic plain
  reference of the benchmark, which the all-zero program does not;
* serving, the fingerprint, the ``wrap`` phase tag and the compile
  counters see the per-axis form.
"""

import importlib.util
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import compile_program, program_fingerprint, run_time_loop
from repro.core import boundary as bc
from repro.core.frontend import ProgramBuilder
from repro.core.schedule import bucket_for
from repro.obs import global_metrics
from repro.serve import StencilEngine, StencilRequest, embed_field

from test_boundary import pw_data, tracer_data

ROOT = Path(__file__).resolve().parents[1]
CYCLIC = ["periodic", "zero", "zero"]          # NEMO jperio = 1
LATERAL = ["periodic", "periodic", "zero"]     # MONC-style LES

KINDS = [CYCLIC, LATERAL, ["zero", "periodic", "zero"],
         ["zero", "zero", "periodic"], "zero", "periodic"]


def numpy_shift(x, offset, boundary):
    """``out[i] = x[i + offset]``: np.roll on wrap axes, then zero pad."""
    kinds = bc.per_axis(boundary, x.ndim)
    for ax, (o, k) in enumerate(zip(offset, kinds)):
        if k == "periodic":
            x = np.roll(x, -o, axis=ax)
    h = max([abs(o) for o in offset] + [0])
    xp = np.pad(x, [(h, h) if k == "zero" else (0, 0) for k in kinds])
    return xp[tuple(slice(h + o, h + o + n) if k == "zero" else slice(None)
                    for o, n, k in zip(offset, x.shape, kinds))]


def numpy_pad(x, lo, hi, boundary, align):
    kinds = bc.per_axis(boundary, x.ndim)
    for ax, k in enumerate(kinds):
        if k == "periodic":
            idx = np.arange(-lo[ax], x.shape[ax] + hi[ax]) % x.shape[ax]
            x = np.take(x, idx, axis=ax)
            x = np.pad(x, [(0, align[ax]) if a == ax else (0, 0)
                           for a in range(x.ndim)])
    return np.pad(x, [(lo[a], hi[a] + align[a]) if k == "zero" else (0, 0)
                      for a, k in enumerate(kinds)])


# ------------------------------------------------ helpers against numpy

@pytest.mark.parametrize("boundary", KINDS, ids=str)
@pytest.mark.parametrize("offset", [(1, 0, 0), (-1, 1, 0), (1, -2, 1),
                                    (-2, 1, -1)], ids=str)
def test_shift_field_per_axis_matches_numpy(boundary, offset):
    x = np.random.default_rng(0).normal(size=(5, 6, 7)).astype(np.float32)
    got = np.asarray(bc.shift_field(x, offset, boundary))
    np.testing.assert_array_equal(got, numpy_shift(x, offset, boundary))


@pytest.mark.parametrize("boundary", KINDS, ids=str)
def test_pad_field_per_axis_matches_numpy(boundary):
    x = np.random.default_rng(1).normal(size=(5, 6, 7)).astype(np.float32)
    lo, hi, align = (2, 1, 1), (1, 2, 0), (3, 0, 1)
    got = np.asarray(bc.pad_field(x, lo, hi, boundary, align_hi=align))
    want = numpy_pad(x, lo, hi, boundary, align)
    np.testing.assert_array_equal(got, want)
    if "zero" in bc.per_axis(boundary, 3) and "periodic" in \
            bc.per_axis(boundary, 3):
        # a corner outside along a zero axis reads 0 even where the other
        # axis wraps
        z = bc.per_axis(boundary, 3).index("zero")
        corner = [slice(0, lo[a]) for a in range(3)]
        corner[z] = slice(0, lo[z])
        assert not got[tuple(corner)].any()


def test_per_axis_and_compact_round_trip():
    assert bc.per_axis("zero", 3) == ("zero",) * 3
    assert bc.per_axis(CYCLIC, 3) == tuple(CYCLIC)
    assert bc.compact(["zero"] * 3) == "zero"
    assert bc.compact(CYCLIC) == tuple(CYCLIC)
    with pytest.raises(ValueError, match="2 kinds for 3 axes"):
        bc.per_axis(["zero", "zero"], 3)
    with pytest.raises(ValueError, match="unknown boundary"):
        bc.per_axis(["zero", "reflect", "zero"], 3)


# ------------------------------------------------ validation, per axis

def test_periodic_along_axis_rejects_zero_input_along_it():
    b = ProgramBuilder("bad", ndim=2)
    x = b.input("x", boundary=["zero", "periodic"])
    o = b.output("o", boundary=["periodic", "zero"])
    b.define(o, x[1, 0] + x[-1, 0])
    with pytest.raises(ValueError, match="periodic along axis 0"):
        b.build()


def test_lateral_periodic_coefficient_on_its_zero_axis_accepted():
    """MONC's LES domain: the per-level coefficients lie on the bounded
    vertical, so they zero-extend and every op may read them."""
    p = pw_advection(boundary=LATERAL)
    assert not p.is_torus()
    assert [bc.coeff_mode(p, ax) for ax in range(3)] == [
        "periodic", "periodic", "zero"]
    b = ProgramBuilder("coef", ndim=2, boundary=["zero", "periodic"])
    x = b.input("x")
    o = b.output("o")
    c = b.coeff("c", axis=1)
    b.define(o, x[0, 1] * c[0])
    assert b.build().boundaries()["o"] == ("zero", "periodic")
    b = ProgramBuilder("coef0", ndim=2, boundary=["zero", "periodic"])
    x = b.input("x")
    o = b.output("o", boundary=["zero", "zero"])
    b.input("y", boundary="zero")   # axis 1 no longer wraps everywhere
    c = b.coeff("c", axis=1)
    b.define(o, x[0, 1] * c[0])
    b.build()                       # a zero output may read it
    b = ProgramBuilder("coef1", ndim=2, boundary=["zero", "periodic"])
    x = b.input("x")
    o = b.output("o")
    b.input("y", boundary="zero")
    c = b.coeff("c", axis=1)
    b.define(o, x[0, 1] * c[0])
    with pytest.raises(ValueError, match="torus along it"):
        b.build()


# ------------------------------------------------ backends agree

CASES = {
    "tracer-cyclic": (tracer_advection, CYCLIC, (8, 8, 64), tracer_data,
                      tracer_advection_update),
    "pw-lateral": (pw_advection, LATERAL, (8, 8, 64), pw_data,
                   lambda: pw_advection_update(0.1)),
}
LOWERINGS = {
    "jnp_fused": dict(backend="jnp_fused"),
    "block": dict(backend="pallas"),
    "block-fused": dict(backend="pallas", strategy="fused"),
    "stream": dict(backend="pallas", schedule="stream"),
}


@pytest.mark.parametrize("lowering", list(LOWERINGS))
@pytest.mark.parametrize("case", list(CASES))
def test_per_axis_backend_parity(case, lowering):
    prog, boundary, grid, data, _ = CASES[case]
    p = prog(boundary=boundary)
    fields, scalars, coeffs = data(grid)
    ref = compile_program(p, grid, backend="jnp_naive")(fields, scalars,
                                                        coeffs)
    out = compile_program(p, grid, **LOWERINGS[lowering])(fields, scalars,
                                                         coeffs)
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)


@pytest.mark.parametrize("lowering", list(LOWERINGS))
@pytest.mark.parametrize("case", list(CASES))
def test_per_axis_fused_loop_matches_host_loop(case, lowering):
    prog, boundary, grid, data, make_update = CASES[case]
    p = prog(boundary=boundary)
    fields, scalars, coeffs = data(grid)
    update = make_update()
    want = run_time_loop(compile_program(p, grid, backend="jnp_naive"),
                         dict(fields), scalars, coeffs, 3, update)
    got = compile_program(p, grid, steps=3, update=update,
                          **LOWERINGS[lowering])(fields, scalars, coeffs)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=1e-5, rtol=1e-5, err_msg=k)


def test_per_axis_boundary_override_matches_declared():
    grid = (8, 8, 64)
    fields, scalars, coeffs = tracer_data(grid)
    a = compile_program(tracer_advection(boundary=CYCLIC), grid,
                        backend="jnp_fused")(fields, scalars, coeffs)
    b = compile_program(tracer_advection(), grid, backend="jnp_fused",
                        boundary=CYCLIC)(fields, scalars, coeffs)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


# ------------------------------------------------ the benchmark's reference

def _load_reference():
    path = ROOT / "bench" / "configs" / "tracer_advection_cyclic.py"
    spec = importlib.util.spec_from_file_location("cyclic_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _change_err(start, got, ref):
    """The benchmark's ``change_err`` for the tracer: error as a share of
    the change the reference made."""
    den = np.max(np.abs(np.asarray(ref) - np.asarray(start)))
    return float(np.max(np.abs(np.asarray(got) - np.asarray(ref))) / den)


def test_cyclic_fused_loop_matches_plain_reference():
    grid, steps = (16, 16, 128), 6
    ref_mod = _load_reference()
    fields, scalars, coeffs = tracer_data(grid)
    update = tracer_advection_update()
    ref = ref_mod.run(fields, scalars, coeffs, steps, [])
    got = compile_program(tracer_advection(boundary=CYCLIC), grid,
                          steps=steps, update=update)(fields, scalars,
                                                      coeffs)
    assert _change_err(fields["t"], got["t"], ref["t"]) <= 1e-4
    zero = compile_program(tracer_advection(), grid, steps=steps,
                           update=update)(fields, scalars, coeffs)
    assert _change_err(fields["t"], zero["t"], ref["t"]) > 1e-2


# ------------------------------------------------ a 2x2 mesh

MESH_SCRIPT = r"""
import numpy as np, jax
from repro.apps import tracer_advection, tracer_advection_update
from repro.core import compile_program, make_auto_mesh
assert jax.device_count() == 4
from test_boundary import tracer_data
grid = (8, 8, 64)
fields, scalars, coeffs = tracer_data(grid)
p = tracer_advection(boundary=["periodic", "zero", "zero"])
mesh = make_auto_mesh((2, 2), ("X", "Y"))
update = tracer_advection_update()
LOWERINGS = {"block": dict(backend="pallas"),
             "stream": dict(backend="pallas", schedule="stream"),
             "jnp_fused": dict(backend="jnp_fused")}
for name, opts in LOWERINGS.items():
    for steps in (None, 3):
        kw = dict(opts) if steps is None else dict(opts, steps=steps,
                                                   update=update)
        ex = compile_program(p, grid, mesh=mesh, mesh_axes=("X", "Y", None),
                             **kw)
        assert ex.shard.local_grid == (4, 4, 64)
        b = ex(fields, scalars, coeffs)
        # the same tiles, unsharded: the rounding of a kernel's arithmetic
        # may depend on its tile shape, never on the mesh
        a = compile_program(p, grid, plan=ex.plan, **kw)(
            fields, scalars, coeffs)
        for k in a:
            if name == "jnp_fused":
                # XLA fuses the sharded graph differently: as for the zero
                # boundary, the jnp lowerings match to rounding only
                np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                           atol=1e-5, rtol=1e-5)
            else:
                np.testing.assert_array_equal(np.asarray(a[k]),
                                              np.asarray(b[k]),
                                              err_msg=f"{name}/{steps}/{k}")

# served under a mesh: a field periodic only along unsharded axes is served
# and matches the unsharded compile; periodic along a sharded axis is
# rejected up front
from repro.serve import StencilEngine, StencilRequest
req = StencilRequest(program=p, fields=fields, scalars=scalars,
                     coeffs=coeffs, steps=3, update=update,
                     update_key="tracer")
want = compile_program(p, grid, backend="jnp_fused", steps=3,
                       update=update)(fields, scalars, coeffs)
with StencilEngine(window_s=0.0, mesh=mesh,
                   mesh_axes=(None, "X", "Y")) as eng:
    got = eng.run(req, timeout=300).outputs
for k in want:
    np.testing.assert_allclose(got[k], np.asarray(want[k]), atol=1e-5,
                               rtol=1e-5, err_msg=f"served/{k}")
eng = StencilEngine(mesh=mesh, mesh_axes=("X", "Y", None), autostart=False)
try:
    eng.describe(req)
    raise SystemExit("field periodic along a sharded axis not rejected")
except ValueError as e:
    assert "sharded axis" in str(e), e
print("MESH_CYCLIC_OK")
"""


def test_cyclic_on_2x2_mesh_bit_matches_unsharded_and_serves():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"),
                                         str(ROOT / "tests")])
    r = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-4000:]}"
    assert "MESH_CYCLIC_OK" in r.stdout


# ------------------------------------------------ serving

def test_embed_field_per_axis():
    spec = bucket_for(pw_advection(), (5, 6, 9))
    x = np.random.default_rng(2).normal(size=(5, 6, 9)).astype(np.float32)
    e = embed_field(x, spec, CYCLIC)
    o = spec.offset
    # wrapped along axis 0, zero along axis 1 and in the corner
    np.testing.assert_array_equal(e[o[0] - 1, o[1]:o[1] + 6, o[2]:o[2] + 9],
                                  x[-1])
    assert not e[o[0] - 1, o[1] - 1].any()
    assert not e[:, :o[1]].any()


def test_served_request_with_per_axis_boundary():
    p = pw_advection()
    grid = (6, 6, 12)
    fields, scalars, coeffs = pw_data(grid, seed=4)
    update = pw_advection_update(0.01)
    req = StencilRequest(program=p, fields=fields, scalars=scalars,
                         coeffs=coeffs, steps=3, update=update,
                         update_key="pw/dt=0.01", boundary=LATERAL)
    with StencilEngine(window_s=0.0) as eng:
        res = eng.run(req, timeout=300)
    ref = compile_program(p.with_boundary(LATERAL), grid, backend="jnp_fused",
                          steps=3, update=update)(fields, scalars, coeffs)
    for k in ref:
        np.testing.assert_allclose(res.outputs[k], np.asarray(ref[k]),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------ identity and tracing

def test_fingerprint_tells_zero_torus_and_cyclic_apart():
    prints = {str(b): program_fingerprint(tracer_advection(boundary=b))
              for b in ("zero", "periodic", CYCLIC)}
    assert len(set(prints.values())) == 3
    # a per-axis list naming one kind is that kind
    assert program_fingerprint(tracer_advection(["zero"] * 3)) == \
        prints["zero"]


@pytest.mark.parametrize("boundary,wraps", [("zero", False), (CYCLIC, True)],
                         ids=["zero", "cyclic"])
def test_wrap_tag_only_in_periodic_programs(boundary, wraps):
    grid = (16, 16, 128)
    p = tracer_advection(boundary=boundary)
    metrics = global_metrics()
    before = {k: metrics.counter(f"compile.halo_axes.{k}").value
              for k in bc.BOUNDARIES}
    ex = compile_program(p, grid, steps=3, update=tracer_advection_update())
    added = {k: metrics.counter(f"compile.halo_axes.{k}").value - before[k]
             for k in bc.BOUNDARIES}
    n_fields = len(p.fields)
    assert added == ({"zero": 2 * n_fields, "periodic": n_fields} if wraps
                     else {"zero": 3 * n_fields, "periodic": 0})
    args = ({f: jax.ShapeDtypeStruct(grid, np.float32)
             for f in p.input_fields()},
            {s: np.float32(0.1) for s in p.scalars},
            {"ztfreez": jax.ShapeDtypeStruct((grid[2],), np.float32)})
    tags = re.findall(r'repro_phase = "([a-z_]+)"',
                      ex.lower(*args).as_text())
    assert ("wrap" in tags) is wraps
    # a kernel stores the zero program's new ``t`` in its carry; the cyclic
    # program's wraps, so XLA refills it
    assert ("carry_write" in tags) is wraps
