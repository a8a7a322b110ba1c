"""Fused on-device time loop: ``compile_program(..., steps=N, update=...)``.

Invariants:
* N fused on-device iterations match N host-side ``run_time_loop``
  iterations to 1e-5 on every backend (pallas interpret, jnp_fused,
  jnp_naive), including programs with scalars and per-level coefficients.
* The whole loop is one compiled program: the user's update rule is traced
  into it exactly once regardless of N, and repeated calls hit the jit
  cache.
* The pallas loop leaves a field the rule never changes in the carry, and
  computes a changed one in the kernel that holds what it reads, where the
  rule is plane-local; the rest stays on XLA, with the same numbers.
* A block kernel that computes a field zero on every axis stores it
  straight into the padded back buffer, bit for bit what XLA's refill
  writes, for any step count; other fields keep XLA's carry write, and
  both of its styles ("repad" rebuild and "inplace" scatter) agree.
"""

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import (pw_advection, pw_advection_update, tracer_advection,
                        tracer_advection_update)
from repro.core import (compile_program, lower_pallas, plan_time_loop,
                        run_time_loop)
from repro.core import boundary as bc
from repro.core.schedule import adapt_update, auto_plan
from repro.kernels import stencil3d
from repro.obs.metrics import global_metrics
from repro.serve import bucket_for, serving_program, wrap_update
from repro.serve.bucket import embed_request

BACKENDS = ["jnp_naive", "jnp_fused", "pallas"]


def pw_data(grid, seed=0):
    rng = np.random.default_rng(seed)
    fields = {f: jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.1)
              for f in ("u", "v", "w")}
    scalars = {"tcx": jnp.float32(0.05), "tcy": jnp.float32(0.05)}
    coeffs = {c: jnp.asarray(
        np.linspace(0.9, 1.1, grid[2]).astype(np.float32))
        for c in ("tzc1", "tzc2", "tzd1", "tzd2")}
    return fields, scalars, coeffs


def tracer_data(grid, seed=1):
    rng = np.random.default_rng(seed)
    fields = {
        "t": jnp.asarray(rng.normal(size=grid).astype(np.float32) + 15.0),
        "un": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.2),
        "vn": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.2),
        "wn": jnp.asarray(rng.normal(size=grid).astype(np.float32) * 0.05),
        "e3t": jnp.asarray(
            np.abs(rng.normal(size=grid)).astype(np.float32) + 1.0),
        "msk": jnp.asarray(
            (rng.uniform(size=grid) > 0.05).astype(np.float32)),
    }
    scalars = {"rdt": jnp.float32(0.05), "zeps": jnp.float32(1e-6)}
    coeffs = {"ztfreez": jnp.asarray(np.full(grid[2], -1.8, np.float32))}
    return fields, scalars, coeffs


def check_fused(p, grid, data, update, steps, backend, atol=1e-5,
                **compile_kw):
    fields, scalars, coeffs = data
    ex = compile_program(p, grid, backend=backend, **compile_kw)
    ref = run_time_loop(ex, dict(fields), scalars, coeffs, steps, update)
    exN = compile_program(p, grid, backend=backend, steps=steps,
                          update=update, **compile_kw)
    got = exN(fields, scalars, coeffs)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(got[k]), np.asarray(ref[k]), atol=atol, rtol=atol,
            err_msg=f"{p.name}/{k} backend={backend} steps={steps}")
    return exN


# ------------------------------------------------- parity (scalars + coeffs)

@pytest.mark.parametrize("backend", BACKENDS)
def test_pw_advection_fused_matches_host_loop(backend):
    grid = (8, 8, 128)
    check_fused(pw_advection(), grid, pw_data(grid),
                pw_advection_update(0.1), steps=4, backend=backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_tracer_advection_fused_matches_host_loop(backend):
    grid = (6, 8, 64)
    check_fused(tracer_advection(), grid, tracer_data(grid),
                tracer_advection_update(), steps=3, backend=backend)


@pytest.mark.parametrize("grid", [(5, 7, 130), (9, 6, 96)])
def test_fused_loop_odd_grids_alignment(grid):
    """Non-divisible grids: the carry keeps lane-alignment padding."""
    check_fused(pw_advection(), grid, pw_data(grid),
                pw_advection_update(0.1), steps=3, backend="pallas")


@pytest.mark.parametrize("strategy", ["fused", "per_field", "auto"])
def test_fused_loop_multi_group_strategies(strategy):
    """Cross-group temps re-materialise per step inside the loop."""
    grid = (6, 8, 64)
    check_fused(tracer_advection(), grid, tracer_data(grid),
                tracer_advection_update(), steps=2, backend="pallas",
                strategy=strategy)


@pytest.mark.parametrize("carry_write", ["repad", "inplace"])
def test_fused_loop_carry_write_styles(carry_write):
    grid = (8, 8, 128)
    check_fused(pw_advection(), grid, pw_data(grid),
                pw_advection_update(0.1), steps=3, backend="pallas",
                carry_write=carry_write)


def test_steps_one_equals_single_step_plus_update():
    grid = (8, 8, 64)
    p = pw_advection()
    fields, scalars, coeffs = pw_data(grid)
    update = pw_advection_update(0.1)
    out = compile_program(p, grid, backend="jnp_fused")(fields, scalars,
                                                        coeffs)
    want = update(fields, out)
    got = compile_program(p, grid, backend="jnp_fused", steps=1,
                          update=update)(fields, scalars, coeffs)
    for k in want:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   atol=1e-6, rtol=1e-6)


# ------------------------------------------------------ single-dispatch jit

@pytest.mark.parametrize("backend", BACKENDS)
def test_update_traced_once_per_compile(backend):
    """The loop lowers into ONE jitted program: the update rule is traced
    into it exactly once for steps=5 (a host-driven loop traces/dispatches
    it per step), and a second executable call hits the jit cache (no
    retrace).  On pallas that one trace is the build-time read that
    places the updates: the kernels then run the rule's traced ops."""
    grid = (6, 6, 64)
    p = pw_advection()
    fields, scalars, coeffs = pw_data(grid)
    inner = pw_advection_update(0.1)
    traces = [0]

    def update(fl, out):
        traces[0] += 1
        return inner(fl, out)

    ex = compile_program(p, grid, backend=backend, steps=5, update=update)
    ex(fields, scalars, coeffs)
    ex(fields, scalars, coeffs)
    assert traces[0] == 1


def test_partial_update_keeps_untouched_fields():
    """An update returning a subset of fields leaves the rest unchanged."""
    grid = (6, 6, 64)
    p = tracer_advection()
    fields, scalars, coeffs = tracer_data(grid)
    exN = compile_program(p, grid, backend="jnp_fused", steps=2,
                          update=lambda fl, out: {"t": out["ta"]})
    got = exN(fields, scalars, coeffs)
    for f in ("un", "vn", "wn", "e3t", "msk"):
        np.testing.assert_array_equal(np.asarray(got[f]),
                                      np.asarray(fields[f]))


# ------------------------------------------------- where the update runs

def on_xla(rule):
    """``rule`` marked as not plane-local: every change it makes is
    computed by XLA in the loop body, as before kernels hosted updates."""
    def xla_rule(fields, outputs, scalars):
        return adapt_update(rule)(fields, outputs, scalars)
    xla_rule._takes_scalars = True
    xla_rule._plane_local = False
    return xla_rule


def pw_rolled_w(dt=0.1):
    """PW's rule with ``w`` read through a roll, which is not point-wise."""
    def update(fl, out):
        return {"u": fl["u"] + dt * out["su"], "v": fl["v"] + dt * out["sv"],
                "w": fl["w"] + dt * jnp.roll(out["sw"], 1, axis=0)}
    return update


def pw_masked_w(grid, dt=0.1):
    """PW's rule with ``w`` scaled by a grid-shaped array the rule closes
    over, which no kernel holds; ``u`` and ``v`` as PW's."""
    mask = (np.arange(np.prod(grid)).reshape(grid) % 3 > 0).astype(np.float32)

    def update(fl, out):
        return {"u": fl["u"] + dt * out["su"], "v": fl["v"] + dt * out["sv"],
                "w": fl["w"] + dt * mask * out["sw"]}
    return update


def tiled(p, grid, block):
    """Options for ``p``'s default plan at ``grid`` cut into ``block``
    tiles, so an epilogue sees less than the whole interior."""
    plan = auto_plan(p, grid, backend="pallas")
    return {"plan": dataclasses.replace(plan, block=block)}


def tracer_unheld(fl, out):
    """``un`` reads ``ta``, whose group does not hold ``un`` once the plan
    splits per field; ``msk`` reads only what that group holds."""
    return {"t": out["ta"], "un": fl["un"] + 0.01 * out["ta"],
            "msk": fl["msk"] + 0.01 * out["ta"]}


def serve_case():
    sp = serving_program(pw_advection())
    spec = bucket_for(sp, (6, 6, 20))
    fields, scalars, coeffs = pw_data(spec.grid)
    data = embed_request(sp, spec, fields, scalars, coeffs)
    return (sp, spec.bucket, data,
            wrap_update(sp, spec, pw_advection_update(0.1)), {})


G_PW, G_TR = (8, 8, 128), (6, 8, 64)
#: case -> (program, grid, data, rule, compile options), the fields
#: updated (in a kernel, kept in the carry, on XLA), and how their new
#: values reach the carry (stored by a kernel, refilled or scattered by
#: XLA, kept)
PLACEMENT_CASES = {
    "pw-zero": (lambda: (pw_advection("zero"), G_PW, pw_data(G_PW),
                         pw_advection_update(0.1), {}), (3, 0, 0),
                (3, 0, 0, 0)),
    "pw-periodic": (lambda: (pw_advection("periodic"), G_PW, pw_data(G_PW),
                             pw_advection_update(0.1), {}), (3, 0, 0),
                    (0, 3, 0, 0)),
    "tracer-zero": (lambda: (tracer_advection("zero"), G_TR,
                             tracer_data(G_TR), tracer_advection_update(),
                             {}), (1, 5, 0), (1, 0, 0, 5)),
    "tracer-periodic": (lambda: (tracer_advection("periodic"), G_TR,
                                 tracer_data(G_TR),
                                 tracer_advection_update(), {}), (1, 5, 0),
                        (0, 1, 0, 5)),
    "tracer-cyclic": (lambda: (tracer_advection(["periodic", "zero",
                                                 "zero"]), G_TR,
                               tracer_data(G_TR), tracer_advection_update(),
                               {}), (1, 5, 0), (0, 1, 0, 5)),
    "pw-not-plane-local": (lambda: (pw_advection(), G_PW, pw_data(G_PW),
                                    on_xla(pw_advection_update(0.1)), {}),
                           (0, 0, 3), (0, 3, 0, 0)),
    "serve-wrapped": (serve_case, (0, 0, 3), (0, 3, 0, 0)),
    "pw-not-pointwise": (lambda: (pw_advection(), G_PW, pw_data(G_PW),
                                  pw_rolled_w(), {}), (2, 0, 1),
                         (2, 1, 0, 0)),
    "pw-not-pointwise-inplace": (lambda: (pw_advection(), G_PW,
                                          pw_data(G_PW), pw_rolled_w(),
                                          {"carry_write": "inplace"}),
                                 (2, 0, 1), (2, 0, 1, 0)),
    "pw-closed-over-array": (lambda: (pw_advection(), G_PW, pw_data(G_PW),
                                      pw_masked_w(G_PW),
                                      tiled(pw_advection(), G_PW,
                                            (4, 4, 128))), (2, 0, 1),
                             (0, 3, 0, 0)),
    "tracer-unheld-field": (lambda: (tracer_advection(), G_TR,
                                     tracer_data(G_TR), tracer_unheld,
                                     {"strategy": "per_field"}), (2, 3, 1),
                            (1, 2, 0, 3)),
}


@pytest.mark.parametrize("case", sorted(PLACEMENT_CASES))
def test_update_placement_matches_xla_update(case):
    """The fused block loop puts each field's update in a kernel, in the
    carry or on XLA, as the rule's trace, its ``_plane_local`` flag and
    the fuse groups allow, and has the kernel store a new value zero on
    every axis in the carry where its tiles allow; the time spec and the
    compile counters say so, and the answer matches the loop that
    updates every changed field on XLA within float32 rounding."""
    make, want, want_carry = PLACEMENT_CASES[case]
    p, grid, (fields, scalars, coeffs), rule, opts = make()
    names = ([f"compile.update_fields.{k}" for k in ("kernel", "kept", "xla")]
             + [f"compile.carry_write.{k}"
                for k in ("kernel", "refill", "inplace", "kept")])
    before = [global_metrics().counter(n).value for n in names]
    ex = compile_program(p, grid, steps=3, update=rule, **opts)
    after = [global_metrics().counter(n).value for n in names]
    assert tuple(ex.time_spec.update_counts().values()) == want
    assert tuple(ex.time_spec.carry_counts().values()) == want_carry
    assert tuple(a - b for a, b in zip(after, before)) == want + want_carry
    got = ex(fields, scalars, coeffs)
    ref = compile_program(p, grid, steps=3, update=on_xla(rule),
                          **opts)(fields, scalars, coeffs)
    for f in ref:
        np.testing.assert_allclose(np.asarray(got[f]), np.asarray(ref[f]),
                                   rtol=1e-6, atol=1e-6, err_msg=f)


# ------------------------------------------ the kernel writes the carry

@contextlib.contextmanager
def refill_only():
    """Block kernels that cannot store a carry layout, so XLA refills
    every changed field, as before kernels wrote the back buffer."""
    build = stencil3d.build_group_call

    def without(*args, **kwargs):
        call = build(*args, **kwargs)
        call.writes_carry = False
        return call
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(stencil3d, "build_group_call", without)
        mp.setattr(lower_pallas, "build_group_call", without)
        yield


#: case -> (program, grid, data, rule, compile options), and the carry
#: writes (stored by a kernel, refilled, scattered, kept)
CARRY_CASES = {
    "pw": (lambda: (pw_advection(), G_PW, pw_data(G_PW),
                    pw_advection_update(0.1), {}), (3, 0, 0, 0)),
    "tracer": (lambda: (tracer_advection(), G_TR, tracer_data(G_TR),
                        tracer_advection_update(), {}), (1, 0, 0, 5)),
    # three tiles of two planes over five: the last tile's second plane
    # lies in the alignment slab
    "pw-odd-aligned": (lambda: (pw_advection(), (5, 7, 130),
                                pw_data((5, 7, 130)),
                                pw_advection_update(0.1),
                                tiled(pw_advection(), (5, 7, 130),
                                      (2, 7, 130))), (3, 0, 0, 0)),
    "tracer-per-field": (lambda: (tracer_advection(), G_TR,
                                  tracer_data(G_TR),
                                  tracer_advection_update(),
                                  {"strategy": "per_field"}), (1, 0, 0, 5)),
}


@pytest.mark.parametrize("case,steps", [
    ("pw", 1), ("pw", 3), ("pw", 4), ("pw", 7), ("pw-odd-aligned", 3),
    ("tracer", 4), ("tracer-per-field", 1)])
def test_kernel_carry_write_matches_refill(case, steps):
    """A kernel that stores its new field straight into the padded back
    buffer gives the loop's answer bit for bit, whatever the step count's
    parity (an odd one runs its last step after the two-step body).  Both
    loops run op by op, each kernel on its own as Mosaic runs it: under
    one jit the CPU backend fuses the interpreted kernels' bodies across
    steps, and may round a long chain differently at a point."""
    make, want = CARRY_CASES[case]
    p, grid, data, rule, opts = make()
    with jax.disable_jit():
        ex = compile_program(p, grid, steps=steps, update=rule, **opts)
        got = ex(*data)
        with refill_only():
            ref_ex = compile_program(p, grid, steps=steps, update=rule,
                                     **opts)
            ref = ref_ex(*data)
    assert tuple(ex.time_spec.carry_counts().values()) == want
    assert ref_ex.time_spec.carry_counts()["kernel"] == 0
    for f in ref:
        np.testing.assert_array_equal(np.asarray(got[f]), np.asarray(ref[f]),
                                      err_msg=f)


def test_carry_layout_store_keeps_unwritten_planes():
    """The block call stores its value in the back buffer's padded layout:
    the interior, a zero ring on the untiled axes, zeros on the planes
    past the grid; the axis-0 halo planes it never writes keep what the
    buffer held."""
    p = pw_advection()
    grid, block = (5, 7, 130), (2, 7, 130)
    plan = tiled(p, grid, block)["plan"]
    call = stencil3d.build_group_call(p, plan.groups[0], block, grid)
    pad = np.array([[1, 2], [1, 1], [1, 1]])    # axis 0: halo 1, slab 1
    fields, scalars, coeffs = pw_data(grid)
    padded = {f: bc.pad_field(fields[f], call.halo_lo, call.halo_hi, "zero",
                              align_hi=call.align_hi)
              for f in call.group_inputs}
    svec = jnp.asarray([scalars[s] for s in p.scalars])
    pcs = {c: bc.pad_coeff(coeffs[c], call.pad_lo[2], call.pad_hi[2], "zero")
           for c in call.group_coeffs}
    want = call(padded, svec, pcs)["su"]
    writer = call.rebuild(carry_pad={"su": pad})
    assert call.writes_carry and writer.carry_names == ("su",)
    shape = tuple(g + int(pad[a].sum()) for a, g in enumerate(grid))
    back = jnp.full(shape, 7.0, jnp.float32)
    got = np.asarray(writer(padded, svec, pcs, back={"su": back})["su"])
    expect = np.pad(np.asarray(want), [(1, 2), (1, 1), (1, 1)])
    expect[0] = 7.0                 # below the first tile: never written
    expect[-1] = 7.0                # past the last tile: never written
    np.testing.assert_array_equal(got, expect)
    with pytest.raises(ValueError, match="carry layout"):
        stencil3d.build_group_call(p, plan.groups[0], (2, 4, 130), grid,
                                   carry_pad={"su": pad})


# ------------------------------------------------------------ plan layer

def test_time_loop_spec_geometry():
    import dataclasses

    p = pw_advection()
    grid = (9, 8, 128)
    plan = dataclasses.replace(auto_plan(p, grid, backend="pallas"),
                               block=(8, 8, 128))
    spec = plan_time_loop(p, plan, grid, 7)
    assert spec.steps == 7
    assert spec.persistent == ["u", "v", "w"]
    assert set(spec.double_buffer) == {"u", "v", "w"}
    slots = [s for pair in spec.double_buffer.values() for s in pair]
    assert len(slots) == len(set(slots))  # disjoint front/back slots
    for f in spec.persistent:
        pad = spec.field_pad[f]
        assert pad.shape == (3, 2)
        assert (pad >= 0).all()
        # tile alignment: 9 -> 2x8 tiles on axis 0 pads 7 on the hi side
        assert pad[0, 1] >= 7
    # offsets place every group window inside the carry
    for offs in spec.group_offsets:
        for f, o in offs.items():
            assert all(v >= 0 for v in o)


def dead_op_program():
    """A live 1-wide stencil plus a DCE'd op reaching 4 cells up-axis-0."""
    from repro.core.frontend import ProgramBuilder
    b = ProgramBuilder("deadop", ndim=3)
    u, = b.inputs("u")
    dead = b.temp("dead")                     # produced, never consumed
    su = b.output("su")
    b.define(dead, u[4, 0, 0] * 2.0)
    b.define(su, u[-1, 0, 0] + u[1, 0, 0] - 2.0 * u[0, 0, 0])
    return b.build()


def test_dead_op_carry_padding_gated_on_backend():
    """Regression: the raw-access widening workaround is for the jnp
    lowerings (which evaluate every op, no DCE); the pallas backend only
    runs live fuse groups, so its carry must not be over-allocated for a
    dead op's reach."""
    p = dead_op_program()
    grid = (8, 8, 128)
    pallas_spec = plan_time_loop(p, auto_plan(p, grid, backend="pallas"),
                                 grid, 2)
    jnp_spec = plan_time_loop(p, auto_plan(p, grid, backend="jnp_fused"),
                              grid, 2)
    # live halo on axis 0 is 1; the dead op reads at +4
    assert pallas_spec.field_pad["u"][0, 1] == 1
    assert jnp_spec.field_pad["u"][0, 1] == 4


@pytest.mark.parametrize("backend", BACKENDS)
def test_dead_op_fused_loop_parity(backend):
    """Both carry geometries stay numerically correct with a dead op."""
    grid = (8, 8, 64)
    p = dead_op_program()
    rng = np.random.default_rng(3)
    fields = {"u": jnp.asarray(rng.normal(size=grid).astype(np.float32))}
    check_fused(p, grid, (fields, {}, {}),
                lambda fl, out: {"u": fl["u"] + 0.1 * out["su"]},
                steps=3, backend=backend)


def test_steps_requires_update():
    p = pw_advection()
    with pytest.raises(ValueError, match="update"):
        compile_program(p, (8, 8, 64), backend="jnp_fused", steps=3)


def test_bad_carry_write_rejected():
    p = pw_advection()
    with pytest.raises(ValueError, match="carry_write"):
        compile_program(p, (8, 8, 64), backend="jnp_fused", steps=3,
                        update=pw_advection_update(), carry_write="wat")
