"""Modeled TPU performance for stencil programs (paper Fig. 4 analogue).

The FPGA paper's II=1 design is *streaming-bandwidth limited*: one result
per cycle with every input element fetched exactly once.  The TPU dataflow
backend has the same property (windows fetch each element once per fuse
group), so the model is:

    time/pt = max( bytes_per_point / HBM_bw,  flops_per_point / VPU_f32 )
    MPt/s   = 1e-6 / time_per_point    (per chip; x chips when distributed)

bytes_per_point per backend:
  * pallas (dataflow) — each group input read once, each group output
    written once (+halo fraction, negligible at production block sizes)
  * jnp_fused (DaCe role)   — inputs re-read per consuming op after XLA
    fusion boundaries: approximated as one read per field per op-cluster
  * jnp_naive (Vitis -O0 role) — one read per stencil ACCESS, one write per
    op (no reuse at all)
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .. import hw
from ..core.ir import Program, count_flops
from ..core.passes import infer_halo, live_ops, stage_split

# v5e vector unit f32 throughput (8x128 lanes x FMA x ~0.94 GHz) — estimate
VPU_F32_FLOPS = 7.5e12

# Fixed cost of one stream-sweep grid step (window shift + DMA dispatch),
# amortised by spatial unrolling: a ``plane_tile = P`` sweep pays it only
# ``ceil(n_steps / P)`` times.  Rough estimate; it exists so the roofline
# can *rank* P honestly, not to predict absolute seconds.
STREAM_STEP_OVERHEAD_S = 5e-9


@dataclasses.dataclass
class StencilModel:
    flops_per_point: float
    bytes_per_point: dict      # backend -> bytes
    mpts_chip: dict            # backend -> modeled MPt/s on one chip

    def mpts(self, backend: str, chips: int = 1) -> float:
        return self.mpts_chip[backend] * chips


def model_program(p: Program, dtype_bytes: int = 4) -> StencilModel:
    fl = p.flops_per_point()
    alive = live_ops(p)
    groups = stage_split(p, "auto")

    # dataflow: per group, each external input read once + outputs written
    reads = 0
    writes = 0
    for g in groups:
        gh = infer_halo(p, g)
        reads += len(gh.group_inputs) + len(gh.group_coeffs) * 0  # coeffs tiny
        writes += len(gh.group_outputs)
    dataflow_b = (reads + writes) * dtype_bytes

    # naive: one read per access, one write per op
    accesses = sum(len(p.ops[i].accesses()) for i in alive)
    naive_b = (accesses + len(alive)) * dtype_bytes

    # fused jnp: XLA fuses elementwise chains but rematerialises between
    # reduction/reshape boundaries; empirical middle ground — one read per
    # distinct field per op + one write per op
    fused_reads = sum(len({a.field for a in p.ops[i].accesses()})
                      for i in alive)
    fused_b = (fused_reads + len(alive)) * dtype_bytes

    bytes_pp = {"pallas": dataflow_b, "jnp_fused": fused_b,
                "jnp_naive": naive_b}
    mpts = {}
    for k, b in bytes_pp.items():
        t_mem = b / hw.TPU_V5E.hbm_bandwidth
        t_cmp = fl / VPU_F32_FLOPS
        mpts[k] = 1e-6 / max(t_mem, t_cmp)
    return StencilModel(flops_per_point=fl, bytes_per_point=bytes_pp,
                        mpts_chip=mpts)


def plan_bytes_per_point(p: Program, plan, grid, graph=None) -> float:
    """Modeled HBM bytes per grid point for one plan's actual geometry.

    Schedule-aware (the reuse structure is the whole point of the plan
    dimension):

    * ``"block"`` — each fuse-group input is fetched as an overlapping
      window, so its traffic carries the halo overhead
      ``prod(window) / prod(block)``: a small block on a wide halo re-reads
      the overlap every tile.
    * ``"stream"`` — the shift-register sweep fetches **each input cell
      once per region sweep** (the paper's headline property); the only
      overhead is the padded halo ring itself, ``prod(padded extents) /
      prod(grid)``, which vanishes at production grids.  With temporal
      blocking (effective ``time_tile = T > 1`` on the graph) one sweep
      advances T time steps, so the whole sweep's traffic — inputs read
      through the T-deepened (chained) halo, outputs written once — is
      charged **once per T steps**: bytes/point/step shrinks ~1/T, which
      is exactly the reuse the tuner searches T for.

    Outputs are written once either way.  The jnp backends ignore plan
    geometry and collapse to :func:`model_program`'s backend-level numbers.
    """
    bs = hw.DTYPE_BYTES[plan.dtype]
    if plan.backend != "pallas":
        return float(model_program(p, dtype_bytes=bs)
                     .bytes_per_point[plan.backend])
    grid = [int(g) for g in grid]
    if getattr(plan, "schedule", "block") == "stream":
        if graph is None:
            from ..core.dataflow import lower_to_dataflow
            graph = lower_to_dataflow(p, plan, grid)
        T = max(1, int(getattr(graph, "time_tile", 1)))
        bytes_pp = 0.0
        # chained halos: the sweep's real fetch geometry under temporal
        # blocking (identical to the per-step halos at T = 1)
        for gh in graph.group_halos():
            padded = [grid[a] + int(gh.input_halo[a, 0])
                      + int(gh.input_halo[a, 1]) for a in range(p.ndim)]
            overhead = float(np.prod(padded)) / float(np.prod(grid))
            bytes_pp += (len(gh.group_inputs) * overhead * bs
                         + len(gh.group_outputs) * bs) / T
        return bytes_pp
    blk = np.minimum(np.asarray(plan.block[:p.ndim], dtype=np.int64),
                     np.asarray(grid, dtype=np.int64))
    blk = np.maximum(blk, 1)
    bytes_pp = 0.0
    for grp in plan.groups:
        gh = infer_halo(p, grp)
        win = blk + gh.input_halo[:, 0] + gh.input_halo[:, 1]
        overhead = float(np.prod(win)) / float(np.prod(blk))
        bytes_pp += len(gh.group_inputs) * overhead * bs
        bytes_pp += len(gh.group_outputs) * bs
    return bytes_pp


def _plan_flops_per_point(p: Program, plan, grid, graph=None) -> float:
    """Recompute-inflated flops/point: block margins extend every tile,
    stream margins only widen the non-stream axes of each plane (stream-axis
    dependencies ride in ring buffers, recompute-free).  A temporal chain
    (effective ``time_tile = T > 1``) runs every op once per chain stage;
    earlier stages compute over margin-extended planes (stage ``s`` adds
    ``(T-1-s)`` per-step halo reaches on the non-stream axes, mirroring the
    kernel's ``stage_margins``) so the redundant boundary work the chain
    trades for HBM traffic is priced in, amortised over the T steps one
    sweep advances."""
    grid = [int(g) for g in grid]
    if getattr(plan, "schedule", "block") == "stream":
        if graph is None:
            from ..core.dataflow import lower_to_dataflow
            graph = lower_to_dataflow(p, plan, grid)
        T = max(1, int(getattr(graph, "time_tile", 1)))
        flops_pp = 0.0
        plane = np.asarray(grid[1:], dtype=np.int64)
        for region in graph.regions:
            ih = region.halo.input_halo          # per-step reach
            step = ih[1:, 0] + ih[1:, 1]
            for s in range(T):
                acc = T - 1 - s
                for i in region.ops:
                    m = region.halo.margins[i]
                    ext = plane + m[1:, 0] + m[1:, 1] + acc * step
                    recompute = float(np.prod(ext)) / float(np.prod(plane))
                    flops_pp += count_flops(p.ops[i].expr) * recompute / T
        return flops_pp
    blk = np.minimum(np.asarray(plan.block[:p.ndim], dtype=np.int64),
                     np.asarray(grid, dtype=np.int64))
    blk = np.maximum(blk, 1)
    flops_pp = 0.0
    for grp in plan.groups:
        gh = infer_halo(p, grp)
        for i in grp:
            m = gh.margins[i]
            ext = blk + m[:, 0] + m[:, 1]
            recompute = float(np.prod(ext)) / float(np.prod(blk))
            flops_pp += count_flops(p.ops[i].expr) * recompute
    return flops_pp


def model_plan(p: Program, plan, grid) -> float:
    """Modeled seconds per time step for one *specific* plan (tuner pruner).

    :func:`model_program` prices the three backend roles; this prices a
    candidate :class:`~repro.core.schedule.DataflowPlan`'s actual geometry
    so the tuner can rank candidates *before* paying for a measurement —
    reuse-aware via :func:`plan_bytes_per_point` (stream schedules charge
    each input cell once per sweep, block schedules re-read window
    overlaps) and recompute-aware via the margin-extended flop count.

    The jnp backends ignore block shape and fuse groups, so their candidates
    collapse to the backend-level bytes/point of :func:`model_program`.
    """
    pts = float(np.prod([int(g) for g in grid]))
    bs = hw.DTYPE_BYTES[plan.dtype]
    if plan.backend != "pallas":
        m = model_program(p, dtype_bytes=bs)
        return pts / (m.mpts(plan.backend) * 1e6)
    graph = None
    if getattr(plan, "schedule", "block") == "stream":
        # legalise once; both the bytes and flops terms consume it
        from ..core.dataflow import lower_to_dataflow
        graph = lower_to_dataflow(p, plan, grid)
    t_mem = (plan_bytes_per_point(p, plan, grid, graph=graph) * pts
             / hw.TPU_V5E.hbm_bandwidth)
    t_cmp = (_plan_flops_per_point(p, plan, grid, graph=graph) * pts
             / VPU_F32_FLOPS)
    t_step = 0.0
    if graph is not None:
        # per-grid-step sweep overhead, amortised P-fold by spatial
        # unrolling and spread over the T time steps one sweep advances
        T = max(1, int(getattr(graph, "time_tile", 1)))
        P = max(1, int(getattr(graph, "plane_tile", 1)))
        n_steps = int(grid[0])
        t_step = (len(graph.regions) * -(-n_steps // P)
                  * STREAM_STEP_OVERHEAD_S / T)
    return max(t_mem, t_cmp) + t_step

