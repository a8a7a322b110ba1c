"""Paper Tables 1/2: resource utilisation analogue.

FPGA resources map to TPU budgets as:
    %BRAM  -> VMEM window bytes per kernel instance / 128 MiB
    %LUT/FF-> (no analogue: Mosaic owns logic; we report kernel count)
    AXI ports / HBM banks -> field inputs per fuse group (memory streams)
    BRAM growth with problem size -> coefficient ('small data') bytes

Derived from the actual compiled plans, per problem size.  Prints
``name,us_per_call,derived`` CSV rows (``us_per_call`` is 0: these are
counts, not timings):

    PYTHONPATH=src python benchmarks/tab1_2_resources.py
"""

from __future__ import annotations

from repro import hw
from repro.apps import pw_advection, tracer_advection
from repro.core.passes import infer_halo
from repro.core.schedule import auto_plan, vmem_cost

SIZES = {
    "8M": (256, 256, 128),
    "32M": (512, 256, 256),
    "134M": (1024, 512, 256),
}


def run(emit):
    for prog_fn in (pw_advection, tracer_advection):
        p = prog_fn()
        for size, grid in SIZES.items():
            if p.name == "tracer_advection" and size == "134M":
                continue
            plan = auto_plan(p, grid)
            vmem = vmem_cost(p, plan, grid)
            pct = 100.0 * vmem / hw.TPU_V5E.vmem_bytes
            ports = max(len(infer_halo(p, g).group_inputs)
                        + len(infer_halo(p, g).group_outputs)
                        for g in plan.groups)
            coeff_bytes = sum(grid[ax] * 4 for _, ax in p.coeffs.items())
            emit(f"tab1_2/{p.name}/{size}/vmem_pct", 0.0,
                 f"{pct:.2f}% of VMEM ({vmem/2**20:.2f} MiB, "
                 f"block={plan.block}, groups={len(plan.groups)})")
            emit(f"tab1_2/{p.name}/{size}/stream_ports", 0.0,
                 f"{ports} field streams in widest group "
                 f"(paper: 7 AXI ports/CU for PW)")
            emit(f"tab1_2/{p.name}/{size}/small_data_bytes", 0.0,
                 f"{coeff_bytes} B coeff arrays (grows with nz, "
                 f"paper: BRAM grows with size)")


if __name__ == "__main__":
    run(lambda name, us, derived: print(f"{name},{us:.2f},{derived}"))
