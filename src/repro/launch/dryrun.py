import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")  # SPMD remat warnings off

"""Multi-pod dry-run (deliverable e): lower + compile every
(architecture x input shape) on the production meshes, record memory and
cost analysis + roofline terms.

The two lines above MUST run before any jax import: jax locks the device
count at first initialisation.  Smoke tests and benches never import this
module, so they see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
        --shape train_4k --pods 1
    PYTHONPATH=src python -m repro.launch.dryrun --all --pods both \
        --out experiments/dryrun
"""

import argparse
import json
import time
import traceback

import jax  # noqa: E402  (after XLA_FLAGS on purpose)

from repro.analysis.roofline import (analytic_flops, analytic_traffic,
                                     roofline_report)
from repro.configs import ARCHS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh, make_rules
from repro.launch.specs import build_step

# long_500k needs sub-quadratic attention: skip for pure full-attention
# archs (noted in DESIGN.md §Arch-applicability); run for SWA/SSM/hybrid.
FULL_ATTN_ARCHS = {"grok_1_314b", "nemotron_4_340b", "chameleon_34b",
                   "whisper_small"}


def cell_is_skipped(arch: str, shape_name: str) -> str | None:
    if shape_name == "long_500k" and arch in FULL_ATTN_ARCHS:
        return "skip:full-attention arch (sub-quadratic required)"
    return None


def scan_correction(cfg, shape, microbatches: int = 1) -> float:
    """Known outer-scan trip counts multiplied back into HLO flops/bytes.

    Train/score paths scan over layers and over grad-accumulation
    microbatches (bodies counted once by XLA cost analysis).  Prefill/decode
    paths are layer-unrolled (factor 1); inner sequence-chunk scans (flash
    KV chunks, SSM chunks) remain under-counted and are flagged in notes.
    """
    if shape.kind == "train":
        return float(cfg.n_layers) * float(microbatches)
    return 1.0


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str | None = None, variant: str = "baseline") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_is_skipped(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if skip:
        rec["status"] = skip
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rules = make_rules(mesh, kind=("train" if shape.kind == "train"
                                   else "serve"), variant=variant)
    t0 = time.time()
    step, args, in_sh = build_step(cfg, shape, rules)
    mb = getattr(step, "microbatches", 1)
    out_sh = getattr(step, "out_shardings", None)
    jit_kwargs = {"in_shardings": in_sh}
    if out_sh is not None:
        jit_kwargs["out_shardings"] = out_sh
    with mesh:
        lowered = jax.jit(step, **jit_kwargs).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    af = analytic_flops(cfg, shape)
    tp = mesh.shape["model"] if rules.tp else 1
    fsdp = (chips // tp if (shape.kind == "train" and rules.fsdp) else 1)
    dp_total = chips // tp
    traffic = analytic_traffic(cfg, shape, chips=chips, tp=tp, fsdp=fsdp,
                               dp_total=dp_total)
    rep = roofline_report(chips=chips, cost=cost, hlo_text=hlo,
                          scan_correction=scan_correction(cfg, shape, mb),
                          model_flops=af["model_flops"], analytic=traffic)
    rec.update({
        "status": "ok",
        "chips": chips,
        "microbatches": mb,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "per_device_total": int(mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "analytic_flops": af,
        "roofline": rep,
    })
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        path = os.path.join(
            out_dir, f"{arch}__{shape_name}__{rec['mesh']}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def summarise(rec: dict) -> str:
    if rec["status"] != "ok":
        return (f"{rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:8s} "
                f"{rec['status']}")
    m = rec["memory"]["per_device_total"] / 2**30
    t = rec["roofline"].get("terms_primary",
                            rec["roofline"]["terms_corrected"])
    return (f"{rec['arch']:18s} {rec['shape']:12s} {rec['mesh']:8s} ok "
            f"mem/dev={m:6.2f}GiB compute={t['compute_s']:.2e}s "
            f"memory={t['memory_s']:.2e}s coll={t['collective_s']:.2e}s "
            f"dom={t['dominant']:10s} "
            f"(compile {rec['compile_s']:.0f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--pods", default="1", choices=["1", "2", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-save", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    help="'+'-joined levers: sp, dp_remap, kvseq")
    args = ap.parse_args()

    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"1": [False], "2": [True], "both": [False, True]}[args.pods]
    out = None if args.no_save else args.out

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                try:
                    rec = run_cell(arch, shape, mp, out, args.variant)
                except Exception as e:
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": f"FAIL {type(e).__name__}: {e}"}
                    traceback.print_exc()
                    if out:
                        os.makedirs(out, exist_ok=True)
                        with open(os.path.join(
                                out, f"{arch}__{shape}__{rec['mesh']}.json"),
                                "w") as f:
                            json.dump(rec, f, indent=1)
                print(summarise(rec), flush=True)
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
