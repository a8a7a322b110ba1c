#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python bench/run.py --workload pw.134m.loop --seed 7 --seconds 20 --trace 0

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its traffic
file's ``kind`` picks the driver (``drive_<kind>.py``): ``loop`` or
``serve``. A run makes its data from ``--seed`` on the device,
compiles through JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR``, or
``bench/.jax_cache``), warms up the cell's own shapes, measures for
``--seconds`` and then compares a sample of what the window produced with
the plain reference. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` the profiler records the window and
the per-layer metrics (``metrics/<name>.py``) read its trace.

The last lines of standard error, and the ``check`` entry that comes last
in the result line, give each number compared beside its limit. A run
that finds no TPU, or fewer chips than the cell asks for, exits 2 and
prints no result.

``--rehearse`` runs the cell on the CPU instead, with the Pallas
interpreter, every grid cut by 16 per axis (at least 8) and at most 3
steps per dispatch. It is for trying the harness without a chip: its
line carries ``readings`` and no ``metrics`` or ``device``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REHEARSE_DEVICES = 4

if "--rehearse" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force"
                               f"_host_platform_device_count={REHEARSE_DEVICES}")
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import jax  # noqa: E402

import common  # noqa: E402
import tracing  # noqa: E402


@dataclasses.dataclass
class Run:
    """One run of one cell: what the driver is given and what it fills."""
    cell: common.Cell
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    devices: list
    watch: tracing.CompileWatch
    setup_s: float = None
    setup_compile_s: float = None
    memory_peak: int = None
    attempted: int = 0
    failed: int = 0
    metrics: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    readings: dict = dataclasses.field(default_factory=dict)
    last_window: tracing.Window = None
    check: dict = None
    correct: bool = False

    def grid(self, grid) -> tuple:
        g = tuple(int(x) for x in grid)
        return tuple(max(8, x // 16) for x in g) if self.rehearse else g

    def steps(self, steps: int) -> int:
        return min(int(steps), 3) if self.rehearse else int(steps)

    @contextlib.contextmanager
    def phase(self, name: str):
        """A span of the run, timed into ``counters[<name>_s]``."""
        t0 = time.perf_counter()
        with tracing.span(name):
            yield
        self.counters[f"{name}_s"] = time.perf_counter() - t0

    def setup_done(self):
        """Set-up ends here: process start to the first timed dispatch."""
        self.setup_s = time.perf_counter() - T_START
        self.setup_compile_s = self.watch.seconds

    def window(self) -> tracing.Window:
        trace_dir = BENCH / ".traces" / self.cell.name if self.trace else None
        self.last_window = tracing.Window(self.watch, trace_dir)
        return self.last_window

    def read_memory(self):
        stats = [d.memory_stats() or {} for d in self.devices]
        peaks = [s["peak_bytes_in_use"] for s in stats
                 if "peak_bytes_in_use" in s]
        self.memory_peak = max(peaks) if peaks else None


def per_layer(r: Run, bench: dict, summary, device_kind: str) -> dict:
    """The per-layer metrics this cell lists, each from its own reader;
    a reader that finds nothing to read returns None and is left out."""
    ctx = {"trace": summary, "counters": r.counters, "config": r.cell.config,
           "chips": r.cell.chips, "setup_compile_s": r.setup_compile_s,
           "peaks": common.peaks(device_kind)}
    out = {}
    for m in bench["per_layer"]:
        if r.cell.name not in m.get("workloads", [r.cell.name]):
            continue
        reader = common.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                    f"metric_{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def find_devices(cell: common.Cell, rehearse: bool):
    """The devices the cell runs on, or None where this machine has no TPU
    or too few chips (a rehearsal takes the CPU's)."""
    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    return devices


def use_compile_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or at the
    fixed ``bench/.jax_cache``, keeping every compile however short."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        BENCH / ".jax_cache")
    Path(cache).mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: common.Cell, devices, args) -> Run:
    """One run of ``cell``: set-up, window and check, as its traffic
    kind's driver does them; the check's verdict is on ``r.correct``."""
    r = Run(cell=cell, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), rehearse=args.rehearse,
            devices=devices[:cell.chips], watch=tracing.CompileWatch())
    driver = importlib.import_module(f"drive_{cell.traffic['kind']}")
    driver.run(r)
    w = r.last_window
    print(f"{cell.name}: setup_s={r.setup_s:.3f} "
          f"setup_compile_s={r.setup_compile_s:.3f} window_s={w.seconds:.3f}"
          f" compiles_in_window={w.compiles} traces_in_window={w.traces} "
          f"counters={json.dumps(r.counters)}", flush=True)
    ok, r.check = common.judge(r.readings, cell.limits)
    r.correct = ok and r.failed == 0
    return r


def main(argv=None) -> int:
    args = parse(argv)
    bench = common.load_json(BENCH.parent / "BENCHMARK.json")
    cell = common.load_cell(args.workload)
    devices = find_devices(cell, args.rehearse)
    if devices is None:
        return 2
    use_compile_cache()
    r = run_cell(cell, devices, args)
    for name, e in r.check.items():
        print(f"check {name}: {e['value']!r} limit {e['limit']!r}",
              file=sys.stderr)

    if args.rehearse:
        print(json.dumps({"rehearsal": True, "correct": r.correct,
                          "attempted": r.attempted, "failed": r.failed,
                          "readings": r.metrics,
                          "check": common.plain(r.check)}))
        return 0 if r.correct else 1

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": r.memory_peak}
    result = {"correct": r.correct, "attempted": r.attempted,
              "failed": r.failed}
    if args.trace:
        summary = tracing.reduce(r.last_window.trace_dir, cell.chips)
        metrics = per_layer(r, bench, summary, dev.device_kind)
        device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.idle_gaps()}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in dict(r.metrics, setup_s=r.setup_s).items()}
    result.update(metrics=metrics, device=device, check=r.check)
    print(json.dumps(common.plain(result)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
