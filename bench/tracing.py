"""The benchmark's own spans, the compile watch, the measured window and
the reduction of the profiler's trace to what the per-layer metrics read.

Spans are ``jax.profiler.TraceAnnotation``s named ``bench.<what>`` around
the benchmark's calls into the program (``data``, ``dispatch``, ``wait``,
``submit``, ``reference``), and ``bench.window`` around the measured
window. They cost next to nothing when no trace is being taken. With
``--trace 1`` the profiler records the window alone, without Python
function events, and :func:`reduce` reads the file it writes.
"""

from __future__ import annotations

import dataclasses
import gzip
import re
import shutil
import time
from pathlib import Path

import jax
from jax._src.profiler import ProfileData

#: JAX's own monitoring events of a compile: tracing, lowering, and the
#: backend compile (which also covers a read from the persistent cache)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[-1]

WINDOW = "bench.window"
DEVICE_OPS_LINE = "XLA Ops"
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all", "collective-broadcast")
#: ops whose time is that of the ops they run inside them, such as the
#: fused loop's ``while``: left out of every sum
CONTAINERS = ("while", "conditional", "call")


def span(name: str):
    """A host span ``bench.<name>`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


class CompileWatch:
    """Counts and times JAX's compiles, so that set-up reports its compile
    seconds and the window reports how many compiles fell inside it."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.compiles += event == BACKEND_COMPILE
            self.traces += event == COMPILE_EVENTS[0]

    def counts(self) -> tuple:
        return self.compiles, self.traces


class Window:
    """The measured window: from ``t0`` to ``t1`` on the host clock, with
    the profiler on around it when ``trace_dir`` is given, and the number
    of compiles JAX made inside it."""

    def __init__(self, watch: CompileWatch, trace_dir: Path | None):
        self.watch, self.trace_dir = watch, trace_dir
        self.t0 = self.t1 = None

    def __enter__(self):
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        self._ann = span("window")
        self._ann.__enter__()
        self._before = self.watch.counts()
        self.t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def close(self):
        """End the window now (idempotent); the profiler stops later."""
        if self.t1 is None:
            self.t1 = time.perf_counter()
            after = self.watch.counts()
            self.compiles = after[0] - self._before[0]
            self.traces = after[1] - self._before[1]
            self._ann.__exit__(None, None, None)

    def __exit__(self, *exc):
        self.close()
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# --------------------------------------------------------------------------
# trace reduction
# --------------------------------------------------------------------------

_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def classify(hlo: str) -> str:
    """``kernel`` for a generated Pallas kernel (a ``tpu_custom_call``),
    ``collective`` for an exchange between chips, ``container`` for an op
    that runs others inside it, ``other`` for the rest (XLA's fusions,
    pads, slices and copies), from the op's HLO text as the device trace
    names it: ``%name = <shape> opcode(operands), ...``."""
    if 'custom_call_target="tpu_custom_call"' in hlo:
        return "kernel"
    rhs = hlo.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rhs)
    op = m.group(1) if m else ""
    if op in CONTAINERS:
        return "container"
    return "collective" if op.startswith(COLLECTIVES) else "other"


def op_label(hlo: str) -> str:
    """Short label of a device op: its HLO name and opcode class."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return f"{name} ({classify(hlo)})"


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a, b) -> list:
    """Measure-wise ``a \\ b`` of two sorted disjoint interval lists."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


@dataclasses.dataclass
class Op:
    start: float            # ns, on the trace's clock
    end: float
    hlo: str
    cls: str


@dataclasses.dataclass
class TraceSummary:
    """The traced window: each device's ops inside it, and the host spans
    of the benchmark."""
    window: tuple                   # (start, end) ns
    devices: dict                   # plane name -> [Op]
    spans: list                     # (name, start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _mean(self, fn) -> float:
        vals = [fn(ops) for ops in self.devices.values()]
        return sum(vals) / len(vals) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the devices."""
        return self._mean(lambda ops: _length(
            _union((o.start, o.end) for o in ops)))

    def class_s(self, cls: str) -> float:
        """Seconds in which an op of class ``cls`` ran, device mean."""
        return self._mean(lambda ops: _length(
            _union((o.start, o.end) for o in ops if o.cls == cls)))

    def exposed_s(self, cls: str = "collective") -> float:
        """Seconds in which an op of class ``cls`` ran and no op of any
        other class did, device mean."""
        def one(ops):
            a = _union((o.start, o.end) for o in ops if o.cls == cls)
            b = _union((o.start, o.end) for o in ops if o.cls != cls)
            return _length(_minus(a, b))
        return self._mean(one)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` device ops that took most time, device mean, s."""
        tot: dict = {}
        for ops in self.devices.values():
            for o in ops:
                k = op_label(o.hlo)
                tot[k] = tot.get(k, 0.0) + (o.end - o.start)
        k = len(self.devices)
        return sorted(([name, t * 1e-9 / k] for name, t in tot.items()),
                      key=lambda x: -x[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest stretches in which the first device ran
        nothing, each named by the benchmark span that covers most of it
        (``untracked`` where none does), s."""
        ops = next(iter(self.devices.values()))
        busy = _union((o.start, o.end) for o in ops)
        gaps = _minus([list(self.window)], busy)
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            best, label = 0.0, "untracked"
            for name, a, b in self.spans:
                cover = min(e, b) - max(s, a)
                if cover > best:
                    best, label = cover, name.removeprefix("bench.")
            out.append([label, (e - s) * 1e-9])
        return out


def read(trace: Path) -> tuple:
    """Read the profiler's file (``trace``, gzipped or not, or the newest
    ``.xplane.pb`` under that directory): the first ``bench.window`` span
    (None if there is none), the benchmark's other spans, and each device
    plane's ``XLA Ops`` but its containers."""
    trace = Path(trace)
    files = [trace] if trace.is_file() else sorted(trace.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace}")
    if files[-1].suffix == ".gz":
        with gzip.open(files[-1]) as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(str(files[-1]))
    window, spans, devices = None, [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif re.match(r"/device:[A-Z]+:\d+$", plane.name):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    ops = [Op(ev.start_ns, ev.end_ns, ev.name,
                              classify(ev.name)) for ev in line.events]
                    devices[plane.name] = [o for o in ops
                                           if o.cls != "container"]
    return window, spans, devices


def reduce(trace: Path, chips: int | None = None) -> TraceSummary:
    """The traced window of ``trace`` (see :func:`read`): each device's
    ops clipped to the ``bench.window`` span, and the spans inside it.
    ``chips`` keeps the first that many devices, those a cell uses."""
    window, spans, devices = read(trace)
    if window is None:
        raise ValueError(f"no {WINDOW} span in {trace}")
    lo, hi = window
    used = sorted(devices, key=lambda d: int(d.rsplit(":", 1)[1]))[:chips]
    clipped = {d: [Op(max(o.start, lo), min(o.end, hi), o.hlo, o.cls)
                   for o in devices[d] if o.end > lo and o.start < hi]
               for d in used}
    return TraceSummary(window=window, devices=clipped,
                        spans=[s for s in spans if s[2] > lo and s[1] < hi])
