"""The fused loop's phases in the device trace.

The program tags the XLA ops it runs around its generated kernels with a
phase (``repro.obs.phase``): ``entry``, ``window``, ``group_pad``,
``update``, ``carry_write``, ``exit`` and, across a mesh, ``halo``. The
tag rides in the op's ``frontend_attributes``, which the device trace's
name for the op (its HLO text) includes: ``repro_phase="update"``. A fused
op carries the tag of its root op.
"""

from __future__ import annotations

import re

from tracing import TraceSummary

_TAG = re.compile(r'repro_phase="([a-z_]+)"')


def phase(hlo: str) -> str | None:
    """The phase an op's HLO text names, or None if it names none."""
    m = _TAG.search(hlo)
    return m.group(1) if m else None


def ms_per_step(ctx, phases) -> float | None:
    """Device ms per time step in which an op that is neither a kernel
    nor a collective, and whose phase is in ``phases`` (None standing for
    an op with no phase), ran: the union of those ops' intervals on each
    device, averaged over the devices. None where the trace has no device
    ops or no op carries a phase at all, as in a program that tags none."""
    t, c = ctx["trace"], ctx["counters"]
    if t is None or not t.devices or not c.get("steps"):
        return None
    if not any(phase(o.hlo) for ops in t.devices.values() for o in ops):
        return None
    picked = TraceSummary(
        window=t.window, spans=[],
        devices={d: [o for o in ops
                     if o.cls == "other" and phase(o.hlo) in phases]
                 for d, ops in t.devices.items()})
    return 1e3 * picked.busy_s() / c["steps"]
