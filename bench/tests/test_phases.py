"""The phase reduction: the six fused-loop readers on hand-made traces
whose ops carry the program's phase tags, and on a dispatch recorded on
a TPU v5e (``data/``)."""

from pathlib import Path

import pytest

import common
import phases
import tracing
from tracing import Op, TraceSummary

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
READERS = ("update", "carry_write", "group_pad", "window", "entry_exit",
           "untagged")

KERNEL = ('%blk_su_sv_sw.6 = (f32[256,256,128]{2,1,0:T(8,128)}) custom-call('
          'f32[1,2]{1,0:T(1,128)S(1)} %bitcast.23), custom_call_target="tpu_'
          'custom_call", frontend_attributes={kernel_metadata={}}')
PERMUTE = ('%collective-permute-start.3 = (f32[1,256,258]{2,1,0:T(8,128)}) '
           'collective-permute-start(f32[1,256,258]{2,1,0:T(8,128)} %slice.7)'
           ', frontend_attributes={repro_phase="halo"}')
PLAIN = ('%add.29 = s32[]{:T(128)} add(s32[]{:T(128)} %get-tuple-element.169,'
         ' s32[]{:T(128)} %constant.29)')


def tagged(op: str, tag: str) -> str:
    return (f'%{op}.1 = f32[256,256,128]{{2,1,0:T(8,128)}} {op}(f32[256,256,'
            f'128]{{2,1,0:T(8,128)}} %p.1), frontend_attributes={{repro_'
            f'phase="{tag}"}}')


def read_all(summary, steps):
    ctx = {"trace": summary, "counters": {"steps": steps}}
    return {name: common.load_module(
        BENCH / "metrics" / f"{name}_ms_per_step.loop.py",
        f"metric_{name}").read(ctx) for name in READERS}


def _summary(ops_per_device):
    return TraceSummary(
        window=(0, 1000), spans=[],
        devices={f"/device:TPU:{i}": [Op(s, e, h, tracing.classify(h))
                                      for s, e, h in ops]
                 for i, ops in enumerate(ops_per_device)})


def test_phase_of_an_op():
    assert phases.phase(tagged("pad", "carry_write")) == "carry_write"
    assert phases.phase(KERNEL) is None
    assert phases.phase(PLAIN) is None


def test_readers_partition_the_glue():
    dev0 = [(0, 10, tagged("pad", "entry")), (10, 50, KERNEL),
            (50, 70, tagged("fusion", "update")),
            (60, 65, tagged("slice", "update")),      # overlaps: one union
            (70, 85, tagged("pad", "carry_write")),
            (85, 86, PLAIN), (86, 90, PERMUTE),
            (90, 94, tagged("pad", "group_pad")),
            (94, 97, tagged("slice", "window")), (97, 99, tagged("slice", "exit"))]
    dev1 = [(0, 40, KERNEL), (40, 60, tagged("fusion", "update"))]
    s = _summary([dev0, dev1])
    got = read_all(s, steps=2)
    ms = 1e3 * 1e-9 / 2 / 2          # ns per device-mean per step, in ms
    assert got["update"] == pytest.approx((20 + 20) * ms)
    assert got["carry_write"] == pytest.approx(15 * ms)
    assert got["group_pad"] == pytest.approx(4 * ms)
    assert got["window"] == pytest.approx(3 * ms)
    assert got["entry_exit"] == pytest.approx(12 * ms)
    assert got["untagged"] == pytest.approx(1 * ms)   # the collective is out
    glue = 1e3 * s.class_s("other") / 2
    assert sum(got.values()) == pytest.approx(glue)


def test_readers_are_silent_without_tags():
    """A program that tags nothing (the parent of the tags) gives no
    reading, so the result line leaves the metrics out."""
    s = _summary([[(0, 10, KERNEL), (10, 20, PLAIN)]])
    assert set(read_all(s, steps=1).values()) == {None}
    assert set(read_all(None, steps=1).values()) == {None}
    assert set(read_all(s, steps=0).values()) == {None}


def test_recorded_chip_trace():
    """One 10-step dispatch of ``tracer_advection`` at 256x256x128 with
    the default plan, traced on a TPU v5e around a ``bench.dispatch``
    span: its 24 kernels each named by the field it produces, and the six
    readers with the kernels' time making up the device's busy time."""
    window, spans, devices = tracing.read(
        DATA / "tracer_advection.8m.one_dispatch.xplane.pb.gz")
    assert window is None and [s[0] for s in spans] == ["bench.dispatch"]
    ops = devices["/device:TPU:0"]
    kernels = [o.hlo.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
               for o in ops if o.cls == "kernel"]
    assert len(set(kernels)) == 24 and len(kernels) == 24 * 10
    assert all(k.startswith("blk_") for k in kernels)
    assert {"blk_zta1", "blk_ta", "blk_zdiv2"} <= set(kernels)
    assert {phases.phase(o.hlo) for o in ops if o.cls == "other"} >= {
        "entry", "window", "group_pad", "update", "carry_write", "exit"}
    _, lo, hi = spans[0]
    s = TraceSummary(window=(lo, hi), devices=devices, spans=[])
    got = read_all(s, steps=10)
    assert None not in got.values()
    kernel_ms = 1e3 * s.class_s("kernel") / 10
    assert sum(got.values()) + kernel_ms == pytest.approx(
        1e3 * s.busy_s() / 10, rel=5e-3)
