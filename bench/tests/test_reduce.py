"""The reduction from the profiler's trace to per-layer metrics: op
classes from the HLO text the device trace carries (the strings are the
chip's), interval arithmetic on small hand-made traces, and the reading
of a small trace recorded on a TPU v5e (``data/``)."""

from pathlib import Path

import pytest

import tracing
from tracing import Op, TraceSummary

DATA = Path(__file__).resolve().parent / "data"

KERNEL = ('%closed_call.11 = (f32[1024,512,256]{2,1,0:T(8,128)}) custom-call('
          'f32[1,2]{1,0:T(1,128)S(1)} %bitcast.8, f32[1026,514,258]{2,1,0:T(8,'
          '128)} %get-tuple-element.231), custom_call_target="tpu_custom_call"'
          ', frontend_attributes={kernel_metadata={}}')
FUSION = ('%multiply_add_fusion.9 = f32[1024,512,256]{2,1,0:T(8,128)} fusion('
          'f32[1024,512,256]{2,1,0:T(8,128)} %pallas_call.23, f32[1026,514,258'
          ']{2,1,0:T(8,128)} %get-tuple-element.231), kind=kLoop')
PERMUTE = ('%collective-permute-start.3 = (f32[1,256,258]{2,1,0:T(8,128)}, '
           'f32[1,256,258]{2,1,0:T(8,128)}) collective-permute-start(f32[1,256'
           ',258]{2,1,0:T(8,128)} %slice.7), source_target_pairs={{0,2}}')
PERMUTE_DONE = ('%collective-permute-done.3 = f32[1,256,258]{2,1,0:T(8,128)} '
                'collective-permute-done((f32[1,256,258]{2,1,0}, f32[1,256,258'
                ']{2,1,0}) %collective-permute-start.3)')
LOOP = ('%while.3 = (s32[]{:T(128)}, f32[1026,514,258]{2,1,0:T(8,128)}) while('
        '(s32[]{:T(128)}, f32[1026,514,258]{2,1,0:T(8,128)}) %tuple.5), '
        'condition=%cond.1, body=%body.2')
CONSUMER = ('%concatenate.4 = f32[258,256,258]{2,1,0:T(8,128)} concatenate('
            'f32[1,256,258]{2,1,0:T(8,128)} %collective-permute-done.3, f32[256'
            ',256,258]{2,1,0:T(8,128)} %get-tuple-element.9), dimensions={0}')


@pytest.mark.parametrize("hlo,cls", [
    (KERNEL, "kernel"), (FUSION, "other"), (PERMUTE, "collective"),
    (PERMUTE_DONE, "collective"), (CONSUMER, "other"), (LOOP, "container")])
def test_classify(hlo, cls):
    assert tracing.classify(hlo) == cls


def _summary(ops_per_device, window=(0, 100), spans=()):
    return TraceSummary(
        window=window, spans=list(spans),
        devices={f"/device:TPU:{i}": [Op(s, e, h, tracing.classify(h))
                                      for s, e, h in ops]
                 for i, ops in enumerate(ops_per_device)})


def test_busy_union_classes_and_exposed_collectives():
    dev0 = [(0, 10, KERNEL), (5, 20, FUSION), (30, 40, PERMUTE_DONE),
            (35, 38, FUSION), (60, 70, KERNEL)]
    dev1 = [(0, 50, KERNEL), (50, 60, PERMUTE_DONE)]
    s = _summary([dev0, dev1])
    # device 0 busy 0-20, 30-40, 60-70 = 40; device 1 busy 0-60 = 60
    assert s.busy_s() == pytest.approx(50e-9)
    assert s.class_s("kernel") == pytest.approx((20 + 50) / 2 * 1e-9)
    assert s.class_s("other") == pytest.approx((15 + 3 + 0) / 2 * 1e-9)
    # exposed: device 0 has 10 of collective, 3 hidden by a fusion
    assert s.exposed_s() == pytest.approx((7 + 10) / 2 * 1e-9)
    assert s.window_s == pytest.approx(100e-9)


def test_idle_gaps_named_by_the_span_over_them():
    s = _summary([[(0, 10, KERNEL), (50, 60, KERNEL)]],
                 spans=[("bench.wait", 10, 45), ("bench.dispatch", 45, 50)])
    gaps = s.idle_gaps()
    assert gaps[0] == ["wait", pytest.approx(40e-9)]
    assert gaps[1] == ["untracked", pytest.approx(40e-9)]
    top = s.top_ops()
    assert top[0][0] == "closed_call.11 (kernel)"
    assert top[0][1] == pytest.approx(20e-9)


def test_recorded_chip_trace():
    """One 100-step dispatch of ``pw_advection`` at 1024x512x256 with the
    default plan, traced on a TPU v5e around a ``bench.dispatch`` span:
    one generated kernel per step, the fused loop's ``while`` left out,
    no collectives, and the device busy for nearly all of the span."""
    window, spans, devices = tracing.read(
        DATA / "pw_advection.134m.one_dispatch.xplane.pb.gz")
    assert window is None and [s[0] for s in spans] == ["bench.dispatch"]
    assert list(devices) == ["/device:TPU:0"]
    ops = devices["/device:TPU:0"]
    assert sum(o.cls == "kernel" for o in ops) == 100
    assert {o.cls for o in ops} == {"kernel", "other"}
    _, lo, hi = spans[0]
    s = TraceSummary(window=(lo, hi), devices=devices, spans=[])
    assert 0.99 * s.window_s < s.busy_s() <= s.window_s
    assert s.class_s("kernel") + s.class_s("other") == pytest.approx(
        s.busy_s(), rel=1e-3)
    assert s.top_ops(1)[0][0] == "closed_call.11 (kernel)"
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce(DATA / "pw_advection.134m.one_dispatch.xplane.pb.gz")
