"""The reduction from a profiler trace to busy time, idle gaps, kernel
and collective time."""
import glob
import os

import pytest

import readers
import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")


def _ev(name, s, t):
    return tr.Event(name, s, t, name)


def test_busy_is_the_union_of_overlapping_ops():
    spans = [_ev("bench.step", 0, 100)]
    dev = [_ev("%a.1 = x", 10, 30), _ev("%b.2 = x", 20, 40),   # overlap
           _ev("%c.3 = x", 40, 50),                            # touching
           _ev("%d.4 = x", 60, 70), _ev("%e.5 = x", 95, 120)]  # clipped
    t = tr.Trace(spans, {"/device:TPU:0": dev})
    assert t.busy_intervals("/device:TPU:0") == [[10, 50], [60, 70],
                                                 [95, 100]]
    assert t.busy_s() == pytest.approx(55e-9)
    assert t.gaps("/device:TPU:0") == [(0, 10), (50, 60), (70, 95)]
    b = t.breakdown()
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(25e-9)]


def test_gaps_are_named_by_the_innermost_span():
    spans = [_ev("bench.step", 0, 100), _ev("bench.loss_read", 50, 90)]
    t = tr.Trace(spans, {"/device:TPU:0": [_ev("%a.1 = x", 0, 50),
                                           _ev("%b.2 = x", 90, 100)]})
    assert t.breakdown()["idle_gaps"] == [["bench.loss_read",
                                           pytest.approx(40e-9)]]


def test_busy_averages_over_devices_and_loops_stay_out_of_the_ops():
    spans = [_ev("bench.step", 0, 100)]
    t = tr.Trace(spans, {
        "/device:TPU:0": [_ev("%while.1 = (s32) while(x)", 0, 100),
                          _ev("%fusion.2 = f32", 10, 20)],
        "/device:TPU:1": [_ev("%fusion.2 = f32", 0, 50)]})
    assert t.busy_s() == pytest.approx(75e-9)
    names = [n for n, _ in t.breakdown()["device_ops"]]
    assert names == ["%fusion.2 = f32"]


def test_kernel_and_collective_names():
    mac = ('%_call_fused_fwd.2 = (s32[128,128]{1,0}) custom-call(s32[896,'
           '128]{1,0} %pad.70), custom_call_target="tpu_custom_call"')
    assert tr.MAC.search(mac)
    assert tr.MAC.search('%_call.300 = (s32[512,2048]) custom-call(), '
                         'custom_call_target="tpu_custom_call"')
    assert tr.MAC.search('%_call_dw_partials.1 = (s32[1,896,128]) '
                         'custom-call(), custom_call_target="tpu_custom_call"')
    # The fused ⊞-SGD update and the combine are not ⊞-MAC launches.
    assert not tr.MAC.search('%_call_fused_update.4 = (s32[8,128]) '
                             'custom-call(), custom_call_target='
                             '"tpu_custom_call"')
    assert not tr.MAC.search("%fusion.36 = s32[400] fusion(s32[20])")
    # The data-parallel fold's wrapper is named ``_call`` too; its tag
    # tells it apart (as recorded on four chips).
    assert not tr.MAC.search(
        '%_call.8 = (s32[1,78592]{1,0:T(1,128)S(1)}) custom-call(s32[8,'
        '78592]{1,0:T(8,128)} %bitcast.1), custom_call_target="tpu_custom_'
        'call", frontend_attributes={kernel_metadata={\n"kind":"boxsum"'
        '\n}}')
    assert tr.MAC.search(
        '%_call.5 = (s32[128,128]) custom-call(), custom_call_target="tpu_'
        'custom_call", frontend_attributes={kernel_metadata={\n"kind":'
        '"dx",\n"r":"64"\n}}')
    assert tr.COLLECTIVE.search("%all-gather.3 = s32[4,784,128] "
                                "all-gather(s32[1,784,128] %x)")
    assert tr.COLLECTIVE.search("%all-gather-start.1 = (s32[1]) "
                                "all-gather-start(s32[1] %x)")
    assert not tr.COLLECTIVE.search("%fusion.1 = s32[4] fusion(%all-gather)")


def test_readers_leave_out_what_they_cannot_read():
    spans = [_ev("bench.step", 0, 100)]
    t = tr.Trace(spans, {"/device:TPU:0": [_ev("%fusion.1 = f32", 0, 10)]})

    class Ctx:
        trace, steps, chips = t, 1, 1
        counts = {"mac_calls": [(8, 8, 8)], "model_ops": 1.0}
        peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}

    assert readers.mac_ms_per_step(Ctx) is None
    assert readers.mac_roofline(Ctx) is None
    assert readers.collective_ms_per_step(Ctx) is None
    assert readers.idle_share(Ctx) == pytest.approx(90.0)


RECORDED = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_a_trace_recorded_on_the_chip(path):
    """A few steps of a cell, traced on a TPU v5e: the devices, the harness
    spans, the ⊞-MAC kernels, and on four chips the all-gather."""
    from jax.profiler import ProfileData
    t = tr.Trace.from_profile(ProfileData.from_file(path))
    assert t.devices and all(d.startswith("/device:TPU:") for d in t.devices)
    assert {e.name for e in t.spans} >= {"bench.feed", "bench.step",
                                         "bench.sync"}
    assert 0 < t.busy_s() < t.window_s
    assert t.count(tr.MAC) > 0 and t.matched_s(tr.MAC) < t.busy_s()
    if len(t.devices) == 4:
        assert t.count(tr.COLLECTIVE) > 0
