"""The readers of what the program names in its trace (``bench/tags.py``),
and the readings of the metrics that were there before them, pinned on
the trace recorded before the program tagged anything."""
import os
import types

import pytest

import readers
import roofline
import tags
import trace as tr
from common import load_json, load_module

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces")
UNTAGGED = os.path.join(DATA, "paper-mlp.online-b5.xplane.pb")
TAGGED = os.path.join(DATA, "paper-mlp.online-b5.tagged.xplane.pb")
NEW = ("mlp.fwd_ms_per_step", "mlp.dx_ms_per_step", "mlp.dw_ms_per_step",
       "mlp.mac_fill", "mlp.host_ms_per_step", "mlp.step_idle_share")


def _ev(name, s, t):
    return tr.Event(name, s, t, name)


def _mac(kind, s, t, r=5, c=100, ct=784, rp=128, cp=128, ctp=896):
    md = (f'{{\n"kind":"{kind}",\n"r":"{r}",\n"c":"{c}",\n"ct":"{ct}",\n'
          f'"rp":"{rp}",\n"cp":"{cp}",\n"ctp":"{ctp}"\n}}')
    return _ev(f"%_call.1 = (s32[128,128]) custom-call(), custom_call_target="
               f'"tpu_custom_call", frontend_attributes={{kernel_metadata='
               f"{md}}}", s, t)


def _load(path):
    """The reduced trace, with the program's spans set on it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    t = tr.Trace.from_profile(pd)
    t.program_spans = tags.program_spans(pd)
    return t


def _ctx(t, steps):
    c = load_json("configs", "paper-mlp.json")
    counts = load_module("configs", "paper-mlp").counts(
        c, types.SimpleNamespace(items_per_step=5), 1)
    return types.SimpleNamespace(trace=t, steps=steps, counts=counts,
                                 chips=1,
                                 peaks=roofline.peaks("TPU v5 lite"))


def _read(name, ctx):
    return load_module("metrics", name).read(ctx)


def _steps(t):
    """The steps of a recorded window: its ``bench.step`` spans."""
    return sum(1 for e in t.spans if e.name == "bench.step")


# ------------------------------------------------------------ by hand ---
def test_metadata_is_read_from_the_launch_name():
    e = _mac("dw_update", 0, 10)
    assert tags.metadata(e.text) == {"kind": "dw_update", "r": "5",
                                     "c": "100", "ct": "784", "rp": "128",
                                     "cp": "128", "ctp": "896"}
    assert tags.metadata("%_call.1 = custom-call(), frontend_attributes="
                         "{kernel_metadata={}}") == {}
    assert tags.metadata("%fusion.3 = s32[4] fusion()") == {}
    assert tags.Kind(*tags.DW).search(e.text)
    assert not tags.Kind(*tags.FWD).search(e.text)


def test_kinds_split_the_kernel_time_and_fill_counts_each_launch():
    spans = [_ev("bench.step", 0, 1000)]
    dev = [_mac("fused_fwd", 0, 100), _mac("dx", 100, 110, ct=10,
                                           ctp=16),
           _mac("dw_update", 110, 140, r=784, ct=5, rp=896, ctp=8),
           _ev("%fusion.1 = f32", 140, 150),
           _mac("fwd", 990, 1100)]                    # clipped at 1000
    t = tr.Trace(spans, {"/device:TPU:0": dev})
    ctx = types.SimpleNamespace(trace=t, steps=2)
    assert tags.kind_ms_per_step(ctx, tags.FWD) == pytest.approx(
        1e3 * 110e-9 / 2)
    assert tags.kind_ms_per_step(ctx, tags.DX) == pytest.approx(
        1e3 * 10e-9 / 2)
    assert tags.kind_ms_per_step(ctx, tags.DW) == pytest.approx(
        1e3 * 30e-9 / 2)
    useful = 2 * 5 * 100 * 784 + 5 * 100 * 10 + 784 * 100 * 5
    launched = 2 * 128 * 128 * 896 + 128 * 128 * 16 + 896 * 128 * 8
    assert tags.mac_fill(ctx) == pytest.approx(100.0 * useful / launched)


def test_host_spans_give_time_a_step_and_the_idle_they_hold():
    spans = [_ev("bench.step", 0, 100), _ev("bench.step", 100, 200)]
    t = tr.Trace(spans, {"/device:TPU:0": [_ev("%a.1 = x", 50, 60),
                                           _ev("%b.2 = x", 150, 190)]})
    t.program_spans = [_ev("repro.train_step", 10, 40),
                       _ev("repro.train_step", 110, 130),
                       _ev("repro.other", 0, 200)]
    ctx = types.SimpleNamespace(trace=t, steps=2)
    assert tags.host_ms_per_step(ctx) == pytest.approx(1e3 * 50e-9 / 2)
    # Idle: [0,50) [60,150) [190,200) = 150; inside the step spans: 30+20.
    assert tags.step_idle_share(ctx) == pytest.approx(100.0 * 50 / 150)


def test_readers_find_nothing_where_nothing_is_tagged():
    spans = [_ev("bench.step", 0, 100)]
    t = tr.Trace(spans, {"/device:TPU:0": [
        _ev('%_call.1 = custom-call(), custom_call_target="tpu_custom_'
            'call", frontend_attributes={kernel_metadata={}}', 0, 10)]})
    ctx = types.SimpleNamespace(trace=t, steps=1)
    for name in NEW:
        assert _read(name, ctx) is None, name
    t.program_spans = []
    assert tags.host_ms_per_step(ctx) is None
    assert tags.step_idle_share(ctx) is None


# ----------------------------------------- the trace recorded untagged ---
def test_the_untagged_trace_reads_as_it_did():
    """The metrics and breakdown that were there before the tags read the
    recording made before any launch was tagged exactly as they did then;
    the new ones read nothing."""
    t = _load(UNTAGGED)
    ctx = _ctx(t, _steps(t))
    assert ctx.steps == 2
    assert _read("mlp.mfu", ctx) == 0.00019284793934612262
    assert _read("mlp.mac_ms_per_step", ctx) == 0.396052
    assert _read("mlp.mac_roofline", ctx) == 0.10499848399922128
    assert _read("mlp.idle_share", ctx) == 89.69681934367024
    assert readers.collective_ms_per_step(ctx) is None
    assert (t.lo, t.hi) == (43358757.0, 51771266.0)
    assert t.busy_s() == 0.0008667560000000001
    assert t.count(tr.MAC) == 10
    assert t.matched_s(tr.MAC) == 0.0007921040000000001
    b = t.breakdown()
    assert [v for _, v in b["device_ops"]] == [
        0.0006484700000000001, 7.584800000000001e-05, 4.9217e-05,
        1.1556e-05, 7.0130000000000004e-06, 6.924e-06, 6.921e-06,
        3.4940000000000003e-06, 3.4870000000000002e-06, 2.17e-06]
    assert [k[:24] for k, _ in b["device_ops"]] == [
        "%_call_fused_fwd.2 = (s3", "%_call_fused_fwd.3 = (s3",
        "%_call_dw_update.2 = (s3", "%_call.1 = (s32[128,128]",
        "%_call_dw_update.3 = (s3", "%fusion.36 = s32[400]{0:",
        "%fusion.35 = s32[400]{0:", "%fusion.37 = s32[200]{0:",
        "%fusion.38 = s32[200]{0:", "%copy.77 = s32[784,100]{"]
    assert all(len(k) == 120 for k, _ in b["device_ops"][:9])
    assert b["idle_gaps"] == [
        ["bench.step", 0.004704301], ["bench.sync", 0.0019191800000000001],
        ["bench.step", 0.0009210790000000001],
        ["bench.step", 3.5500000000000004e-07], ["bench.step", 3.53e-07],
        ["bench.step", 2e-09], ["bench.step", 2e-09], ["bench.step", 2e-09],
        ["bench.step", 2e-09], ["bench.step", 2e-09]]
    for name in NEW:
        assert _read(name, ctx) is None, name


# ------------------------------------------- the trace recorded tagged ---
@pytest.fixture(scope="module")
def tagged():
    t = _load(TAGGED)
    return _ctx(t, _steps(t))


def test_the_kinds_add_up_to_the_kernel_time(tagged):
    """Every ⊞-MAC launch is tagged: forward, dX and dW together are the
    launches the name pattern finds, to a nanosecond over the window."""
    parts = sum(_read(n, tagged) for n in ("mlp.fwd_ms_per_step",
                                           "mlp.dx_ms_per_step",
                                           "mlp.dw_ms_per_step"))
    whole = _read("mlp.mac_ms_per_step", tagged)
    assert abs(parts - whole) * 1e-3 * tagged.steps < 1e-9
    assert all(_read(n, tagged) > 0 for n in NEW)


def test_the_fill_is_the_configurations_shapes_over_their_padding(tagged):
    """Each launch (r rows, ct deep, c columns) runs on a grid padded to
    128-wide blocks, the contraction to 128-deep blocks or, when shorter,
    to the 8-row sublane tile (``lns_matmul.tile``)."""
    def pad(dim, align):
        return -(-dim // 128) * 128 if dim > 128 else -(-dim // align) * align

    calls = tagged.counts["mac_calls"]
    useful = sum(r * ct * c for r, ct, c in calls)
    launched = sum(pad(r, 128) * pad(ct, 8) * pad(c, 128)
                   for r, ct, c in calls)
    assert (useful, launched) == (799_000, 17_694_720)
    assert _read("mlp.mac_fill", tagged) == pytest.approx(
        100.0 * useful / launched, rel=1e-12)


def test_program_spans_lie_inside_the_harness_step(tagged):
    t = tagged.trace
    steps = [e for e in t.program_spans if e.name == tags.TRAIN_STEP]
    harness = [e for e in t.spans if e.name == "bench.step"]
    assert len(steps) == len(harness) == tagged.steps
    for e in steps:
        assert any(h.start <= e.start and e.end <= h.end for h in harness)
    share = _read("mlp.step_idle_share", tagged)
    assert 0 < share <= 100
    assert 0 < _read("mlp.host_ms_per_step", tagged) < 1e3 * t.window_s
