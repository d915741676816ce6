"""The per-layer metrics of ``qwen3-1.7b.train-lut20``, read from a traced
window of the cell recorded on a TPU v5e (its 3 traced steps through
``bench/run.py --trace 1``, the ``.xplane.pb`` gzipped): each reads a
number, and the numbers agree with each other and with the
configuration's counts."""
import gzip
import json
import os
import types

import pytest

import roofline
import tags
import trace as tr
import traffic
from common import ROOT, load_json, load_module

CELL = "qwen3-1.7b.train-lut20"
PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces",
                    CELL + ".xplane.pb.gz")
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
METRICS = [m["name"] for m in BENCHMARK["per_layer"]
           if CELL in m.get("workloads", [])]
UNIT = {m["name"]: m["unit"] for m in BENCHMARK["per_layer"]}


@pytest.fixture(scope="module")
def ctx():
    from jax.profiler import ProfileData
    with open(PATH, "rb") as f:
        t = tr.Trace.from_profile(
            ProfileData.from_serialized_xspace(gzip.decompress(f.read())))
    w = load_json("workloads", CELL + ".json")
    c = load_json("configs", w["config"] + ".json")
    counts = load_module("configs", w["config"]).counts(
        c, traffic.make(w["traffic"], c, 0), w["chips"])
    steps = sum(1 for e in t.spans if e.name == "bench.step")
    return types.SimpleNamespace(trace=t, steps=steps, counts=counts,
                                 chips=w["chips"],
                                 peaks=roofline.peaks("TPU v5 lite"))


def _read(name, ctx):
    return load_module("metrics", name).read(ctx)


def test_the_cell_lists_the_lm_metrics():
    assert sorted(METRICS) == sorted(
        ["lm.mfu", "lm.mac_ms_per_step", "lm.mac_roofline",
         "lm.fwd_ms_per_step", "lm.dx_ms_per_step", "lm.dw_ms_per_step",
         "lm.idle_share"])


@pytest.mark.parametrize("name", METRICS)
def test_each_metric_reads_a_number(ctx, name):
    v = _read(name, ctx)
    assert isinstance(v, float) and v > 0, (name, v)
    if UNIT[name] == "%":
        assert v <= 100.0, (name, v)


def test_the_window_holds_the_cells_steps(ctx):
    """One chip, three closed-loop steps, each with its loss read back,
    and the device busy in between."""
    t = ctx.trace
    assert list(t.devices) == ["/device:TPU:0"]
    assert ctx.steps == load_json("workloads", CELL + ".json")["trace_steps"]
    assert sum(1 for e in t.spans if e.name == "bench.loss_read") == \
        ctx.steps
    assert 0 < t.busy_s() < t.window_s


def test_the_kinds_add_up_to_the_kernel_time(ctx):
    """Every ⊞-MAC launch is tagged: forward, dX and dW together are the
    launches the name pattern finds, and one launch per product and
    projection runs a step."""
    parts = sum(_read(f"lm.{k}_ms_per_step", ctx) for k in ("fwd", "dx",
                                                           "dw"))
    whole = _read("lm.mac_ms_per_step", ctx)
    assert abs(parts - whole) * 1e-3 * ctx.steps < 1e-9
    launches = ctx.trace.count(tags.Kind(*tags.MAC_KINDS))
    assert launches == ctx.steps * len(ctx.counts["mac_calls"])
    assert launches == ctx.trace.count(tr.MAC)
