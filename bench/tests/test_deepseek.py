"""``deepseek-v2-lite.train-lut20`` on the CPU at a small size, and its
per-layer metrics read from a traced window recorded on a TPU v5e (its 2
traced steps through ``bench/run.py --trace 1``; the ``.xplane.pb`` cut to
what the readers use, the device's ``XLA Ops`` line with its events'
metadata and the host plane, then gzipped).

A sound run of the program reads correct; the program's own 12-bit LNS
path in place of the 16-bit one, and the program with one held expert's
output left out of every MoE layer, read not correct.  The step reports
the rows each held expert computed and the assignments it dropped.
"""
import gzip
import json
import os
import types

import numpy as np
import pytest

import grouped
import roofline
import run
import tags
import trace as tr
import traffic
from common import ROOT, load_json, load_module

CELL = "deepseek-v2-lite.train-lut20"
#: Sizes a CPU holds: the block at small widths, the dense layer and two
#: MoE layers, 64 routed experts of which 16 are held, top-6, as
#: published.  With the published router the gates of a token's experts
#: are small (~1/30), as at full size; with 8 experts the top-6 gates are
#: ~1/8 and one routing decision that flips on a float32 rounding moves
#: the gradients by up to ~0.1.
SMALL = {"hidden_size": 256, "intermediate_size": 512,
         "moe_intermediate_size": 64, "num_attention_heads": 2,
         "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
         "kv_lora_rank": 64, "vocab_size": 512, "num_hidden_layers": 3}
SEED = 2**33 + 17
TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traces",
                     CELL + ".xplane.pb.gz")


@pytest.mark.parametrize("variant,correct", [(None, True),
                                             ("control", False),
                                             ("drop_expert", False)])
def test_the_cell_runs_on_the_cpu(variant, correct):
    r = run.run_cell(CELL, SEED, 0.2, False, variant=variant,
                     require_compiled=False, config_overrides=SMALL)
    assert r["correct"] is correct, r["checks"]


def test_the_step_reports_its_expert_counters():
    """Rows per held expert and MoE layer, and assignments dropped: 0,
    with every assignment to a held expert computed."""
    c = {**load_json("configs", "deepseek-v2-lite.json"), **SMALL}
    cell = load_json("workloads", CELL + ".json")
    mod = load_module("configs", "deepseek-v2-lite")
    gen = traffic.make(cell["traffic"], c, SEED)
    system = mod.System(c, cell, SEED)
    system.step(gen.batch(0))
    routed = np.asarray(system.counters["moe/routed"])
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    assert routed.shape == (n_moe, c["experts_held"])
    # Each token picks 6 distinct experts of 64: a held expert gets at
    # most one row a token.
    assert 0 < routed.sum() and routed.max() <= gen.items_per_step
    assert np.asarray(system.counters["moe/dropped"]).tolist() == [0] * n_moe


def test_the_counts_follow_the_configuration():
    """790 G ⊞-MACs a step: every projection's forward, dX and dW over the
    1,024 tokens, and the routed experts' over the expected routed rows,
    1,024 · 6 · 16 / 64 = 1,536 a layer, as 36 grouped launches."""
    w = load_json("workloads", CELL + ".json")
    c = load_json("configs", w["config"] + ".json")
    k = load_module("configs", w["config"]).counts(
        c, traffic.make(w["traffic"], c, 0), w["chips"])
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    plain = 5 * attn + 3 * 2048 * 10944 + 4 * 3 * 2048 * 2816
    gate_up, down = (1536, 2048, 1408, 16), (1536, 1408, 2048, 16)
    assert k["gmm_calls"] == ([gate_up] * 6 + [down] * 3) * 4
    assert k["macs"] == 3 * 1024 * plain + 36 * 1536 * 2048 * 1408
    assert round(k["macs"] / 1e9, 1) == 790.0
    pk = roofline.peaks("TPU v5 lite")
    one = max(2 * 1536 * 2048 * 1408 / pk["bf16_flops"],
              2 * (1536 * 2048 + 16 * 2048 * 1408 + 1536 * 1408)
              / pk["hbm_bytes_per_s"])
    assert grouped.least_s(k["gmm_calls"], pk) == pytest.approx(36 * one)


@pytest.fixture(scope="module")
def ctx():
    from jax.profiler import ProfileData
    with open(TRACE, "rb") as f:
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


def test_the_recorded_trace_is_small():
    assert os.path.getsize(TRACE) < 500_000


def test_the_moe_metrics_read_a_recorded_trace(ctx):
    """Both metrics read a number; the grouped launches are 36 a step
    (forward, dX and dW of three projections in four MoE layers), a part
    of the device's busy time, and under their roofline."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = [m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [])]
    assert sorted(names) == ["moe.gmm_ms_per_step", "moe.gmm_roofline"]
    ms = load_module("metrics", "moe.gmm_ms_per_step").read(ctx)
    roof = load_module("metrics", "moe.gmm_roofline").read(ctx)
    assert 0 < ms < 1e3 * ctx.trace.busy_s() / ctx.steps
    assert 0 < roof < 100
    kinds = tags.Kind("gmm_fwd", "gmm_dx", "gmm_dw")
    assert ctx.trace.count(kinds) == 36 * ctx.steps
    assert ctx.steps == load_json("workloads", CELL + ".json")["trace_steps"]


def test_the_plain_kernel_readers_leave_the_grouped_launches_out(ctx):
    """``bench/trace.py: MAC`` matches the plain ⊞-MAC launches by the
    name of their wrapper; the grouped launches, made by another
    wrapper, are never among them."""
    t = ctx.trace
    gmm = tags.Kind(*grouped.KINDS)
    assert t.count(tr.MAC) > 0
    assert not any(tr.MAC.search(e.text) and gmm.search(e.text)
                   for evs in t.devices.values() for e in evs)
