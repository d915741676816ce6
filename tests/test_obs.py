"""Telemetry contract tests: metrics never change results.

The obs subsystem's hard contract (ROADMAP §Telemetry): collection is
observer-only.  Pinned here:

* **Bit-parity** — N-step mixed-format (hidden=lns12, out=lns16) training
  produces the exact same weight codes through ``train_step_metrics`` as
  through ``train_step``, on both backends (emulate and pallas), fused
  and unfused; serve drains produce the same greedy outputs with an
  external registry attached as without one.
* **True no-op off** — the plain train step's jaxpr is identical to a
  trace with collection force-suspended: no extra outputs, no extra ops.
* **Pinned vocabulary** — ``DHIST_EDGES`` (committed dhist rows depend on
  them), the rejection-code vocabulary, and the registry row schema.
* **Backend-identical taps** — the Δ-LUT occupancy histogram replays the
  sequential MAC order both backends share, so it is bit-identical
  emulate vs pallas.
"""
import os

import jax
import numpy as np
import pytest

from repro.obs import (DHIST_EDGES, JsonlSink, MetricsRegistry, StepTimer,
                       read_jsonl)
from repro.obs import metrics as _obs
from repro.paper.mlp import LNSMLP, MLPConfig
from repro.serve import (REJECT_CODES, REJECT_DEADLINE_EXPIRED,
                         REJECT_PROMPT_OVER_BUDGET, REJECT_QUEUE_FULL,
                         REJECT_RESERVATION_OVER_POOL, REJECTED, TERMINAL,
                         RequestQueue, ServeConfig, ServingEngine)

B, N_IN, N_OUT = 8, 12, 4


def _mixed_spec(backend):
    return f"lns16-train-{backend};hidden=fmt:lns12,metrics:full"


def _mlp(spec, fused=True):
    return LNSMLP(MLPConfig(n_in=N_IN, n_hidden=9, n_out=N_OUT, lr=0.01,
                            momentum=0.9, spec=spec, matmul_block=8,
                            fused=fused))


def _batches(steps=3, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(B, N_IN)).astype(np.float32),
             rng.integers(0, N_OUT, size=(B,)))
            for _ in range(steps)]


def _train(mlp, with_metrics, steps=3):
    """N steps; returns (params, momentum, losses, per-step host taps)."""
    params = mlp.init(jax.random.PRNGKey(1))
    mom = mlp.init_momentum(params)
    losses, taps_all = [], []
    for xb, yb in _batches(steps):
        if with_metrics:
            (params, mom, loss), taps = mlp.train_step_metrics(
                params, xb, yb, mom)
            taps_all.append(jax.device_get(taps))
        else:
            params, mom, loss = mlp.train_step(params, xb, yb, mom)
        losses.append(float(loss))
    return params, mom, losses, taps_all


def _assert_codes_equal(a, b):
    for k in a:
        np.testing.assert_array_equal(a[k].code, b[k].code, err_msg=k)
        np.testing.assert_array_equal(a[k].sign, b[k].sign, err_msg=k)


# ------------------------------------------------------- pinned surface ---
def test_dhist_edges_pinned():
    # Committed metrics_sample.jsonl dhist rows are bucketed against
    # exactly these edges; changing them invalidates every sample.
    assert DHIST_EDGES == (1.0, 2.0, 4.0, 8.0, 10.0)


def test_reject_code_vocabulary_pinned():
    assert REJECT_CODES == ("queue-full", "prompt-over-budget",
                            "reservation-over-pool", "deadline-expired",
                            "retry-exhausted", "watchdog-abort")


# ----------------------------------------------------------- bit-parity ---
@pytest.mark.parametrize("backend", ["emulate", "pallas"])
@pytest.mark.parametrize("fused", [True, False])
def test_train_parity_metrics_on_off(backend, fused):
    """Mixed lns12/lns16 plan: weight/momentum codes and losses through
    the metrics entry point are bit-identical to the plain step."""
    spec = _mixed_spec(backend)
    p0, m0, l0, _ = _train(_mlp(spec, fused=fused), with_metrics=False)
    p1, m1, l1, taps = _train(_mlp(spec, fused=fused), with_metrics=True)
    _assert_codes_equal(p0, p1)
    _assert_codes_equal(m0, m1)
    assert l0 == l1
    # The metrics lane actually collected something for both layers.
    labels = set(taps[0])
    assert any(k.startswith("hidden/") for k in labels)
    assert any(k.startswith("out/") for k in labels)
    assert "hidden/fwd/dhist" in labels  # metrics=full on hidden


def test_metrics_off_layer_is_silent():
    mlp = _mlp("lns16-train-emulate;out=metrics:off")
    _, _, _, taps = _train(mlp, with_metrics=True, steps=1)
    assert any(k.startswith("hidden/") for k in taps[0])
    assert not any(k.startswith("out/") for k in taps[0])


def test_dhist_identical_across_backends():
    """The Δ-LUT occupancy shadow pass replays the sequential MAC order
    both backends execute bit-identically — so its histogram is too."""
    out = {}
    for backend in ("emulate", "pallas"):
        _, _, _, taps = _train(_mlp(_mixed_spec(backend)),
                               with_metrics=True, steps=2)
        out[backend] = [t["hidden/fwd/dhist"] for t in taps]
    for a, b in zip(out["emulate"], out["pallas"]):
        np.testing.assert_array_equal(a, b)
        assert a.shape == (len(DHIST_EDGES) + 1,)


def test_plain_step_graph_has_no_telemetry():
    """Collection-off is a true no-op: the plain step traces to exactly
    the jaxpr of the same body with collection force-suspended (in which
    every tap site is statically unreachable)."""
    mlp = _mlp(_mixed_spec("emulate"))
    params = mlp.init(jax.random.PRNGKey(1))
    mom = mlp.init_momentum(params)
    xb, yb = _batches(1)[0]

    def plain(p, m, x, y):
        return mlp._step_impl(p, x, y, m)

    def suspended(p, m, x, y):
        with _obs.suspended():
            return mlp._step_impl(p, x, y, m)

    jp = jax.make_jaxpr(plain)(params, mom, xb, yb)
    js = jax.make_jaxpr(suspended)(params, mom, xb, yb)
    assert str(jp) == str(js)
    assert _obs._COLLECTORS == [] and _obs._SCOPES == []


# ------------------------------------------------------ profiler spans ---
def _host_events(trace_dir, name):
    import glob
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    pd = ProfileData.from_file(files[0])
    return [ev for plane in pd.planes if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def test_train_step_opens_one_host_span_per_call(tmp_path):
    """Each call of the train-step entry point is one ``repro.train_step``
    host span in a profiler trace, and tracing changes no result."""
    spec = "lns16-train-pallas"
    mlp = _mlp(spec)
    p_off, m_off, l_off, _ = _train(mlp, with_metrics=False)
    with jax.profiler.trace(str(tmp_path)):
        p_on, m_on, l_on, _ = _train(_mlp(spec), with_metrics=False)
    assert len(_host_events(str(tmp_path), "repro.train_step")) == 3
    _assert_codes_equal(p_off, p_on)
    _assert_codes_equal(m_off, m_on)
    assert l_off == l_on


def test_step_timer_spans_are_host_spans_in_the_profile(tmp_path):
    """``StepTimer`` times and the trace mark the same boundaries, and
    ``maybe_profile`` is the one switch that records a trace."""
    from repro.obs import maybe_profile
    timer = StepTimer()
    with maybe_profile(None) as off:
        with timer.span("train.step"):
            pass
    assert off is None
    with maybe_profile(str(tmp_path / "prof")) as on:
        for _ in range(2):
            with timer.span("train.step"):
                pass
    assert on == str(tmp_path / "prof")
    assert len(timer.samples("train.step")) == 3
    assert len(_host_events(on, "train.step")) == 2


# ------------------------------------------------------- lanes / plan -----
def test_per_layer_interpret_override_resolves_lane():
    """Satellite: per-layer `interpret` rules resolve to distinct lanes,
    and the lane label lands on every metrics row for that layer."""
    mlp = _mlp("lns16-train-pallas;hidden=interpret:off")
    lanes = mlp.lanes()
    assert lanes["hidden"] == "pallas-hw"         # forced off
    assert lanes["out"] == "pallas-interpret"     # auto on CPU
    assert _mlp(_mixed_spec("emulate")).lanes() == {"hidden": "emulate",
                                                    "out": "emulate"}
    reg = MetricsRegistry()
    reg.merge_numerics_taps({"hidden/act/elems": 7, "out/act/elems": 9},
                            lanes=lanes)
    rows = {(r["layer"], r["lane"]) for r in reg.rows()}
    assert rows == {("hidden", "pallas-hw"), ("out", "pallas-interpret")}


# -------------------------------------------------------- registry/sink ---
class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self, tmp_path):
        reg = MetricsRegistry(base_labels={"arch": "t"})
        reg.counter_inc("c", 2, layer="h")
        reg.counter_inc("c", 3, layer="h")
        reg.gauge_set("g", 1.5)
        reg.histogram_record("h", 10.0)
        reg.histogram_record("h", 30.0)
        reg.bucketed_record("b", [1, 2, 3], (0.5, 1.5))
        reg.bucketed_record("b", [1, 0, 1], (0.5, 1.5))  # accumulates
        assert reg.counter_value("c", layer="h") == 5
        rows = reg.rows(reset=True)
        by = {r["name"]: r for r in rows}
        assert by["c"]["value"] == 5 and by["c"]["arch"] == "t"
        assert by["g"]["value"] == 1.5
        assert by["h"]["count"] == 2 and by["h"]["sum"] == 40.0
        assert by["b"]["counts"] == [2, 2, 4]
        # reset clears gauges/histograms, keeps cumulative counters
        names = {r["name"] for r in reg.rows()}
        assert names == {"c", "b"} or names == {"c"}
        # sink round-trip with step stamping
        p = tmp_path / "m.jsonl"
        with JsonlSink(p) as sink:
            sink.write(rows, step=3, loss=1.25)
        back = read_jsonl(p)
        assert len(back) == len(rows)
        assert all(r["step"] == 3 and r["loss"] == 1.25 for r in back)
        assert {r["name"] for r in back} == set(by)

    def test_bucketed_shape_validated(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.bucketed_record("b", [1, 2], (0.5, 1.5))

    def test_malformed_tap_label_raises(self):
        with pytest.raises(ValueError):
            MetricsRegistry().merge_numerics_taps({"no-slashes": 1})

    def test_merge_taps_scalar_and_dhist(self):
        reg = MetricsRegistry()
        reg.merge_numerics_taps(
            {"hidden/fwd/sat": np.int32(4),
             "hidden/fwd/dhist": np.arange(len(DHIST_EDGES) + 1,
                                           dtype=np.int32)})
        assert reg.counter_value("numerics.sat", layer="hidden",
                                 op="fwd") == 4
        rows = [r for r in reg.rows() if r["kind"] == "bucketed_histogram"]
        assert rows[0]["edges"] == list(DHIST_EDGES)

    def test_step_timer_summary(self):
        t = StepTimer()
        for ms in (50.0, 2.0, 3.0):
            t.record("s", ms)
        s = t.summary(skip_first=1)["s"]
        assert s["count"] == 3 and s["best_ms"] == 2.0
        assert s["mean_ms"] == 2.5  # warmup sample dropped


# ---------------------------------------------------------------- serve ---
from repro.nn import init_params  # noqa: E402
from repro.nn.config import ModelConfig  # noqa: E402

TINY = ModelConfig(name="tiny-obs", family="dense", n_layers=2,
                   d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                   vocab_size=64, d_head=16, vocab_pad_to=64,
                   numerics="fp32", param_dtype="float32", remat="none",
                   q_chunk=8)


@pytest.fixture(scope="module")
def tiny():
    return TINY, init_params(jax.random.PRNGKey(0), TINY)


def _serve_prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 64, size=int(rng.integers(2, 7)))
            for _ in range(n)]


class TestServeTelemetry:
    def test_drain_outputs_unchanged_by_registry(self, tiny):
        cfg, params = tiny
        sc = ServeConfig(max_batch=2, max_len=32, block_size=8,
                         prefill_chunk=8)
        prompts = _serve_prompts(4)
        base = ServingEngine(cfg, params, sc).run(prompts, max_new=6)
        reg = MetricsRegistry(base_labels={"component": "serve"})
        eng = ServingEngine(cfg, params, sc, registry=reg)
        assert eng.run(prompts, max_new=6) == base
        # ... and the registry actually observed the drain.
        assert reg.counter_value("serve.requests_finished") == 4
        assert reg.counter_value("serve.tokens_out") == sum(
            len(o) for o in base)
        assert len(reg.histogram_values("serve.latency_ms")) == 4
        assert len(reg.histogram_values("serve.ttft_ms")) == 4
        kinds = {r["name"] for r in reg.rows()}
        assert "serve.queue_depth" in kinds
        assert eng.stats["stall_steps"] == 0

    def test_rejection_counter_queue_full(self, tiny):
        cfg, params = tiny
        eng = ServingEngine(cfg, params,
                            ServeConfig(max_batch=1, max_len=32,
                                        block_size=8, prefill_chunk=8,
                                        max_queue=1))
        eng.submit([3, 4], max_new=2)
        rid = eng.submit([5, 6], max_new=2)
        req = eng.poll(rid)
        assert req.state == REJECTED and req.reason == "queue full"
        assert req.reason_code == REJECT_QUEUE_FULL
        assert eng.queue.rejections[REJECT_QUEUE_FULL] == 1
        assert eng.registry.counter_value(
            "serve.rejected", reason=REJECT_QUEUE_FULL) == 1

    def test_rejection_counter_prompt_over_budget(self, tiny):
        cfg, params = tiny
        eng = ServingEngine(cfg, params,
                            ServeConfig(max_batch=2, max_len=16,
                                        block_size=8, prefill_chunk=8))
        rid = eng.submit(np.full((20,), 5, np.int32), max_new=2)
        req = eng.poll(rid)
        assert req.state == REJECTED
        assert "prompt exceeds max_len" in req.reason
        assert req.reason_code == REJECT_PROMPT_OVER_BUDGET
        assert eng.queue.rejections[REJECT_PROMPT_OVER_BUDGET] == 1
        assert eng.registry.counter_value(
            "serve.rejected", reason=REJECT_PROMPT_OVER_BUDGET) == 1

    def test_rejection_counter_reservation_over_pool(self, tiny):
        cfg, params = tiny
        eng = ServingEngine(cfg, params,
                            ServeConfig(max_batch=2, max_len=64,
                                        block_size=8, prefill_chunk=8,
                                        num_blocks=3))
        rid = eng.submit(np.full((30,), 5, np.int32), max_new=30)
        req = eng.poll(rid)
        assert req.state == REJECTED
        assert "reservation exceeds pool" in req.reason
        assert req.reason_code == REJECT_RESERVATION_OVER_POOL
        assert eng.queue.rejections[REJECT_RESERVATION_OVER_POOL] == 1
        assert eng.registry.counter_value(
            "serve.rejected", reason=REJECT_RESERVATION_OVER_POOL) == 1

    def test_rejection_counter_deadline_expired(self, tiny):
        cfg, params = tiny
        eng = ServingEngine(cfg, params,
                            ServeConfig(max_batch=1, max_len=32,
                                        block_size=8, prefill_chunk=8))
        # Fill the only slot, then queue one with an immediate deadline.
        eng.submit([3, 4, 5], max_new=8)
        eng.step()
        rid = eng.submit([6, 7], max_new=2, deadline_steps=0)
        eng.step()
        req = eng.poll(rid)
        assert req.state == REJECTED and "deadline" in req.reason
        assert req.reason_code == REJECT_DEADLINE_EXPIRED
        assert eng.queue.rejections[REJECT_DEADLINE_EXPIRED] == 1
        assert eng.registry.counter_value(
            "serve.rejected", reason=REJECT_DEADLINE_EXPIRED) == 1

    def test_queue_level_counters_direct(self):
        q = RequestQueue(max_depth=1)
        q.submit([1], 2, None, 0)
        r2 = q.submit([2], 2, None, 0)
        assert r2.reason_code == REJECT_QUEUE_FULL
        r3 = q.submit([3], 2, 0, 0)  # wait: depth cap hit again
        assert r3.reason_code == REJECT_QUEUE_FULL
        assert q.rejections[REJECT_QUEUE_FULL] == 2
        # unknown code refused — the vocabulary is closed
        with pytest.raises(ValueError):
            q.reject(q.peek(), "nope", 1, "not-a-code")
        expired = q.expire(5)  # head request has no deadline
        assert expired == []
        q2 = RequestQueue(max_depth=4)
        r = q2.submit([1], 2, 0, 0)
        assert q2.expire(2) == [r]
        assert r.reason == "deadline exceeded while queued"
        assert q2.rejections[REJECT_DEADLINE_EXPIRED] == 1


# --------------------------------------------------------------- report ---
def test_metrics_report_renders_committed_sample(capsys):
    sample = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                          "baselines", "metrics_sample.jsonl")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metrics_report", os.path.join(os.path.dirname(sample), "..",
                                       "metrics_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    per = mod.report(sample)
    assert ("hidden", "fwd") in per and "dhist" in per[("hidden", "fwd")]
    assert per[("out", "logits")]["elems"] > 0
    out = capsys.readouterr().out
    assert "Δ-LUT occupancy" in out and "serve.rejected" in out
