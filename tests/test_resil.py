"""Resilience contract tests: faults are injected, never accidental.

The resil subsystem's hard contracts (ROADMAP §Contracts):

* **No plan ⇒ no op.**  With no `FaultPlan` active (and guardrails
  disabled), traced graphs, trained weight codes, and greedy serve
  outputs are bit-identical to the fault-free build on both lanes.
* **Deterministic.**  The same plan + seed reproduces the same faults
  byte-for-byte, identically on the emulate and pallas lanes.
* **Recovery preserves numerics.**  DP device-drop recovery recombines
  bit-identical to the undamaged run; format widening is a plan
  override + exact code conversion; serve aborts extend `REJECT_CODES`
  append-only and never leak KV blocks.
* **Crash safety.**  Checkpoint writes are atomic (torn dirs rejected
  loudly), corrupt autotune caches are quarantined, JSONL sinks flush
  per row and the tolerant reader drops only the torn tail.
"""
import json
import os
import shutil
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DELTA_DEFAULT, LNS16, DeltaEngine, encode
from repro.core.delta import DeltaSpec
from repro.paper.mlp import LNSMLP, MLPConfig, PARAM_LAYER, make_mlp
from repro.resil import (FaultPlan, GuardConfig, GuardedTrainer,
                         SnapshotRing, corrupt_engine, detect, fault_plan,
                         inject_codes, inject_segment_partials, injecting,
                         recover_segment_partials, shrink)
from repro.resil import inject as _inj

B, N_IN, N_OUT = 8, 12, 4


def _mlp_cfg(spec, faults=None):
    return MLPConfig(n_in=N_IN, n_hidden=9, n_out=N_OUT, lr=0.01,
                     momentum=0.9, spec=spec, matmul_block=8,
                     faults=faults)


def _batches(steps=3, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.normal(size=(B, N_IN)).astype(np.float32),
             rng.integers(0, N_OUT, size=(B,)))
            for _ in range(steps)]


def _assert_codes_equal(a, b):
    for k in a:
        np.testing.assert_array_equal(a[k].code, b[k].code, err_msg=k)
        np.testing.assert_array_equal(a[k].sign, b[k].sign, err_msg=k)


def _train_plain(m, steps=3, seed=0):
    params = m.init(jax.random.PRNGKey(1))
    mom = m.init_momentum(params)
    for xb, yb in _batches(steps, seed):
        params, mom, _ = m.train_step(params, xb, yb, mom)
    return params, mom


def _train_faults(m, steps=3, seed=0):
    params = m.init(jax.random.PRNGKey(1))
    mom = m.init_momentum(params)
    for i, (xb, yb) in enumerate(_batches(steps, seed)):
        (params, mom, _), _ = m.train_step_metrics(params, xb, yb, mom,
                                                   step=jnp.int32(i))
    return params, mom


# ------------------------------------------------------ FaultPlan surface --
class TestFaultPlan:
    def test_roundtrip_lossless(self):
        s = ("seed=42,start=3,stop=5;hidden=flip_w:0.001,sat_lanes:2;"
             "out=lut:3;serve=hang_step:7,slow_req:2")
        p = FaultPlan.parse(s)
        assert str(p) == s
        assert FaultPlan.parse(str(p)) == p

    def test_value_canonicalization(self):
        # flip_w:1e-3 re-serializes as 0.001 — equality is semantic.
        assert (FaultPlan.parse("seed=1;hidden=flip_w:1e-3")
                == FaultPlan.parse("seed=1;hidden=flip_w:0.001"))

    def test_none_and_empty_pass_through(self):
        assert FaultPlan.parse(None) is None
        assert FaultPlan.parse("") is None
        p = FaultPlan.parse("seed=1;hidden=lut:1")
        assert FaultPlan.parse(p) is p

    def test_default_head_omitted(self):
        assert str(FaultPlan.parse("seed=0;hidden=lut:1")) \
            == "seed=0;hidden=lut:1"

    def test_resolve_precedence_later_wins(self):
        p = FaultPlan.parse("seed=0;*=flip_w:0.5;hidden=flip_w:0.25")
        assert p.resolve("hidden") == {"flip_w": 0.25}
        assert p.resolve("out") == {"flip_w": 0.5}

    @pytest.mark.parametrize("bad", [
        "seed=0;hidden=nosuch:1",          # unknown kind
        "seed=0;hidden=flip_w:0.1,flip_w:0.2",  # duplicate kind
        "seed=0;hidden=flip_w:2.0",        # rate out of (0, 1]
        "seed=0;hidden=sat_lanes:0",       # count below minimum
        "seed=0;hidden=",                  # rule without faults
        "bogus;hidden=lut:1",              # malformed head
        "seed=0,seed=1;hidden=lut:1",      # duplicate head key
        "seed=0,start=5,stop=3;hidden=lut:1",  # stop <= start
    ])
    def test_malformed_plans_raise(self, bad):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)

    def test_validate_paths_catches_typos(self):
        p = FaultPlan.parse("seed=0;hiden=flip_w:0.1")
        with pytest.raises(ValueError, match="match no layer path"):
            p.validate_paths(("hidden", "out", "serve"))
        with pytest.raises(ValueError, match="match no layer path"):
            LNSMLP(_mlp_cfg("lns16-train-emulate",
                            faults="seed=0;hiden=flip_w:0.1"))

    def test_fault_plan_convenience(self):
        p = fault_plan({"hidden": "drop_seg:2"}, seed=9)
        assert p.seed == 9 and p.resolve("hidden") == {"drop_seg": 2}


# ------------------------------------------------------- no-op contract ---
@pytest.mark.parametrize("backend", ["emulate", "pallas"])
def test_noop_graph_identical(backend):
    """No active plan ⇒ the step trace is the fault-free graph, op for
    op (the telemetry-contract analogue for injection)."""
    m = LNSMLP(_mlp_cfg(f"lns16-train-{backend}"))
    params = m.init(jax.random.PRNGKey(1))
    mom = m.init_momentum(params)
    xb, yb = _batches(1)[0]

    def plain(p, x, y, mo):
        return m._step_impl(p, x, y, mo)

    def wrapped(p, x, y, mo):
        with injecting(None):
            return m._step_impl(p, x, y, mo)

    jp = jax.make_jaxpr(plain)(params, xb, yb, mom)
    jw = jax.make_jaxpr(wrapped)(params, xb, yb, mom)
    assert str(jp) == str(jw)


@pytest.mark.parametrize("backend", ["emulate", "pallas"])
def test_train_parity_no_plan(backend):
    """cfg.faults=None: the metrics step keyed by ``step`` trains
    bit-identically to the plain step (the step arg is unused)."""
    spec = f"lns16-train-{backend};hidden=fmt:lns12"
    p0, m0 = _train_plain(LNSMLP(_mlp_cfg(spec)))
    p1, m1 = _train_faults(LNSMLP(_mlp_cfg(spec)))
    _assert_codes_equal(p0, p1)
    _assert_codes_equal(m0, m1)


def test_guarded_trainer_all_off_is_plain_training():
    """Guardrails disabled ⇒ GuardedTrainer is a metrics loop: same
    trained codes as driving the step by hand."""
    spec = "lns16-train-emulate"
    m = LNSMLP(_mlp_cfg(spec))
    params = m.init(jax.random.PRNGKey(1))
    mom = m.init_momentum(params)
    t = GuardedTrainer(m, params, mom,
                       guard=GuardConfig(rollback=False, widen=False))
    t.run(_batches(3))
    p0, m0 = _train_plain(LNSMLP(_mlp_cfg(spec)))
    _assert_codes_equal(t.params, p0)
    _assert_codes_equal(t.momentum, m0)
    assert t.events == []


def test_serve_outputs_no_plan():
    """ServingEngine(faults=None) drains identically to the default."""
    from repro.nn import init_params
    from repro.serve import ServeConfig, ServingEngine
    cfg, params = _tiny_lm()
    sc = ServeConfig(max_batch=2, max_len=32, block_size=8,
                     prefill_chunk=8)
    prompts = _prompts(3)
    base = ServingEngine(cfg, params, sc).run(prompts, max_new=6)
    assert ServingEngine(cfg, params, sc, faults=None).run(
        prompts, max_new=6) == base


# ------------------------------------------- determinism + lane identity --
def test_bitflip_deterministic_and_lane_identical():
    plans = "seed=5,start=1;hidden=flip_w:0.3,flip_act:0.1"
    runs = {}
    for backend in ("emulate", "pallas"):
        spec = f"lns16-train-{backend}"
        a, _ = _train_faults(LNSMLP(_mlp_cfg(spec, plans)), steps=2)
        b, _ = _train_faults(LNSMLP(_mlp_cfg(spec, plans)), steps=2)
        _assert_codes_equal(a, b)  # same plan ⇒ same faults, re-run
        runs[backend] = a
    # Injection sites sit on the code tensors both lanes share.
    _assert_codes_equal(runs["emulate"], runs["pallas"])


def test_window_gates_injection():
    """Steps before the window are bit-identical to fault-free."""
    spec = "lns16-train-emulate"
    plan = "seed=5,start=1;hidden=flip_w:0.3"
    clean = LNSMLP(_mlp_cfg(spec))
    faulted = LNSMLP(_mlp_cfg(spec, plan))
    params = clean.init(jax.random.PRNGKey(1))
    mom = clean.init_momentum(params)
    xb, yb = _batches(1)[0]
    pc, _, _ = clean.train_step(params, xb, yb, mom)
    (p0, _, _), _ = faulted.train_step_metrics(params, xb, yb, mom,
                                               step=jnp.int32(0))
    (p1, _, _), _ = faulted.train_step_metrics(params, xb, yb, mom,
                                               step=jnp.int32(1))
    _assert_codes_equal(p0, pc)  # step 0 < start: untouched
    assert any(not np.array_equal(p1[k].code, pc[k].code) for k in pc)


def test_fault_plan_without_step_raises():
    """A model that carries a FaultPlan cannot run its metrics step
    unkeyed: the faults would have no step to fire on."""
    m = LNSMLP(_mlp_cfg("lns16-train-emulate",
                        "seed=5,start=1;hidden=flip_w:0.3"))
    params = m.init(jax.random.PRNGKey(1))
    xb, yb = _batches(1)[0]
    with pytest.raises(ValueError, match="step="):
        m.train_step_metrics(params, xb, yb, m.init_momentum(params))


def test_sat_lanes_pin_to_code_max():
    plan = FaultPlan.parse("seed=3;hidden=sat_lanes:2")
    a = encode(np.random.default_rng(0).normal(
        size=(4, 6)).astype(np.float32), LNS16)
    with injecting(plan):
        out = inject_codes(a, LNS16, layer="hidden")
        out2 = inject_codes(a, LNS16, layer="hidden")
    pinned = np.where(
        (np.asarray(out.code) == LNS16.code_max).all(axis=0))[0]
    assert len(pinned) == 2  # exactly the chosen lanes
    assert (np.asarray(out.sign)[:, pinned] == 0).all()
    np.testing.assert_array_equal(out.code, out2.code)  # host-static pick
    untouched = [c for c in range(6) if c not in pinned]
    np.testing.assert_array_equal(np.asarray(out.code)[:, untouched],
                                  np.asarray(a.code)[:, untouched])


def test_inject_helpers_return_input_object_when_inactive():
    a = encode(np.float32(1.5), LNS16)
    assert inject_codes(a, LNS16, layer="hidden") is a  # no plan at all
    plan = FaultPlan.parse("seed=0;out=sat_lanes:1")
    with injecting(plan):
        assert inject_codes(a, LNS16, layer="hidden") is a  # no rule match


def test_lut_corruption_deterministic_and_copy_on_write():
    eng = DeltaEngine(DELTA_DEFAULT, LNS16)
    before = np.array(eng._tab_plus)
    plan = FaultPlan.parse("seed=11;hidden=lut:3")
    c1 = corrupt_engine(eng, plan, "hidden")
    c2 = corrupt_engine(eng, plan, "hidden")
    assert c1 is not eng
    np.testing.assert_array_equal(c1._tab_plus, c2._tab_plus)
    np.testing.assert_array_equal(c1._tab_minus, c2._tab_minus)
    assert not np.array_equal(c1._tab_plus, before)
    np.testing.assert_array_equal(eng._tab_plus, before)  # shared: untouched
    assert int(c1._tab_minus[0]) == int(eng._tab_minus[0])  # flush sentinel
    # Values stay inside the live table range (wrong, not out-of-domain).
    assert c1._tab_plus.min() >= before.min()
    assert c1._tab_plus.max() <= before.max()
    # No rule for this layer / tableless engines: same object back.
    assert corrupt_engine(eng, plan, "out") is eng
    bs = DeltaEngine(DeltaSpec(kind="bitshift"), LNS16)
    assert corrupt_engine(bs, plan, "hidden") is bs


def test_segment_drop_and_dup():
    m = make_mlp("lns", _mlp_cfg(
        "lns16-train-emulate,reduce.grad_segments=4"))
    inner = m.inner
    params = inner.init(jax.random.PRNGKey(1))
    xb, yb = _batches(1)[0]
    parts, _ = inner.per_segment_grads(params, xb, yb, 4)
    plan = fault_plan({"hidden": "drop_seg:1", "out": "dup_seg:2"}, seed=0)
    with injecting(plan):
        out = inject_segment_partials(
            parts, param_fmts=inner.param_fmts, param_layer=PARAM_LAYER,
            segs_local=4)
    zc = inner.param_fmts["w1"].zero_code
    assert (np.asarray(out["w1"].code[1]) == zc).all()       # dropped
    assert (np.asarray(out["w1"].sign[1]) == 0).all()
    np.testing.assert_array_equal(out["w1"].code[0], parts["w1"].code[0])
    np.testing.assert_array_equal(out["w2"].code[3],          # dup: 3 := 2
                                  parts["w2"].code[2])
    np.testing.assert_array_equal(out["w2"].code[2], parts["w2"].code[2])


# ----------------------------------------------------------- guardrails ---
def test_detect_saturation_storm_and_loss_alerts():
    cfg = GuardConfig(sat_frac=0.25, flush_frac=0.5)
    taps = {"hidden/act/sat": np.int32(30), "hidden/act/elems": np.int32(100),
            "out/act/sat": np.int32(10), "out/act/elems": np.int32(100),
            "out/q/q_flush": np.int32(60), "out/q/q_elems": np.int32(100)}
    alerts = detect(taps, 1.0, cfg, recent_losses=[1.0, 1.1], step=7)
    kinds = {(a.kind, a.layer) for a in alerts}
    assert ("saturation-storm", "hidden") in kinds
    assert ("zero-flush-spike", "out") in kinds
    assert ("saturation-storm", "out") not in kinds  # 10% < 25%
    assert [a.step for a in alerts] == [7] * len(alerts)
    assert any(a.kind == "nonfinite-loss"
               for a in detect({}, float("nan"), cfg))
    assert any(a.kind == "loss-spike"
               for a in detect({}, 50.0, cfg, recent_losses=[1.0, 1.2]))
    assert not detect({}, 1.3, cfg, recent_losses=[1.0, 1.2])


def test_snapshot_ring_bounded():
    ring = SnapshotRing(2)
    for i in range(5):
        ring.push(i, {"w": np.full((2,), i)})
    assert len(ring) == 2
    step, (p, mom, rng) = ring.latest()
    assert step == 4 and mom is None and rng is None
    np.testing.assert_array_equal(p["w"], [4, 4])


def test_rollback_restores_snapshot():
    """A loss alert rolls params/momentum back to the pre-step snapshot
    (loss_abs=0 makes every detected step alert — pure mechanics test)."""
    m = LNSMLP(_mlp_cfg("lns16-train-emulate"))
    params = m.init(jax.random.PRNGKey(1))
    mom = m.init_momentum(params)
    t = GuardedTrainer(m, params, mom,
                       guard=GuardConfig(loss_abs=0.0, widen=False,
                                         cooldown=0))
    (xb, yb) = _batches(1)[0]
    r = t.step(xb, yb)
    assert r["action"] == "rollback"
    assert [a.kind for a in r["alerts"]] == ["loss-spike"]
    _assert_codes_equal(t.params, params)  # update discarded
    _assert_codes_equal(t.momentum, mom)
    assert t.events[-1]["action"] == "rollback"
    assert t.registry.counter_value("guard.rollbacks") == 1


def test_widen_on_saturation_storm():
    """A stuck-lane storm in an lns12 layer widens it to lns16 via a plan
    override; training continues under the widened model."""
    spec = "lns16-train-emulate;hidden=fmt:lns12,metrics:full"
    m = make_mlp("lns", _mlp_cfg(spec, "seed=7,start=2;hidden=sat_lanes:4"))
    params = m.init(jax.random.PRNGKey(1))
    t = GuardedTrainer(m, params, m.init_momentum(params),
                       guard=GuardConfig(sat_frac=0.10))
    results = t.run(_batches(4))
    widen = [e for e in t.events if e["action"] == "widen"]
    assert widen and widen[0]["layer"] == "hidden"
    assert "hidden=fmt:lns16" in widen[0]["plan_after"]
    assert t.model.fmts["hidden"].qf == 10  # rebuilt under lns16
    assert any("widen" in (r["action"] or "") for r in results)
    # Codes were converted exactly: momentum/params parse under new fmt.
    assert t.params["w1"].code.dtype == np.int32


def test_widen_noop_when_already_wide():
    m = LNSMLP(_mlp_cfg("lns16-train-emulate"))
    params = m.init(jax.random.PRNGKey(1))
    t = GuardedTrainer(m, params, m.init_momentum(params))
    assert t._widen("hidden") is False
    assert t.events == []


# --------------------------------------------- DP device-drop recovery ----
def test_device_drop_recovery_bit_identical():
    """Lost segment partials recomputed from their own batch rows and
    recombined on the fixed schedule == the undamaged combine, bit for
    bit (the device-count-invariance contract extended to loss)."""
    from repro.distributed.lns_reduce import combine_partials
    m = make_mlp("lns", _mlp_cfg(
        "lns16-train-emulate,reduce.grad_segments=4"))
    inner = m.inner
    params = inner.init(jax.random.PRNGKey(1))
    xb, yb = _batches(1)[0]
    parts, _ = inner.per_segment_grads(params, xb, yb, 4)
    plan = fault_plan({"*": "drop_seg:2"}, seed=0)
    with injecting(plan):
        bad = inject_segment_partials(
            parts, param_fmts=inner.param_fmts, param_layer=PARAM_LAYER,
            segs_local=4)
    recovered = recover_segment_partials(inner, params, xb, yb, bad,
                                         grad_segments=4, lost=[2])
    reference = {k: combine_partials(g, inner.param_engines[k])
                 for k, g in parts.items()}
    _assert_codes_equal(recovered, reference)


def test_recover_validates_inputs():
    m = make_mlp("lns", _mlp_cfg(
        "lns16-train-emulate,reduce.grad_segments=4"))
    inner = m.inner
    params = inner.init(jax.random.PRNGKey(1))
    xb, yb = _batches(1)[0]
    parts, _ = inner.per_segment_grads(params, xb, yb, 4)
    with pytest.raises(ValueError, match="not divisible"):
        recover_segment_partials(inner, params, xb[:6], yb[:6], parts,
                                 grad_segments=4, lost=[0])
    with pytest.raises(ValueError, match="out of range"):
        recover_segment_partials(inner, params, xb, yb, parts,
                                 grad_segments=4, lost=[4])


def test_shrink_rebuilds_dp_model():
    m = make_mlp("lns", _mlp_cfg(
        "lns16-train-emulate,reduce.grad_segments=4"))
    s = shrink(m, 1)
    assert type(s) is type(m) and s.dp.num_devices == 1
    with pytest.raises(TypeError):
        shrink(LNSMLP(_mlp_cfg("lns16-train-emulate")), 1)


# ----------------------------------------------------- crash-safe ckpt ----
def test_checkpoint_atomic_overwrite_and_torn_rejection(tmp_path):
    from repro.ckpt import latest_step, load_checkpoint, save_checkpoint
    d = str(tmp_path)
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    save_checkpoint(d, 1, tree)
    # Overwrite in place: the rename dance must handle an existing final
    # dir and leave no .tmp / .old.tmp litter behind.
    tree2 = {"w": np.ones((2, 3), np.float32)}
    save_checkpoint(d, 1, tree2)
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]
    out = load_checkpoint(d, 1, tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), tree2["w"])

    # Kill-mid-write: a torn dir (no manifest) is never a checkpoint.
    os.makedirs(os.path.join(d, "step_00000002"))
    np.save(os.path.join(d, "step_00000002", "leaf_0.npy"), tree["w"])
    assert latest_step(d) == 1  # torn dir invisible to discovery
    with pytest.raises(ValueError, match="torn/partial"):
        load_checkpoint(d, 2, tree)

    # Torn manifest (killed mid-json-write) is rejected loudly too.
    with open(os.path.join(d, "step_00000002", "manifest.json"), "w") as f:
        f.write('{"step": 2, "n_le')
    with pytest.raises(ValueError, match="torn/partial"):
        load_checkpoint(d, 2, tree)

    # Missing leaf file (manifest promises more than is on disk).
    save_checkpoint(d, 3, tree)
    os.remove(os.path.join(d, "step_00000003", "leaf_0.npy"))
    with pytest.raises(ValueError, match="leaf_0.npy"):
        load_checkpoint(d, 3, tree)


def test_checkpoint_survives_stale_intermediate_dirs(tmp_path):
    """Crash between the two renames leaves .tmp/.old.tmp dirs; the next
    save completes and the latest checkpoint is never ambiguous."""
    from repro.ckpt import latest_step, load_checkpoint, save_checkpoint
    d = str(tmp_path)
    tree = {"w": np.zeros((2,), np.float32)}
    save_checkpoint(d, 1, tree)
    os.makedirs(os.path.join(d, "step_00000001.tmp"))
    os.makedirs(os.path.join(d, "step_00000001.old.tmp"))
    tree2 = {"w": np.ones((2,), np.float32)}
    save_checkpoint(d, 1, tree2)
    np.testing.assert_array_equal(
        np.asarray(load_checkpoint(d, 1, tree)["w"]), tree2["w"])
    assert latest_step(d) == 1


def test_checkpoint_manager_gc_cleans_stale_tmp(tmp_path):
    from repro.ckpt import CheckpointManager
    d = str(tmp_path)
    mgr = CheckpointManager(d, keep=2)
    os.makedirs(os.path.join(d, "step_00000009.tmp"))
    mgr.save(0, {"w": np.zeros((2,), np.float32)})
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]


# ------------------------------------------------- autotune quarantine ----
def test_autotune_corrupt_cache_quarantined(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("LNS_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_caches()
    autotune._WARNED_CORRUPT.clear()
    path = autotune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write('{"env": {"jax": "torn mid-wri')
    with pytest.warns(RuntimeWarning, match="quarantined"):
        entries = autotune._load_disk()
    assert entries == {}
    assert not os.path.exists(path)          # moved aside, not deleted
    assert os.path.exists(path + ".corrupt")
    # Warn once per file per process: a second corrupt copy is silent.
    autotune.clear_caches()
    with open(path, "w") as f:
        f.write("[1, 2,")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert autotune._load_disk() == {}
    # A fresh persist works after quarantine (re-tune path).
    autotune.clear_caches()
    autotune._persist("k", (8, 8, 8), 1.0, {})
    with open(path) as f:
        assert "entries" in json.load(f)
    autotune.clear_caches()
    autotune._WARNED_CORRUPT.clear()


def test_autotune_wrong_json_shape_is_corruption(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("LNS_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_caches()
    autotune._WARNED_CORRUPT.clear()
    path = autotune.cache_path()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write("[1, 2, 3]")   # valid JSON, not a cache object
    with pytest.warns(RuntimeWarning):
        assert autotune._load_disk() == {}
    assert os.path.exists(path + ".corrupt")
    autotune.clear_caches()
    autotune._WARNED_CORRUPT.clear()


# ------------------------------------------------------ crash-safe sinks --
def test_jsonl_sink_flushes_per_row(tmp_path):
    from repro.obs import JsonlSink, read_jsonl
    path = str(tmp_path / "m.jsonl")
    sink = JsonlSink(path)
    sink.write([{"a": 1}, {"a": 2}], step=0)
    # No close(): rows must already be on disk (per-row flush).
    assert len(read_jsonl(path)) == 2
    sink.close()


def test_read_jsonl_tolerant_drops_only_torn_tail(tmp_path):
    from repro.obs import read_jsonl, read_jsonl_tolerant
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"a": 1}\n{"a": 2}\n{"a": 3, "tor')  # killed mid-write
    assert read_jsonl_tolerant(path) == [{"a": 1}, {"a": 2}]
    with pytest.raises(ValueError):
        read_jsonl(path)  # the strict reader still raises


def test_search_journal_resumes_past_torn_tail(tmp_path):
    """The search journal reuses the shared tolerant reader: a torn tail
    does not block resume, and a mismatched header still fails loudly."""
    from repro.search import PlanSearch, SearchConfig, SearchSpace
    space = SearchSpace.for_paper_mlp("lns16-train-emulate",
                                      fmts=("lns16", "lns12"))
    scfg = SearchConfig(epochs=1, steps_per_epoch=2, batch_size=4, seed=0,
                        refine_generations=0, refine_population=2)
    journal = str(tmp_path / "j.jsonl")
    PlanSearch(space, scfg, journal=journal).run()
    with open(journal, "a") as f:
        f.write('{"kind": "eval", "plan": "torn mid-wri')
    # Resume: torn tail dropped, same frontier.
    res = PlanSearch(space, scfg, journal=journal).run()
    assert res.frontier
    with open(journal, "w") as f:
        f.write('{"kind": "header", "space": "other"}\n')
    with pytest.raises(ValueError, match="different search"):
        PlanSearch(space, scfg, journal=journal)


# ------------------------------------------------- serve failure paths ----
def _tiny_lm():
    from repro.nn import init_params
    from repro.nn.config import ModelConfig
    cfg = ModelConfig(name="tiny-resil", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=64, d_head=16, vocab_pad_to=64,
                      numerics="fp32", param_dtype="float32", remat="none",
                      q_chunk=8)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 64, size=6) for _ in range(n)]


class TestServeFailurePaths:
    def test_mid_flight_deadline_expiry(self):
        from repro.serve import (REJECT_DEADLINE_EXPIRED, REJECTED,
                                 ServeConfig, ServingEngine, TERMINAL)
        cfg, params = _tiny_lm()
        eng = ServingEngine(cfg, params, ServeConfig(
            max_batch=2, max_len=32, block_size=8, prefill_chunk=8))
        rid = eng.submit(_prompts(1)[0], max_new=24, deadline_steps=3)
        for _ in range(30):
            eng.step()
            if eng.poll(rid).state in TERMINAL:
                break
        req = eng.poll(rid)
        assert req.state == REJECTED
        assert req.reason_code == REJECT_DEADLINE_EXPIRED
        assert req.reason == "deadline exceeded mid-flight"
        eng.bm.check_conserved()
        assert all(r is None for r in eng.slot_req)

    def test_watchdog_hang_fault_retry_to_completion(self):
        from repro.serve import ServeConfig, ServingEngine, TERMINAL
        cfg, params = _tiny_lm()
        eng = ServingEngine(
            cfg, params,
            ServeConfig(max_batch=2, max_len=32, block_size=8,
                        prefill_chunk=8, retry_budget=1),
            faults="seed=3;serve=hang_step:4")
        rids = [eng.submit(p, max_new=8) for p in _prompts(3)]
        for _ in range(400):
            eng.step()
            if all(eng.poll(r).state in TERMINAL for r in rids):
                break
        assert [eng.poll(r).state for r in rids] == ["DONE"] * 3
        assert sum(eng.poll(r).retries for r in rids) > 0
        assert eng.registry.counter_value("serve.watchdog_fired") == 1
        eng.bm.check_conserved()
        # Retried greedy outputs match a fault-free engine's exactly
        # (abort resets progress; greedy sampling is position-keyed).
        clean = ServingEngine(cfg, params, ServeConfig(
            max_batch=2, max_len=32, block_size=8, prefill_chunk=8))
        crids = [clean.submit(p, max_new=8) for p in _prompts(3)]
        while any(clean.poll(r).state not in TERMINAL for r in crids):
            clean.step()
        assert [eng.poll(r).output for r in rids] \
            == [clean.poll(r).output for r in crids]

    def test_retry_budget_exhaustion(self):
        from repro.serve import (REJECT_RETRY_EXHAUSTED, REJECTED,
                                 ServeConfig, ServingEngine)
        cfg, params = _tiny_lm()
        eng = ServingEngine(cfg, params, ServeConfig(
            max_batch=2, max_len=32, block_size=8, prefill_chunk=8,
            retry_budget=1))
        rid = eng.submit(_prompts(1)[0], max_new=8)
        hangs = 0
        for _ in range(100):
            eng.step()
            req = eng.poll(rid)
            if req.state == REJECTED:
                break
            if req.slot >= 0 and hangs < 2:
                eng._hung = True  # what the hang fault sets
                hangs += 1
        req = eng.poll(rid)
        assert req.state == REJECTED
        assert req.reason_code == REJECT_RETRY_EXHAUSTED
        assert "retry budget exhausted" in req.reason
        assert req.retries == 1
        eng.bm.check_conserved()

    def test_force_abort_conserves_blocks(self):
        from repro.serve import (REJECT_WATCHDOG_ABORT, REJECTED,
                                 ServeConfig, ServingEngine)
        cfg, params = _tiny_lm()
        eng = ServingEngine(cfg, params, ServeConfig(
            max_batch=2, max_len=32, block_size=8, prefill_chunk=8))
        rids = [eng.submit(p, max_new=8) for p in _prompts(2)]
        for _ in range(3):
            eng.step()
        assert any(eng.poll(r).slot >= 0 for r in rids)
        eng.force_abort()
        for r in rids:
            req = eng.poll(r)
            assert req.state == REJECTED
            assert req.reason_code == REJECT_WATCHDOG_ABORT
        eng.bm.check_conserved()
        assert eng.bm.available == eng.bm.capacity
        assert all(r is None for r in eng.slot_req)

    def test_slow_req_fault_preserves_outputs(self):
        """The straggler fault slows a request down without changing its
        greedy continuation (delay is scheduling, not arithmetic)."""
        from repro.serve import ServeConfig, ServingEngine
        cfg, params = _tiny_lm()
        sc = ServeConfig(max_batch=2, max_len=32, block_size=8,
                         prefill_chunk=8)
        prompts = _prompts(2)
        base = ServingEngine(cfg, params, sc).run(prompts, max_new=6)
        slow = ServingEngine(cfg, params, sc,
                             faults="seed=0;serve=slow_req:1")
        assert slow.run(prompts, max_new=6) == base
        assert slow.step_count > 0


# ------------------------------------------------------ drill determinism --
def test_drill_dp_drop_rows_deterministic():
    from repro.launch.drill import run_scenarios
    a = run_scenarios(["dp-drop"], steps=4, seed=3)
    b = run_scenarios(["dp-drop"], steps=4, seed=3)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a[0]["op"] == "fault_drill" and a[0]["mode"] == "dp-drop"
    assert a[0]["ms_per_step"] == 0.0  # detection latency in steps


# ------------------------------------------------------------ nan guard ---
def test_train_step_nan_guard_skips_poisoned_update():
    from repro.configs import get_config, reduced
    from repro.data import DataConfig, SyntheticLMDataset
    from repro.nn import Runtime, init_params
    from repro.nn.config import ShapeCell
    from repro.optim.optimizers import SGDConfig
    from repro.train import TrainConfig, init_train_state, make_train_step
    cfg = reduced(get_config("olmo-1b")).with_(numerics="fp32",
                                              remat="none")
    params = init_params(jax.random.PRNGKey(0), cfg)
    cell = ShapeCell("t", seq_len=32, global_batch=4, kind="train")
    batch = {k: jnp.asarray(v) for k, v in SyntheticLMDataset(
        cfg, cell, DataConfig(seed=0)).batch_at(0).items()}
    opt = SGDConfig(lr=1e-2)
    step = jax.jit(make_train_step(cfg, opt, Runtime(),
                                   TrainConfig(nan_guard=True)))
    # Clean batch: guard is transparent (update applied, flag 0).
    state = init_train_state(params, opt)
    out, m = step(state, batch)
    assert int(m["update_skipped"]) == 0
    assert not np.array_equal(
        np.asarray(out["params"]["emb"]["tok"]),
        np.asarray(state["params"]["emb"]["tok"]))
    # Poisoned params → nonfinite loss → whole update dropped.
    bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan),
                       state["params"])
    bstate = {**state, "params": bad}
    out2, m2 = step(bstate, m2_batch := batch)
    assert int(m2["update_skipped"]) == 1
    assert not np.isfinite(float(m2["loss"]))
    for a, b in zip(jax.tree.leaves(out2["opt"]),
                    jax.tree.leaves(bstate["opt"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(out2["step"]) == int(bstate["step"]) + 1
