"""The NumericsPlan contract: per-layer mixed-format LNS numerics.

Layers of guarantees:

1. Serialization: plan strings (default spec + ``;``-separated
   ``pattern=key:value`` rules) round-trip losslessly through
   ``parse``/``str``; a bare spec string is a plan with no rules whose
   ``str`` equals the spec's.  Unknown keys/values/patterns raise with
   the valid-values (or known-paths) list.
2. Resolution: rules apply in declaration order (later wins); layers
   whose resolved specs are equal share one *cached* runtime; a trivial
   plan resolves every path to the default runtime.
3. Training: N-step mixed-format (lns12 hidden / lns16 out) paper-MLP
   training is bit-identical between the emulate and pallas backends,
   and a bare spec plan reproduces the single-runtime trajectory.
4. Surfaces: kernels accept ``numerics=<plan>, layer=<path>``; the LM
   stack resolves per-component runtimes and rejects dead patterns;
   checkpoints are stamped with the canonical plan string and refuse
   restore on arithmetic mismatch (opt-out for deliberate migration).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (LNS12, LNS16, NumericsPlan, NumericsSpec, encode,
                        get_plan, get_policy)
from repro.core.lns import convert_format
from repro.core.plan import PlanRule

MIXED = "lns16-train-pallas;hidden=fmt:lns12"


# ------------------------------------------------------------ layer 1 ---
def test_plan_round_trip_lossless():
    p = NumericsPlan.parse(
        "lns16-train-pallas;hidden*=fmt:lns12,delta:lut20;out=delta:lut640")
    assert NumericsPlan.parse(str(p)) == p
    assert len(p.rules) == 2 and not p.is_uniform
    # rule overrides canonicalize (sorted keys, normalized values)
    c = NumericsPlan.parse(
        "lns16-train-pallas;out=quantize:grads+params+acts,interpret:on")
    assert str(c) == ("lns16-train-pallas;out=interpret:on,"
                      "quantize:params+acts+grads")
    assert NumericsPlan.parse(str(c)) == c
    # generic lut:<d_max>:<r> values survive the ':'-separated rule form
    odd = NumericsPlan.parse("lns16-exact;hidden=delta:lut:8:0.25")
    assert odd.resolve("hidden").delta_spec.d_max == 8.0
    assert NumericsPlan.parse(str(odd)) == odd


def test_bare_spec_is_trivial_plan():
    p = NumericsPlan.parse("lns16-train-pallas")
    assert p.is_uniform and str(p) == "lns16-train-pallas"
    assert p.default == NumericsSpec.parse("lns16-train-pallas")
    # objects pass through / wrap
    assert NumericsPlan.parse(p) is p
    assert NumericsPlan.parse(NumericsSpec.parse("bf16")).default \
        == NumericsSpec.parse("bf16")
    # spec-shaped delegation (what MLPConfig/TrainConfig surfaces read)
    assert p.fmt is LNS16 and p.backend == "pallas" and p.lns_grad
    assert p.reduce.mode == "boxplus"


def test_plan_parse_errors_list_valid_values():
    with pytest.raises(ValueError, match="spec key"):
        NumericsPlan.parse("lns16-qat;hidden=flux:9")
    with pytest.raises(ValueError, match="lns12"):
        NumericsPlan.parse("lns16-qat;hidden=fmt:fp8")
    with pytest.raises(ValueError, match="no overrides"):
        NumericsPlan.parse("lns16-qat;hidden=")
    with pytest.raises(ValueError, match="empty layer pattern"):
        NumericsPlan.parse("lns16-qat;=fmt:lns12")
    with pytest.raises(ValueError, match="':'"):
        NumericsPlan.parse("lns16-qat;hidden=fmt")
    with pytest.raises(ValueError, match="more than once"):
        NumericsPlan.parse("lns16-qat;hidden=fmt:lns12,fmt:lns16")
    # reduce.* is a *global* contract (the canonical segmentation of the
    # global batch): per-layer reduce rules would be silently ignored by
    # the DP machinery, so they are rejected at parse with a pointer to
    # the default-spec segment.
    with pytest.raises(ValueError, match="global"):
        NumericsPlan.parse(
            "lns16-train-pallas;hidden=reduce.mode:float-psum")
    with pytest.raises(ValueError, match="default spec segment"):
        NumericsPlan.parse("lns16-qat;out=reduce.grad_segments:4")
    with pytest.raises(ValueError, match="unknown numerics alias"):
        NumericsPlan.parse("lns17-qat;hidden=fmt:lns12")
    with pytest.raises(ValueError, match="reserved"):
        NumericsPlan(NumericsSpec.parse("bf16"),
                     (PlanRule("a;b", (("fmt", "lns16"),)),))


def test_unknown_pattern_guard():
    p = NumericsPlan.parse("lns16-train-pallas;hiden=fmt:lns12")  # typo
    with pytest.raises(ValueError, match="match no layer path"):
        p.validate_paths(("hidden", "out"))
    # a matching plan validates and resolves
    ok = NumericsPlan.parse(MIXED).validate_paths(("hidden", "out"))
    layers = ok.resolve_layers(("hidden", "out"))
    assert layers["hidden"].fmt is LNS12 and layers["out"].fmt is LNS16


# ------------------------------------------------------------ layer 2 ---
def test_glob_precedence_later_rule_wins():
    p = NumericsPlan.parse("lns16-train-emulate;*=fmt:lns12;out=fmt:lns16")
    assert p.resolve("hidden").fmt is LNS12
    assert p.resolve("out").fmt is LNS16          # later, more specific
    # declaration order (not specificity) is the contract: flipping the
    # rules makes the '*' override the specific one
    q = NumericsPlan.parse("lns16-train-emulate;out=fmt:lns16;*=fmt:lns12")
    assert q.resolve("out").fmt is LNS12
    # dotted-path globs
    r = NumericsPlan.parse("bf16;layers.*=compute_dtype:float32")
    assert r.resolve("layers.mlp").compute_dtype == "float32"
    assert r.resolve("emb").compute_dtype == "bfloat16"


def test_runtime_sharing_across_same_spec_layers():
    p = get_plan(MIXED)
    # same resolved spec → the same cached runtime object
    assert p.runtime_for("out") is p.runtime_for("head-like-path")
    assert p.runtime_for("hidden") is not p.runtime_for("out")
    # a trivial plan shares one runtime with the plain spec resolution
    t = get_plan("lns16-train-pallas")
    assert t.runtime_for("hidden") is t.runtime_for("out")
    assert t.runtime_for("hidden") is get_policy("lns16-train-pallas")
    # plans are hashable / jit-static
    assert {p: 1}[NumericsPlan.parse(MIXED)] == 1


def test_convert_format_integer_shifts(rng):
    v = rng.normal(size=(64,)).astype(np.float32)
    a16, a12 = encode(v, LNS16), encode(v, LNS12)
    # widening is exact: lns12 codes land on the lns16 grid losslessly
    up = convert_format(a12, LNS12, LNS16)
    np.testing.assert_array_equal(np.asarray(up.code),
                                  np.where(np.asarray(a12.code)
                                           == LNS12.zero_code,
                                           LNS16.zero_code,
                                           np.asarray(a12.code) << 4))
    # round-trip down-up-down is stable (idempotent rounding)
    down = convert_format(a16, LNS16, LNS12)
    again = convert_format(convert_format(down, LNS12, LNS16), LNS16, LNS12)
    np.testing.assert_array_equal(np.asarray(down.code),
                                  np.asarray(again.code))
    # same format is the identity object
    assert convert_format(a16, LNS16, LNS16) is a16
    # zeros stay zeros, signs preserved
    z = encode(np.zeros(3, np.float32), LNS16)
    assert (np.asarray(convert_format(z, LNS16, LNS12).code)
            == LNS12.zero_code).all()


# ------------------------------------------------------------ layer 3 ---
def test_mixed_plan_training_bitexact_across_backends(rng):
    """N-step mixed-format (lns12 hidden / lns16 out) paper-MLP training
    produces bit-identical weight codes on emulate and pallas."""
    from repro.paper.mlp import MLPConfig, make_mlp
    xb = rng.uniform(0, 1, size=(6, 10)).astype(np.float32)
    yb = rng.integers(0, 4, size=(6,))
    kw = dict(n_in=10, n_hidden=7, n_out=4, matmul_block=8)
    runs = {}
    for be in ("emulate", "pallas"):
        cfg = MLPConfig(spec=f"lns16-train-{be};hidden=fmt:lns12", **kw)
        model = make_mlp("lns", cfg)
        assert model.fmts["hidden"] is LNS12
        assert model.fmts["out"] is LNS16
        p = model.init(jax.random.PRNGKey(0))
        for _ in range(3):
            p, loss = model.train_step(p, xb, yb)
        runs[be] = p
        assert np.isfinite(float(loss))
    for k in runs["emulate"]:
        np.testing.assert_array_equal(np.asarray(runs["emulate"][k].code),
                                      np.asarray(runs["pallas"][k].code),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(runs["emulate"][k].sign),
                                      np.asarray(runs["pallas"][k].sign),
                                      err_msg=k)


def test_bare_plan_matches_pre_plan_single_runtime(rng):
    """A spec with no rules resolves both layers onto one shared runtime
    and trains identically whether passed as a spec or a trivial plan."""
    from repro.paper.mlp import MLPConfig, make_mlp
    xb = rng.uniform(0, 1, size=(6, 10)).astype(np.float32)
    yb = rng.integers(0, 4, size=(6,))
    kw = dict(n_in=10, n_hidden=7, n_out=4, matmul_block=8)
    runs = {}
    for tag, spec in (("spec", NumericsSpec.parse("lns16-train-pallas")),
                      ("plan", NumericsPlan.parse("lns16-train-pallas"))):
        model = make_mlp("lns", MLPConfig(spec=spec, **kw))
        assert model.runtimes["hidden"] is model.runtimes["out"]
        p = model.init(jax.random.PRNGKey(0))
        for _ in range(2):
            p, _ = model.train_step(p, xb, yb)
        runs[tag] = p
    for k in runs["spec"]:
        np.testing.assert_array_equal(np.asarray(runs["spec"][k].code),
                                      np.asarray(runs["plan"][k].code),
                                      err_msg=k)


def test_mlp_momentum_threads_state(rng):
    from repro.paper.mlp import MLPConfig, make_mlp
    xb = rng.uniform(0, 1, size=(6, 10)).astype(np.float32)
    yb = rng.integers(0, 4, size=(6,))
    kw = dict(n_in=10, n_hidden=7, n_out=4, matmul_block=8)
    m0 = make_mlp("lns", MLPConfig(spec="lns16-train-emulate", **kw))
    m9 = make_mlp("lns", MLPConfig(spec="lns16-train-emulate",
                                   momentum=0.9, **kw))
    assert m0.init_momentum(m0.init(jax.random.PRNGKey(0))) is None
    p0 = m0.init(jax.random.PRNGKey(0))
    p9 = m9.init(jax.random.PRNGKey(0))
    mom = m9.init_momentum(p9)
    assert set(mom) == set(p9)
    for _ in range(3):
        p0, _ = m0.train_step(p0, xb, yb)
        p9, mom, _ = m9.train_step(p9, xb, yb, mom)
    # momentum accumulates: second-step trajectories must diverge
    assert any(not np.array_equal(np.asarray(p0[k].code),
                                  np.asarray(p9[k].code)) for k in p0)
    # the momentum state itself is LNS (nonzero after 3 steps)
    assert any((np.asarray(mom[k].code)
                != m9.param_fmts[k].zero_code).any() for k in mom)


# ------------------------------------------------------------ layer 4 ---
def test_kernels_accept_plan_and_layer(rng):
    """A layer's runtime under a plan runs its own spec's arithmetic."""
    from repro.kernels.lns_matmul import lns_matmul_trainable
    X = rng.normal(size=(4, 10)).astype(np.float32)
    W = rng.normal(size=(10, 3)).astype(np.float32)
    plan = NumericsPlan.parse(MIXED)
    z_plan = lns_matmul_trainable(
        X, W, plan.runtime_for("hidden", 8, 8, 8).matmul)
    z_12 = lns_matmul_trainable(X, W, NumericsSpec.parse(
        "lns16-train-pallas,fmt=lns12").runtime(8, 8, 8).matmul)
    np.testing.assert_array_equal(np.asarray(z_plan), np.asarray(z_12))
    # default layer = the plan's default spec (lns16 here) → differs
    z_def = lns_matmul_trainable(X, W, plan.runtime(8, 8, 8).matmul)
    assert not np.array_equal(np.asarray(z_plan), np.asarray(z_def))


def test_lm_stack_runs_per_layer_plan():
    from repro.configs import get_config, reduced
    from repro.nn import Runtime, init_params, loss_fn
    from repro.nn.model import known_layer_paths
    cfg = reduced(get_config("olmo-1b")).with_(remat="none")
    assert "layers.mlp" in known_layer_paths(cfg)
    p = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "labels": jnp.zeros((2, 16), jnp.int32)}
    base = float(loss_fn(p, batch, cfg.with_(numerics="bf16")))
    mixed = float(loss_fn(p, batch, cfg.with_(
        numerics="bf16;layers.mlp=fmt:lns16,delta:lut20,quantize:params"
                 "+acts,compute_dtype:float32")))
    assert np.isfinite(base) and np.isfinite(mixed) and base != mixed
    # a dead pattern fails loudly before any compilation
    with pytest.raises(ValueError, match="match no layer path"):
        loss_fn(p, batch, cfg.with_(numerics="bf16;layres.*=fmt:lns16"))


def test_checkpoint_numerics_stamp(tmp_path, rng):
    from repro.ckpt import (CheckpointManager, load_checkpoint,
                            save_checkpoint)
    tree = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)}
    save_checkpoint(str(tmp_path), 3, tree, numerics=MIXED)
    import json
    import os
    with open(os.path.join(tmp_path, "step_00000003",
                           "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["numerics"] == str(NumericsPlan.parse(MIXED))
    # matching (canonicalized) numerics restores fine
    out = load_checkpoint(str(tmp_path), 3, tree, numerics=MIXED)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
    # mismatch fails with a clear pointer...
    with pytest.raises(ValueError, match="allow_numerics_mismatch"):
        load_checkpoint(str(tmp_path), 3, tree,
                        numerics="lns16-train-emulate")
    # ...unless migration is explicit
    load_checkpoint(str(tmp_path), 3, tree, numerics="lns16-train-emulate",
                    allow_numerics_mismatch=True)
    # unstamped checkpoints (pre-PR-4) restore without the check
    save_checkpoint(str(tmp_path), 4, tree)
    load_checkpoint(str(tmp_path), 4, tree, numerics=MIXED)
    # the manager stamps and checks end-to-end
    mgr = CheckpointManager(str(tmp_path / "mgr"), numerics=MIXED)
    mgr.save(1, tree)
    restored, step = mgr.restore_latest(tree)
    assert step == 1
    bad = CheckpointManager(str(tmp_path / "mgr"), numerics="bf16")
    with pytest.raises(ValueError, match="not portable"):
        bad.restore_latest(tree)
    ok = CheckpointManager(str(tmp_path / "mgr"), numerics="bf16",
                           allow_numerics_mismatch=True)
    restored, step = ok.restore_latest(tree)
    assert step == 1
    # a malformed numerics string fails in the constructor, not inside
    # the async writer thread (where it would silently drop every save)
    with pytest.raises(ValueError, match="alias"):
        CheckpointManager(str(tmp_path / "bad"), numerics="lns17-qat")


# ------------------------------------------------------------ plan diff
def test_plan_diff_by_paths():
    from repro.core import plan_diff
    a = NumericsPlan.parse("lns16-train-pallas")
    b = NumericsPlan.parse(MIXED)
    d = a.diff(b, paths=("hidden", "out"))
    assert d["hidden"] == {"fmt": ("lns16", "lns12")}
    assert "out" not in d            # same effective spec there
    assert "<default>" not in d      # defaults equal
    text = plan_diff(a, b, paths=("hidden", "out"),
                     labels=("have", "want"))
    assert "have vs want" in text
    assert "hidden: fmt lns16 -> lns12" in text


def test_plan_diff_defaults_and_rules():
    from repro.core import plan_diff
    a = NumericsPlan.parse("lns16-train-emulate")
    b = NumericsPlan.parse(
        "lns16-train-emulate,fmt=lns12;out=delta:bitshift")
    d = a.diff(b)
    assert d["<default>"]["fmt"] == ("lns16", "lns12")
    assert d["out"]["delta"][1] == "bitshift"
    assert d["out"]["delta"][0] is None     # one-sided override
    # reflexive: no differences
    assert a.diff(a) == {}
    assert "(no differences)" in plan_diff(a, a)


def test_checkpoint_mismatch_message_carries_diff(tmp_path):
    from repro.ckpt import load_checkpoint, save_checkpoint
    tree = {"w": encode(np.ones((2, 2), np.float32), LNS16)}
    save_checkpoint(str(tmp_path), 1, tree, numerics=MIXED)
    with pytest.raises(ValueError) as ei:
        load_checkpoint(str(tmp_path), 1, tree,
                        numerics="lns16-train-pallas")
    msg = str(ei.value)
    assert "numerics diff (saved vs requested)" in msg
    # the saved plan's hidden=fmt:lns12 rule has no counterpart in the
    # requested plan: one-sided overrides render as '-'
    assert "hidden: fmt lns12 -> -" in msg
