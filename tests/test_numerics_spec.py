"""The NumericsSpec → LNSRuntime contract.

Layers of guarantees:

1. Serialization: every registry alias round-trips losslessly through
   ``parse``/``str``; overridden specs round-trip onto nearest-alias +
   sorted ``key=value`` form; the alias table is pinned (renames must be
   deliberate).
2. Resolution: specs are hashable / jit-static; equal specs resolve to the
   *same* cached runtime; the typed ``spec.with_(backend=...)`` override
   picks the identical resolved spec as the retired policy-name string
   surgery, and invalid overrides raise with the valid-values list.
3. Deprecation: the legacy loose knobs (``MLPConfig(matmul_backend=...)``
   etc.) emit a ``DeprecationWarning`` and resolve to the identical
   runtime — including bit-identical N-step paper-MLP training.
"""
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (ALIASES, POLICIES, LNS16, LNSRuntime, NumericsSpec,
                        ReduceSpec, get_policy)
from repro.core.delta import DELTA_BITSHIFT, DELTA_DEFAULT

# The pinned alias table: a rename or removal here is an API break and
# must be deliberate (update this list in the same PR).
GOLDEN_ALIASES = [
    "bf16", "fp32", "lns12-qat", "lns16-exact", "lns16-exact-pallas",
    "lns16-qat", "lns16-train-emulate", "lns16-train-pallas",
    "lns16-w-only",
]


# ------------------------------------------------------------ layer 1 ---
def test_alias_table_is_pinned():
    assert sorted(ALIASES) == GOLDEN_ALIASES
    assert POLICIES is ALIASES  # the legacy name views the same registry


@pytest.mark.parametrize("name", GOLDEN_ALIASES)
def test_alias_round_trip_lossless(name):
    spec = NumericsSpec.parse(name)
    assert str(spec) == name
    assert NumericsSpec.parse(str(spec)) == spec


def test_override_string_round_trip():
    s = NumericsSpec.parse(
        "lns16-train-pallas,reduce.mode=float-psum,reduce.grad_segments=4")
    assert s.reduce == ReduceSpec(mode="float-psum", grad_segments=4)
    assert NumericsSpec.parse(str(s)) == s
    # canonicalization: an override that lands exactly on another alias
    # serializes as that alias
    assert str(NumericsSpec.parse("lns16-train-emulate,backend=pallas")) \
        == "lns16-train-pallas"
    # key=value-only form (no alias) parses too
    kv = NumericsSpec.parse(
        "fmt=lns16,delta=lut20,quantize=params+acts+grads,"
        "compute_dtype=float32,backend=pallas")
    assert kv == NumericsSpec.parse("lns16-train-pallas")
    # non-registry Δ specs survive the generic lut:<d_max>:<r> form
    odd = NumericsSpec.parse("lns16-exact,delta=lut:8:0.25")
    assert odd.delta_spec.d_max == 8.0 and odd.delta_spec.r == 0.25
    assert NumericsSpec.parse(str(odd)) == odd


def test_parse_errors_list_valid_values():
    with pytest.raises(ValueError, match="lns16-train-pallas"):
        NumericsSpec.parse("lns17-qat")           # unknown alias
    with pytest.raises(ValueError, match="reduce.mode"):
        NumericsSpec.parse("lns16-qat,flux=9")    # unknown key
    with pytest.raises(ValueError, match="emulate, pallas"):
        NumericsSpec.parse("lns16-qat,backend=cuda")
    with pytest.raises(ValueError, match="boxplus"):
        NumericsSpec.parse("lns16-train-pallas,reduce.mode=ring")
    with pytest.raises(ValueError, match="lut20"):
        NumericsSpec.parse("lns16-exact,delta=spline")
    with pytest.raises(ValueError, match="fmt"):
        NumericsSpec.parse("lns16-qat,fmt=fp8")


# ------------------------------------------------------------ layer 2 ---
def test_spec_hashable_and_jit_static():
    a = NumericsSpec.parse("lns16-train-pallas")
    b = NumericsSpec.parse("lns16-train-emulate,backend=pallas")
    assert a == b and hash(a) == hash(b)
    assert {a: 1}[b] == 1

    calls = []

    def f(x, spec):
        calls.append(spec)
        return x * (2.0 if spec.backend == "pallas" else 1.0)

    jf = jax.jit(f, static_argnums=1)
    assert float(jf(jnp.float32(3.0), a)) == 6.0
    assert float(jf(jnp.float32(3.0), b)) == 6.0
    assert len(calls) == 1, "equal specs must share one jit cache entry"


def test_equal_specs_resolve_to_same_cached_runtime():
    r1 = NumericsSpec.parse("lns16-exact-pallas").runtime()
    r2 = get_policy("lns16-exact,backend=pallas")
    assert r1 is r2
    assert isinstance(r1, LNSRuntime)
    assert r1.matmul is r1.matmul  # resolved once, cached
    assert r1.matmul.backend == "pallas" and r1.matmul.fmt is LNS16


def test_with_typed_override_matches_string_surgery():
    """The retired ``name.rsplit('-', 1)[0] + '-' + backend`` hack and the
    typed ``spec.with_(backend=...)`` override pick the same spec."""
    for name in ("lns16-train-emulate", "lns16-train-pallas"):
        for be in ("emulate", "pallas"):
            old = NumericsSpec.parse(name.rsplit("-", 1)[0] + "-" + be)
            new = NumericsSpec.parse(name).with_(backend=be)
            assert old == new and str(new) == f"lns16-train-{be}"
    with pytest.raises(ValueError, match="emulate, pallas"):
        NumericsSpec.parse("lns16-train-pallas").with_(backend="cuda")
    with pytest.raises(ValueError, match="reduce.grad_segments"):
        NumericsSpec.parse("lns16-train-pallas").with_(reduce_segments=4)


def test_trainconfig_override_paths_agree():
    from repro.configs import get_config, reduced
    from repro.core.plan import NumericsPlan
    from repro.train.step import TrainConfig, resolve_numerics
    cfg = reduced(get_config("olmo-1b")).with_(
        numerics="lns16-train-emulate", remat="none")
    with pytest.warns(DeprecationWarning, match="backend=pallas"):
        tc = TrainConfig(matmul_backend="pallas")
    legacy_cfg, legacy_plan = resolve_numerics(cfg, tc)
    new_cfg, new_plan = resolve_numerics(
        cfg.with_(numerics="lns16-train-emulate,backend=pallas"),
        TrainConfig())
    # resolve_numerics returns the (trivial) per-layer plan; its default
    # spec is the resolved arithmetic.
    assert legacy_plan == new_plan == NumericsPlan.parse("lns16-train-pallas")
    assert legacy_plan.default == NumericsSpec.parse("lns16-train-pallas")
    assert legacy_cfg.numerics == new_cfg.numerics == "lns16-train-pallas"
    # invalid override value / non-training spec raise with pointers
    with pytest.warns(DeprecationWarning):
        bad = TrainConfig(matmul_backend="cuda")
    with pytest.raises(ValueError, match="emulate, pallas"):
        resolve_numerics(cfg, bad)
    with pytest.warns(DeprecationWarning):
        tc2 = TrainConfig(matmul_backend="pallas")
    with pytest.raises(ValueError, match="grads"):
        resolve_numerics(cfg.with_(numerics="fp32"), tc2)


def test_dp_plan_derives_from_spec():
    from repro.distributed.lns_dp import DPConfig
    spec = NumericsSpec.parse(
        "lns16-train-pallas,reduce.mode=float-psum,reduce.grad_segments=4")
    dp = DPConfig.from_spec(spec, num_devices=2)
    assert dp.reduce is spec.reduce or dp.reduce == spec.reduce
    assert dp.reduce_mode == "float-psum" and dp.grad_segments == 4
    assert dp.segments(8) == 4
    rt = spec.runtime()
    assert rt.dp_config(num_devices=2) == dp


def test_kernels_accept_numerics_spec(rng):
    """The kernels run what a spec resolves to: the runtime's backend is
    the one built from the pieces, and gives the same results."""
    from repro.core import LNSMatmulBackend, encode
    from repro.kernels.lns_boxsum import lns_boxsum_kernel
    from repro.kernels.lns_matmul import lns_matmul_trainable
    X = rng.normal(size=(4, 10)).astype(np.float32)
    W = rng.normal(size=(10, 3)).astype(np.float32)
    be = NumericsSpec.parse("lns16-train-pallas").runtime(8, 8, 8).matmul
    be_expl = LNSMatmulBackend(fmt=LNS16, spec=DELTA_DEFAULT,
                               backend="pallas", block_m=8, block_n=8,
                               block_k=8)
    assert be == be_expl
    z_spec = lns_matmul_trainable(X, W, be)
    z_expl = lns_matmul_trainable(X, W, be_expl)
    np.testing.assert_array_equal(np.asarray(z_spec), np.asarray(z_expl))
    x = encode(rng.normal(size=(6, 5)).astype(np.float32), LNS16)
    ns = NumericsSpec.parse("lns16-exact")
    b_spec = lns_boxsum_kernel(x, fmt=ns.fmt, spec=ns.delta_spec,
                               block_m=8, block_k=5)
    b_expl = lns_boxsum_kernel(x, fmt=LNS16, spec=DELTA_DEFAULT, block_m=8,
                               block_k=5)
    np.testing.assert_array_equal(np.asarray(b_spec.code),
                                  np.asarray(b_expl.code))
    with pytest.raises(ValueError, match="fmt"):
        NumericsSpec.parse("bf16").runtime().matmul


# ------------------------------------------------------------ layer 3 ---
def test_mlpconfig_legacy_knobs_warn_and_resolve_identically():
    from repro.paper.mlp import MLPConfig
    kw = dict(n_in=10, n_hidden=7, n_out=4, matmul_block=8)
    with pytest.warns(DeprecationWarning, match="spec="):
        legacy = MLPConfig(matmul_backend="pallas", reduce_mode="float-psum",
                           grad_segments=4, **kw)
    via_spec = MLPConfig(
        spec="lns16-train-pallas,reduce.mode=float-psum,"
             "reduce.grad_segments=4", **kw)
    assert legacy.spec == via_spec.spec
    assert legacy.runtime() is via_spec.runtime()  # same cached resolution
    # spec-less construction stays warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg = MLPConfig(**kw)
    assert str(cfg.spec) == "lns16-train-emulate"
    # bits/approx still derive the default spec
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg12 = MLPConfig(bits=12, approx="bitshift", **kw)
    assert cfg12.spec.fmt.name == "lns12"
    assert cfg12.spec.delta_spec == DELTA_BITSHIFT


def test_paper_mlp_legacy_and_spec_training_bitexact(rng):
    """Acceptance: N-step paper-MLP training under
    ``NumericsSpec.parse("lns16-train-pallas")`` equals the legacy
    loose-knob configuration, weight code for weight code."""
    from repro.paper.mlp import MLPConfig, make_mlp
    xb = rng.uniform(0, 1, size=(6, 10)).astype(np.float32)
    yb = rng.integers(0, 4, size=(6,))
    kw = dict(n_in=10, n_hidden=7, n_out=4, matmul_block=8)
    with pytest.warns(DeprecationWarning):
        legacy_cfg = MLPConfig(matmul_backend="pallas", **kw)
    spec_cfg = MLPConfig(spec=NumericsSpec.parse("lns16-train-pallas"), **kw)
    runs = {}
    for tag, cfg in (("legacy", legacy_cfg), ("spec", spec_cfg)):
        model = make_mlp("lns", cfg)
        p = model.init(jax.random.PRNGKey(0))
        for _ in range(3):
            p, _ = model.train_step(p, xb, yb)
        runs[tag] = p
    for k in runs["legacy"]:
        np.testing.assert_array_equal(np.asarray(runs["legacy"][k].code),
                                      np.asarray(runs["spec"][k].code),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(runs["legacy"][k].sign),
                                      np.asarray(runs["spec"][k].sign),
                                      err_msg=k)
