"""Block-size autotuner: candidate pruning, cache discipline, spec/plan
threading — and the invariant that blocks never change results.
"""
import json
import os

import numpy as np
import pytest

from repro.core import (DELTA_DEFAULT, LNS16, NumericsPlan, NumericsSpec,
                        encode, parse_blocks, resolve_blocks_arg)
from repro.kernels import autotune


@pytest.fixture
def tuner_dir(tmp_path, monkeypatch):
    """Isolated persistent-cache dir + clean in-memory caches."""
    monkeypatch.setenv("LNS_AUTOTUNE_DIR", str(tmp_path))
    autotune.clear_caches()
    yield str(tmp_path)
    autotune.clear_caches()


# ----------------------------------------------------------- candidates
def test_candidates_respect_vmem_budget():
    for op in ("fwd", "dx", "dw"):
        for blocks in autotune.candidate_blocks(op, (512, 512, 4096)):
            assert autotune.vmem_bytes(op, blocks) \
                <= autotune.DEFAULT_VMEM_BUDGET


def test_candidates_ranked_and_bounded():
    cands = autotune.candidate_blocks("fwd", (64, 100, 784),
                                      max_candidates=5)
    assert 0 < len(cands) <= 5
    assert len(set(cands)) == len(cands)
    # full-shape blocks fit the budget at this size → ranked first
    # (grid volume 1, zero padding waste)
    assert cands[0] == (64, 100, 784)


def test_compiled_lane_candidates_meet_tpu_tiling():
    """Compiled launches only get tiles the chip's (8, 128) rule admits:
    row and column tiles in multiples of 128, the contraction in
    multiples of 8 (whole axes padded up)."""
    for op, shape in (("fwd", (64, 100, 784)), ("dx", (512, 2048, 6144)),
                      ("dw", (784, 100, 64)), ("boxsum", (78400, 1, 4))):
        cands = autotune.candidate_blocks(op, shape, interpret=False)
        assert cands
        for br, bc, bct in cands:
            assert br % 128 == 0 and bct % 8 == 0
            assert bc == 1 if op == "boxsum" else bc % 128 == 0


def test_candidates_dw_partials_pin_contraction():
    """Segment length is part of the DP determinism contract — the
    contraction block is not tunable for the partials kernel."""
    for _, _, bct in autotune.candidate_blocks("dw_partials", (784, 100,
                                                               16)):
        assert bct == 16


def test_heuristic_is_deterministic():
    a = autotune.heuristic_blocks("dw", (784, 100, 64))
    b = autotune.heuristic_blocks("dw", (784, 100, 64))
    assert a == b


def test_unknown_op_raises():
    with pytest.raises(ValueError, match="unknown autotune op"):
        autotune.candidate_blocks("gemm", (8, 8, 8))


# ------------------------------------------------------ cache discipline
def test_lookup_measures_once_and_persists(tuner_dir):
    calls = []

    def stub(op, shape, blocks):
        calls.append(blocks)
        return 1.0 if blocks == (64, 100, 784) else 2.0

    best = autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                           spec=DELTA_DEFAULT, interpret=True,
                           measure=True, measure_fn=stub)
    assert best == (64, 100, 784)
    n = len(calls)
    assert n > 1  # searched a real candidate set
    # memory hit
    assert autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                           spec=DELTA_DEFAULT, interpret=True,
                           measure=True, measure_fn=stub) == best
    assert len(calls) == n
    # disk hit after dropping memory
    autotune.clear_caches()
    assert autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                           spec=DELTA_DEFAULT, interpret=True,
                           measure=True, measure_fn=stub) == best
    assert len(calls) == n


def test_shallow_search_entry_does_not_satisfy_deeper_lookup(tuner_dir):
    """A quick shallow tune (demo) must not pin the blocks a deeper
    search would choose: the deeper lookup re-tunes and overwrites."""
    calls = []

    def stub(op, shape, blocks):
        calls.append(blocks)
        return float(sum(blocks))  # smallest-block candidate wins

    shallow = autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                              spec=DELTA_DEFAULT, measure=True,
                              measure_fn=stub, max_candidates=2, reps=1)
    n_shallow = len(calls)
    # same process (memory cache): the shallow entry must not satisfy
    # the deeper request either
    deep = autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                           spec=DELTA_DEFAULT, measure=True,
                           measure_fn=stub, max_candidates=8, reps=2)
    assert len(calls) > n_shallow, "deep lookup trusted the shallow entry"
    # cross-process (disk cache): drop memory, re-request shallow → the
    # deeper persisted entry satisfies it without re-measuring
    autotune.clear_caches()
    n_deep = len(calls)
    assert autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                           spec=DELTA_DEFAULT, measure=True,
                           measure_fn=stub, max_candidates=2,
                           reps=1) == deep
    assert len(calls) == n_deep
    # when measurement is impossible, the shallow measured entry still
    # beats the pure heuristic
    autotune.clear_caches()
    assert autotune.lookup("fwd", (64, 100, 784), fmt=LNS16,
                           spec=DELTA_DEFAULT, measure=False,
                           max_candidates=8) == deep


def test_cache_file_stamped_with_env_and_commit(tuner_dir):
    autotune.lookup("fwd", (8, 8, 8), fmt=LNS16, spec=DELTA_DEFAULT,
                    interpret=True, measure=True,
                    measure_fn=lambda *a: 1.0)
    with open(autotune.cache_path()) as f:
        data = json.load(f)
    assert data["env"] == autotune.env_stamp()
    (entry,) = data["entries"].values()
    assert set(entry) >= {"blocks", "ms", "commit", "time"}


def test_mismatched_env_cache_ignored(tuner_dir):
    """A cache produced under another environment must not be trusted."""
    autotune.lookup("fwd", (8, 8, 8), fmt=LNS16, spec=DELTA_DEFAULT,
                    interpret=True, measure=True,
                    measure_fn=lambda *a: 1.0)
    path = autotune.cache_path()
    with open(path) as f:
        data = json.load(f)
    data["env"]["jax"] = "0.0.0-other"
    with open(path, "w") as f:
        json.dump(data, f)
    autotune.clear_caches()
    calls = []
    autotune.lookup("fwd", (8, 8, 8), fmt=LNS16, spec=DELTA_DEFAULT,
                    interpret=True, measure=True,
                    measure_fn=lambda *a: calls.append(a) or 1.0)
    assert calls, "stale-env entries were trusted"


def test_nonmeasurable_miss_falls_back_to_heuristic(tuner_dir):
    """measure=False (what a jit-trace-time miss resolves to) returns the
    deterministic heuristic and persists nothing."""
    blocks = autotune.lookup("dw", (16, 8, 8), fmt=LNS16,
                             spec=DELTA_DEFAULT, interpret=True,
                             measure=False)
    assert blocks == autotune.heuristic_blocks("dw", (16, 8, 8))
    assert not os.path.exists(autotune.cache_path())


def test_disable_env_var_blocks_measurement(tuner_dir, monkeypatch):
    monkeypatch.setenv("LNS_AUTOTUNE_DISABLE", "1")
    blocks = autotune.lookup("fwd", (8, 8, 8), fmt=LNS16,
                             spec=DELTA_DEFAULT, interpret=True)
    assert blocks == autotune.heuristic_blocks("fwd", (8, 8, 8))
    assert not os.path.exists(autotune.cache_path())


def test_real_measurement_smoke(tuner_dir):
    """One genuine timed tune on a tiny shape: returns a valid candidate
    and persists a positive timing."""
    best, results = autotune.tune("fwd", (8, 8, 16), fmt=LNS16,
                                  spec=DELTA_DEFAULT, interpret=True,
                                  max_candidates=2, reps=1)
    assert best in results and all(ms > 0 for ms in results.values())


# ------------------------------------------------- spec / plan threading
def test_blocks_axis_parses_and_roundtrips():
    s = NumericsSpec.parse("lns16-train-pallas,blocks=auto")
    assert s.blocks == "auto"
    assert str(s) == "lns16-train-pallas,blocks=auto"
    assert NumericsSpec.parse(str(s)) == s
    assert parse_blocks("256x128x64") == (256, 128, 64)
    for bad in ("16x16", "0x8x8", "axbxc"):
        with pytest.raises(ValueError, match="blocks"):
            NumericsSpec.parse(f"lns16-train-pallas,blocks={bad}")


def test_explicit_blocks_pin_backend_tiles():
    be = NumericsSpec.parse("lns16-train-pallas,blocks=16x8x32") \
        .runtime().matmul
    assert (be.block_m, be.block_n, be.block_k) == (16, 8, 32)
    assert be.blocks == "default"
    assert resolve_blocks_arg("auto", 1, 2, 3) == (1, 2, 3, "auto")


def test_plan_rule_blocks_per_layer():
    plan = NumericsPlan.parse(
        "lns16-train-pallas;hidden=blocks:16x8x32;out=blocks:auto")
    assert str(plan) == \
        "lns16-train-pallas;hidden=blocks:16x8x32;out=blocks:auto"
    assert plan.resolve("hidden").blocks == "16x8x32"
    assert plan.resolve("out").blocks == "auto"
    assert plan.resolve("hidden").runtime().matmul.block_m == 16


def test_auto_blocks_bitexact_vs_default(rng, tuner_dir, monkeypatch):
    """The whole point: the tuner may pick any blocks — results cannot
    change.  Covers heuristic resolution inside jit (train path)."""
    monkeypatch.setenv("LNS_AUTOTUNE_DISABLE", "1")
    x = encode(rng.normal(size=(12, 20)).astype(np.float32), LNS16)
    w = encode(rng.normal(size=(20, 8)).astype(np.float32), LNS16)
    be_auto = NumericsSpec.parse(
        "lns16-train-pallas,blocks=auto").runtime().matmul
    be_def = NumericsSpec.parse("lns16-train-pallas").runtime(8, 8, 8) \
        .matmul
    for op, args in (("matmul", (x, w)),
                     ("matmul_dx", (encode(rng.normal(size=(12, 8))
                                           .astype(np.float32), LNS16), w)),
                     ("matmul_dw", (x, encode(rng.normal(size=(12, 8))
                                              .astype(np.float32),
                                              LNS16)))):
        za = getattr(be_auto, op)(*args)
        zd = getattr(be_def, op)(*args)
        np.testing.assert_array_equal(np.asarray(za.code),
                                      np.asarray(zd.code), err_msg=op)


def test_boxsum_kernel_blocks_auto(rng, tuner_dir, monkeypatch):
    monkeypatch.setenv("LNS_AUTOTUNE_DISABLE", "1")
    from repro.kernels.lns_boxsum import lns_boxsum_kernel, lns_boxsum_ref
    x = encode(rng.normal(size=(10, 6)).astype(np.float32), LNS16)
    za = lns_boxsum_kernel(x, fmt=LNS16, spec=DELTA_DEFAULT, blocks="auto")
    rc, _ = lns_boxsum_ref(x.code, x.sign, fmt=LNS16, spec=DELTA_DEFAULT)
    np.testing.assert_array_equal(np.asarray(za.code), np.asarray(rc))


def test_trainable_op_accepts_blocks_spec(rng, tuner_dir, monkeypatch):
    """lns_matmul_trainable honors the spec's blocks axis end-to-end,
    through the backend the spec's runtime resolves."""
    monkeypatch.setenv("LNS_AUTOTUNE_DISABLE", "1")
    import jax
    from repro.kernels.lns_matmul import lns_matmul_trainable

    def be(text):
        return NumericsSpec.parse(text).runtime().matmul

    X = rng.normal(size=(6, 12)).astype(np.float32)
    W = rng.normal(size=(12, 4)).astype(np.float32)
    za = lns_matmul_trainable(X, W, be("lns16-train-pallas,blocks=auto"))
    zd = lns_matmul_trainable(X, W, be("lns16-train-pallas"))
    np.testing.assert_array_equal(np.asarray(za), np.asarray(zd))
    g = jax.grad(lambda x, w: lns_matmul_trainable(
        x, w, be("lns16-train-pallas,blocks=16x8x32")).sum())(X, W)
    assert np.isfinite(np.asarray(g)).all()


def test_prime_matmul_fills_all_three_ops(tuner_dir):
    seen = []

    def stub(op, shape, blocks):
        seen.append(op)
        return 1.0

    out = autotune.prime_matmul(8, 16, 4, fmt=LNS16, spec=DELTA_DEFAULT,
                                measure=True, measure_fn=stub)
    assert set(out) == {"fwd", "dx", "dw"}
    assert set(seen) == {"fwd", "dx", "dw"}
    assert out["fwd"] == autotune.lookup("fwd", (8, 4, 16), fmt=LNS16,
                                         spec=DELTA_DEFAULT)


# ------------------------------------------------- interpret-lane keys
def test_cache_key_partitioned_by_interpret_lane(tuner_dir):
    """A tune measured on the interpret lane must never satisfy a
    compiled-lane lookup (and vice versa): the lanes time differently,
    so sharing entries would pin interpreter-shaped tiles on hardware."""
    shape = (64, 100, 784)
    heuristic = autotune.heuristic_blocks("fwd", shape, interpret=True)
    # the stub prefers a candidate the heuristic would NOT pick
    cands = autotune.candidate_blocks("fwd", shape, interpret=True)
    seeded = next(c for c in cands if c != heuristic)

    def stub(op, shape, blocks):
        return 1.0 if blocks == seeded else 2.0

    got = autotune.lookup("fwd", shape, fmt=LNS16, spec=DELTA_DEFAULT,
                          interpret=True, measure=True, measure_fn=stub)
    assert got == seeded
    # compiled-lane lookup: no measurement allowed -> must fall back to
    # the (chip-tiled) heuristic, NOT the interpret-tuned entry
    assert autotune.lookup("fwd", shape, fmt=LNS16, spec=DELTA_DEFAULT,
                           interpret=False, measure=False) \
        == autotune.heuristic_blocks("fwd", shape, interpret=False)
    # ... and the other direction: tune compiled, look up interpret
    hw_heuristic = autotune.heuristic_blocks("dx", shape, interpret=False)
    seeded_hw = next(c for c in autotune.candidate_blocks(
        "dx", shape, interpret=False) if c != hw_heuristic)

    def stub2(op, shape, blocks):
        return 1.0 if blocks == seeded_hw else 2.0
    autotune.clear_caches()
    got2 = autotune.lookup("dx", shape, fmt=LNS16, spec=DELTA_DEFAULT,
                           interpret=False, measure=True, measure_fn=stub2)
    assert got2 == seeded_hw
    assert autotune.lookup("dx", shape, fmt=LNS16, spec=DELTA_DEFAULT,
                           interpret=True, measure=False) \
        == autotune.heuristic_blocks("dx", shape, interpret=True)
    # the partition is visible in the key itself
    k_i = autotune.entry_key("fwd", shape, LNS16, DELTA_DEFAULT, True)
    k_c = autotune.entry_key("fwd", shape, LNS16, DELTA_DEFAULT, False)
    assert k_i != k_c
    assert "interpret=True" in k_i and "interpret=False" in k_c


def test_per_layer_interpret_overrides_reach_autotuner(tuner_dir,
                                                       monkeypatch):
    """blocks=auto consults the tuner with each layer's *resolved*
    interpret lane: a per-layer ``interpret:off`` override must surface
    as interpret=False in that layer's lookups only."""
    monkeypatch.setenv("LNS_AUTOTUNE_DISABLE", "1")
    plan = NumericsPlan.parse(
        "lns16-train-emulate,blocks=auto,interpret=on;hidden=interpret:off")
    seen = {}
    real = autotune.lookup

    def spy(op, shape, **kw):
        seen.setdefault(kw["interpret"], 0)
        seen[kw["interpret"]] += 1
        return real(op, shape, **kw)

    monkeypatch.setattr(autotune, "lookup", spy)
    mm_h = plan.runtime_for("hidden").matmul
    mm_o = plan.runtime_for("out").matmul
    assert mm_h._op_blocks("fwd", 8, 16, 32) \
        == real("fwd", (8, 16, 32), fmt=mm_h.fmt, spec=mm_h.spec,
                interpret=False)
    assert seen == {False: 1}
    mm_o._op_blocks("fwd", 8, 16, 32)
    assert seen == {False: 1, True: 1}
