"""Pallas LNS matmul kernel vs pure-jnp oracle (interpret mode).

The kernel preserves the paper's sequential MAC ordering, so comparisons to
ref.py are **bit-exact** across shapes, block shapes, formats and Δ specs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_EXACT,
                        DELTA_SOFTMAX, LNS12, LNS16, decode, encode)
from repro.core.delta import DeltaSpec
from repro.kernels.lns_matmul import lns_matmul_kernel, lns_matmul_ref


def _run(rng, m, k, n, fmt, spec, bm=8, bn=8, bk=16, scale=1.0):
    X = (rng.normal(size=(m, k)) * scale).astype(np.float32)
    W = (rng.normal(size=(k, n)) * scale).astype(np.float32)
    x, w = encode(X, fmt), encode(W, fmt)
    z = lns_matmul_kernel(x, w, fmt=fmt, spec=spec,
                          block_m=bm, block_n=bn, block_k=bk)
    rc, rs = lns_matmul_ref(x.code, x.sign, w.code, w.sign,
                            fmt=fmt, spec=spec)
    np.testing.assert_array_equal(np.asarray(z.code), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(z.sign.astype("int32")),
                                  np.asarray(rs))
    return X, W, z


@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8),        # exactly one block
    (16, 32, 16),      # multi-block every axis
    (5, 7, 3),         # ragged, smaller than one block
    (20, 50, 12),      # ragged, multi-block
    (1, 100, 1),       # degenerate vector dot
])
def test_kernel_bitexact_shapes(rng, m, k, n):
    _run(rng, m, k, n, LNS16, DELTA_DEFAULT)


@pytest.mark.parametrize("spec", [DELTA_DEFAULT, DELTA_BITSHIFT,
                                  DELTA_SOFTMAX, DELTA_EXACT],
                         ids=["lut2", "bitshift", "lut64", "exact"])
def test_kernel_bitexact_specs(rng, spec):
    _run(rng, 12, 24, 10, LNS16, spec)


@pytest.mark.parametrize("fmt", [LNS16, LNS12], ids=["lns16", "lns12"])
def test_kernel_bitexact_formats(rng, fmt):
    _run(rng, 9, 17, 11, fmt, DELTA_DEFAULT)


@pytest.mark.parametrize("bm,bn,bk", [(8, 8, 8), (8, 16, 32), (16, 8, 8)])
def test_kernel_block_shape_invariance(rng, bm, bn, bk):
    """Output must not depend on tiling (sequential-K semantics)."""
    X = rng.normal(size=(17, 40)).astype(np.float32)
    W = rng.normal(size=(40, 9)).astype(np.float32)
    x, w = encode(X, LNS16), encode(W, LNS16)
    z1 = lns_matmul_kernel(x, w, fmt=LNS16, spec=DELTA_DEFAULT,
                           block_m=bm, block_n=bn, block_k=bk)
    z2 = lns_matmul_kernel(x, w, fmt=LNS16, spec=DELTA_DEFAULT,
                           block_m=8, block_n=8, block_k=16)
    np.testing.assert_array_equal(np.asarray(z1.code), np.asarray(z2.code))


def test_kernel_accuracy_vs_float(rng):
    """With the fine softmax LUT the kernel tracks the float matmul."""
    X, W, z = _run(rng, 16, 64, 8, LNS16, DELTA_SOFTMAX)
    got = np.asarray(decode(z, LNS16))
    ref = X @ W
    rel = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-2)
    assert np.median(rel) < 0.02


def test_kernel_zero_inputs(rng):
    X = np.zeros((8, 16), np.float32)
    W = rng.normal(size=(16, 8)).astype(np.float32)
    x, w = encode(X, LNS16), encode(W, LNS16)
    z = lns_matmul_kernel(x, w, fmt=LNS16, spec=DELTA_DEFAULT)
    assert (np.asarray(decode(z, LNS16)) == 0).all()


def test_kernel_mixed_scale(rng):
    """Wide dynamic range exercises saturation paths identically."""
    _run(rng, 8, 12, 8, LNS12, DELTA_DEFAULT, scale=5.0)
    _run(rng, 8, 12, 8, LNS12, DELTA_DEFAULT, scale=0.01)


#: (format, Δ, lanes of the d-planes): None for 1-D planes.  lut20 reads
#: its table with the lane gather, 128 lanes at a time; lut640 keeps the
#: compare-select chain.
DELTA_CASES = {
    "lns16-lut20": (LNS16, DELTA_DEFAULT, None),
    "lns16-lut640": (LNS16, DELTA_SOFTMAX, None),
    "lns12-lut20": (LNS12, DELTA_DEFAULT, None),
    "lns12-lut640": (LNS12, DELTA_SOFTMAX, None),
    "lns16-lut20-lanes128": (LNS16, DELTA_DEFAULT, 128),
    "lns12-lut20-lanes128": (LNS12, DELTA_DEFAULT, 128),
    "lns16-lut20-lanes256": (LNS16, DELTA_DEFAULT, 256),
}


@pytest.mark.parametrize("case", list(DELTA_CASES))
def test_in_kernel_lut_delta_matches_engine_everywhere(case):
    """The in-kernel LUT (lane gather for lut20, compare-select chain for
    lut640) equals DeltaEngine's table lookup at every d-code the format
    can produce, for both signs, and for signs mixed within a plane."""
    from repro.core.delta import DeltaEngine
    from repro.kernels.lns_matmul.lns_matmul import delta_path, make_delta_fn
    fmt, spec, lanes = DELTA_CASES[case]
    eng = DeltaEngine(spec, fmt)
    d = jnp.arange(0, fmt.code_max - fmt.code_min + 1, dtype=jnp.int32)
    if lanes:
        d = d.reshape(-1, lanes)
    want = "lut-gather" if spec == DELTA_DEFAULT else "lut-chain"
    assert delta_path(spec, fmt) == want
    delta = make_delta_fn(spec, fmt, d.shape[0] if lanes else 1)
    plus, minus = np.asarray(eng.plus(d)), np.asarray(eng.minus(d))
    np.testing.assert_array_equal(np.asarray(delta(d, True)), plus)
    np.testing.assert_array_equal(np.asarray(delta(d, False)), minus)
    same = (d % 3) == 0
    np.testing.assert_array_equal(np.asarray(delta(d, same)),
                                  np.where(same, plus, minus))


@pytest.mark.parametrize("fmt,spec,shape,path", [
    (LNS16, DELTA_DEFAULT, (128, 128), "lut-gather"),
    (LNS12, DELTA_DEFAULT, (128, 128), "lut-gather"),
    (LNS16, DELTA_DEFAULT, (1, 256), "lut-gather"),
    (LNS16, DELTA_SOFTMAX, (128, 128), "lut-chain"),
    (LNS16, DeltaSpec("lut", 10.0, 0.75), (128, 128), "lut-chain"),
    (LNS16, DELTA_BITSHIFT, (128, 128), "bitshift"),
    (LNS16, DELTA_EXACT, (128, 128), "exact"),
], ids=["lut20-lns16", "lut20-lns12", "lut20-256lanes", "lut640",
        "lns16-r0.75", "bitshift", "exact"])
def test_delta_path_rule(fmt, spec, shape, path):
    """The gather takes a LUT whose resolution is a power of two in code
    units and whose tables fit 64 lanes; every other LUT keeps the chain,
    and both give DeltaEngine's Δ± on planes of ``shape``."""
    from repro.core.delta import DeltaEngine
    from repro.kernels.lns_matmul.lns_matmul import delta_path, make_delta_fn
    assert delta_path(spec, fmt) == path
    d = jnp.arange(int(np.prod(shape)), dtype=jnp.int32).reshape(shape) * 7
    eng = DeltaEngine(spec, fmt)
    delta = make_delta_fn(spec, fmt, shape[0])
    np.testing.assert_array_equal(np.asarray(delta(d, True)),
                                  np.asarray(eng.plus(d)))
    np.testing.assert_array_equal(np.asarray(delta(d, False)),
                                  np.asarray(eng.minus(d)))


def _wide_case(name, rng):
    """(kernel outputs, reference outputs) of one interpret-mode launch
    whose planes are 256 lanes wide: two 128-lane gathers a ⊞."""
    from repro.core import (DeltaEngine, LogSGDConfig, UpdateEpilogue,
                            apply_update_codes, zeros)
    from repro.core.lns import LNSArray
    from repro.kernels import lns_boxsum_ref
    from repro.kernels.lns_boxsum.lns_boxsum import lns_boxsum_pallas
    from repro.kernels.lns_matmul import (FwdEpilogue,
                                          lns_matmul_dw_update_ref,
                                          lns_matmul_fused_ref)
    from repro.kernels.lns_matmul.grouped import lns_gmm_pallas
    from repro.kernels.lns_matmul.lns_matmul import (
        lns_matmul_dw_update_pallas, lns_matmul_fused_pallas)
    from repro.kernels.lns_matmul.ref import lns_gmm_ref
    from repro.kernels.lns_matmul.update import lns_fused_update_pallas

    def codes(*shape):
        a = encode(rng.normal(size=shape).astype(np.float32), LNS16)
        return a.code, a.sign.astype(jnp.int32)

    kw = dict(fmt=LNS16, spec=DELTA_DEFAULT)
    sgd = UpdateEpilogue.from_sgd(
        LogSGDConfig(lr=0.01, weight_decay=0.001, momentum=0.9), LNS16)
    if name == "fwd-bias":
        (xc, xs), (wc, ws), (bc, bs) = codes(8, 12), codes(12, 256),             codes(256)
        ep = FwdEpilogue(bias=True, llrelu_beta=5)
        got = lns_matmul_fused_pallas(
            xc, xs, wc, ws, epilogue=ep, bias_code=bc, bias_sign=bs,
            block_m=8, block_n=256, block_k=8, interpret=True, **kw)
        want = lns_matmul_fused_ref(xc, xs, wc, ws, epilogue=ep,
                                    bias_code=bc, bias_sign=bs, **kw)[:2]
    elif name == "dw-update":
        (xc, xs), (dc, ds), (wc, ws) = codes(12, 8), codes(12, 256),             codes(8, 256)
        m = zeros((8, 256), LNS16)
        got = lns_matmul_dw_update_pallas(
            xc, xs, dc, ds, w_code=wc, w_sign=ws, m_code=m.code,
            m_sign=m.sign.astype(jnp.int32), epilogue=sgd, block_k=8,
            block_n=256, block_m=8, interpret=True, **kw)
        rw, rm = lns_matmul_dw_update_ref(
            xc, xs, dc, ds, w=LNSArray(wc, ws.astype("int8")), m=m,
            epilogue=sgd, **kw)
        want = (rw.code, rw.sign, rm.code, rm.sign)
    elif name == "gmm":
        (xc, xs), (wc, ws) = codes(20, 12), codes(3, 12, 256)
        sizes = jnp.asarray((7, 0, 11), jnp.int32)
        got = lns_gmm_pallas(xc, xs, wc, ws, sizes, block_rows=8,
                             block_n=256, block_k=8, interpret=True, **kw)
        want = lns_gmm_ref(xc, xs, wc, ws, sizes, **kw)
    elif name == "boxsum":
        c, s = codes(300, 9)
        got = lns_boxsum_pallas(c, s, block_m=256, block_k=8,
                                interpret=True, **kw)
        want = lns_boxsum_ref(c, s, **kw)
    else:
        (wc, ws), (gc, gs) = codes(3, 200), codes(3, 200)
        m = zeros((3, 200), LNS16)
        got = lns_fused_update_pallas(
            wc, ws, gc, gs, m_code=m.code, m_sign=m.sign.astype(jnp.int32),
            epilogue=sgd, block=256, interpret=True, **kw)
        rw, rm = apply_update_codes(LNSArray(wc, ws.astype("int8")),
                                    LNSArray(gc, gs.astype("int8")), m, sgd,
                                    DeltaEngine(DELTA_DEFAULT, LNS16))
        want = (rw.code, rw.sign, rm.code, rm.sign)
    return got, want


@pytest.mark.parametrize("name", ["fwd-bias", "dw-update", "gmm", "boxsum",
                                  "fused-update"])
def test_gather_path_bitexact_on_wide_planes(rng, name):
    """lut20 launches whose planes span two vregs of lanes — the fused
    forward with its bias and llrelu flush, dW with the ⊞-SGD update
    flush, a grouped launch, the DP fold — and the standalone update on
    several 128-lane rows take the lane gather and stay bit-exact against
    their references."""
    from repro.kernels.lns_matmul.lns_matmul import delta_path
    assert delta_path(DELTA_DEFAULT, LNS16) == "lut-gather"
    got, want = _wide_case(name, rng)
    assert len(got) >= len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g),
                                      np.asarray(w).astype(np.int32))


def test_tile_rule():
    """A block covering the axis pads it to the alignment; a shorter one
    must itself be aligned."""
    from repro.kernels.lns_matmul.lns_matmul import tile
    assert tile(128, 100, 128) == 128
    assert tile(512, 784, 8) == 512
    assert tile(1024, 784, 8) == 784
    assert tile(128, 6, 8) == 8
    assert tile(8, 100, 1) == 8
    with pytest.raises(ValueError, match="tiling rule"):
        tile(32, 100, 128)


@pytest.mark.parametrize("m,k,n", [(20, 50, 12), (64, 100, 10)])
def test_kernel_bitexact_chip_tiles(rng, m, k, n):
    """The tiles a compiled launch uses — 128-wide rows and columns, an
    8-row multiple of contraction — padded with the zero code, bit-exact."""
    _run(rng, m, k, n, LNS16, DELTA_DEFAULT, bm=128, bn=128, bk=24)
