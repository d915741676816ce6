"""Pallas LNS matmul kernel vs pure-jnp oracle (interpret mode).

The kernel preserves the paper's sequential MAC ordering, so comparisons to
ref.py are **bit-exact** across shapes, block shapes, formats and Δ specs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_EXACT,
                        DELTA_SOFTMAX, LNS12, LNS16, decode, encode)
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


@pytest.mark.parametrize("spec", [DELTA_DEFAULT, DELTA_SOFTMAX],
                         ids=["lut20", "lut640"])
@pytest.mark.parametrize("fmt", [LNS16, LNS12], ids=["lns16", "lns12"])
def test_in_kernel_lut_delta_matches_engine_everywhere(fmt, spec):
    """The gather-free compare-select LUT equals DeltaEngine's table
    lookup at every d-code the format can produce, for both signs."""
    from repro.core.delta import DeltaEngine
    from repro.kernels.lns_matmul.lns_matmul import make_delta_fn
    eng = DeltaEngine(spec, fmt)
    d = jnp.arange(0, fmt.code_max - fmt.code_min + 1, dtype=jnp.int32)
    delta = make_delta_fn(spec, fmt)
    np.testing.assert_array_equal(np.asarray(delta(d, True)),
                                  np.asarray(eng.plus(d)))
    np.testing.assert_array_equal(np.asarray(delta(d, False)),
                                  np.asarray(eng.minus(d)))


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
