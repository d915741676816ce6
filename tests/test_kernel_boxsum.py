"""⊞-reduction Pallas kernel vs sequential oracle (bit-exact)."""
import numpy as np
import pytest

from repro.core import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_SOFTMAX, LNS12,
                        LNS16, decode, encode)
from repro.kernels import lns_boxsum_kernel, lns_boxsum_ref


def _run(rng, m, k, fmt, spec, bm=8, bk=16, scale=1.0):
    X = (rng.normal(size=(m, k)) * scale).astype(np.float32)
    x = encode(X, fmt)
    z = lns_boxsum_kernel(x, fmt=fmt, spec=spec, block_m=bm, block_k=bk)
    rc, rs = lns_boxsum_ref(x.code, x.sign, fmt=fmt, spec=spec)
    np.testing.assert_array_equal(np.asarray(z.code), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(z.sign.astype("int32")),
                                  np.asarray(rs))
    return X, z


@pytest.mark.parametrize("m,k", [(8, 16), (5, 7), (16, 100), (1, 640)])
def test_boxsum_bitexact_shapes(rng, m, k):
    _run(rng, m, k, LNS16, DELTA_SOFTMAX)


@pytest.mark.parametrize("spec", [DELTA_DEFAULT, DELTA_BITSHIFT,
                                  DELTA_SOFTMAX], ids=["lut2", "bs", "lut64"])
def test_boxsum_bitexact_specs(rng, spec):
    _run(rng, 12, 33, LNS16, spec)


@pytest.mark.parametrize("fmt", [LNS16, LNS12], ids=["16", "12"])
def test_boxsum_formats(rng, fmt):
    _run(rng, 9, 21, fmt, DELTA_DEFAULT)


def test_boxsum_positive_rows_accuracy(rng):
    """Softmax-denominator regime: positive terms, fine LUT."""
    X = rng.uniform(0.01, 2.0, size=(16, 64)).astype(np.float32)
    x = encode(X, LNS16)
    z = lns_boxsum_kernel(x, fmt=LNS16, spec=DELTA_SOFTMAX,
                          block_m=8, block_k=16)
    got = np.asarray(decode(z, LNS16))
    np.testing.assert_allclose(got, X.sum(1), rtol=0.01)


def test_boxsum_block_invariance(rng):
    X = rng.normal(size=(10, 50)).astype(np.float32)
    x = encode(X, LNS16)
    z1 = lns_boxsum_kernel(x, fmt=LNS16, spec=DELTA_DEFAULT,
                           block_m=8, block_k=8)
    z2 = lns_boxsum_kernel(x, fmt=LNS16, spec=DELTA_DEFAULT,
                           block_m=16, block_k=32)
    np.testing.assert_array_equal(np.asarray(z1.code), np.asarray(z2.code))


def test_boxsum_chip_tiles(rng):
    """The DP combine's fold at compiled-launch tiles (128 lanes of
    elements, an 8-row multiple of segments), zero-code padded."""
    _run(rng, 300, 4, LNS16, DELTA_DEFAULT, bm=128, bk=8)
