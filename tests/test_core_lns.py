import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (LNS12, LNS16, LNS21, LNSArray, convert_format,
                        decode, encode, quantization_bound, scalar, zeros)

FMT = [LNS16, LNS12]

finite_vals = st.floats(
    min_value=-15.0, max_value=15.0, allow_nan=False, allow_infinity=False
).filter(lambda v: v == 0.0 or abs(v) > 2 ** -9)


@settings(max_examples=200, deadline=None)
@given(v=finite_vals)
def test_roundtrip_relative_error(v):
    fmt = LNS16
    out = float(decode(encode(np.float32(v), fmt), fmt))
    if v == 0.0:
        assert out == 0.0
    else:
        assert abs(out - v) <= (quantization_bound(fmt) * abs(v)) * (1 + 1e-5)


@settings(max_examples=100, deadline=None)
@given(v=finite_vals)
def test_sign_preserved(v):
    fmt = LNS12
    a = encode(np.float32(v), fmt)
    if v > 0:
        assert int(a.sign) == 0
    elif v < 0:
        assert int(a.sign) == 1


@pytest.mark.parametrize("fmt", FMT)
def test_zero_and_underflow(fmt):
    a = encode(np.zeros(3, np.float32), fmt)
    assert (np.asarray(a.code) == fmt.zero_code).all()
    assert (np.asarray(decode(a, fmt)) == 0).all()
    # deep underflow flushes to zero
    tiny = encode(np.float32(2.0 ** (fmt.code_min / fmt.scale - 10)), fmt)
    assert int(tiny.code) == fmt.zero_code


@pytest.mark.parametrize("fmt", FMT)
def test_overflow_saturates(fmt):
    big = encode(np.float32(1e30), fmt)
    assert int(big.code) == fmt.code_max
    assert float(decode(big, fmt)) == pytest.approx(fmt.max_value)


def test_scalar_matches_encode():
    fmt = LNS16
    for v in (0.01, -3.7, 1.0, 0.0):
        s = scalar(v, fmt)
        e = encode(np.float32(v), fmt)
        assert int(s.code) == int(e.code)
        assert int(s.sign) == int(e.sign)


def test_zeros_helper():
    z = zeros((2, 3), LNS16)
    assert z.shape == (2, 3)
    assert (np.asarray(decode(z, LNS16)) == 0).all()


def test_pytree_flattening():
    import jax

    z = zeros((4,), LNS16)
    leaves, _ = jax.tree_util.tree_flatten(z)
    assert len(leaves) == 2
    mapped = jax.tree.map(lambda x: x, z)
    assert mapped.shape == (4,)


def test_encode_is_jittable():
    import jax

    f = jax.jit(lambda v: encode(v, LNS16).code)
    v = jnp.array([1.0, -2.0, 0.0, 0.5])
    np.testing.assert_array_equal(f(v), encode(v, LNS16).code)


# ------------------------------------------------- convert_format edges
def _arr(codes, signs, dtype_sign="int8"):
    return LNSArray(jnp.asarray(codes, jnp.int32),
                    jnp.asarray(signs, dtype_sign))


def test_convert_format_identity_when_same():
    a = encode(np.float32([1.5, -0.25, 0.0]), LNS16)
    b = convert_format(a, LNS16, LNS16)
    assert b is a


@pytest.mark.parametrize("src,dst", [(LNS16, LNS12), (LNS16, LNS21),
                                     (LNS12, LNS16), (LNS12, LNS21),
                                     (LNS21, LNS12)])
def test_convert_format_zero_code_preserved(src, dst):
    """The reserved exact-zero sentinel maps to the destination's
    sentinel, with the sign cleared."""
    a = _arr([src.zero_code, src.zero_code], [0, 1])
    b = convert_format(a, src, dst)
    assert (np.asarray(b.code) == dst.zero_code).all()
    assert (np.asarray(b.sign) == 0).all()


def test_convert_format_saturating_narrowing_at_extremes():
    """Codes beyond the narrow format's range saturate (top) or flush to
    the zero sentinel (bottom) instead of wrapping."""
    a = _arr([LNS16.code_max, LNS16.min_nonzero_code,
              LNS16.code_min + 5], [0, 1, 1])
    b = convert_format(a, LNS16, LNS12)
    bc = np.asarray(b.code)
    # lns16 code_max (log2 ≈ 16) exceeds lns12's max → saturate.
    assert bc[0] == LNS12.code_max
    # most negative magnitudes underflow lns12's resolution → zero, and
    # the sign plane must be cleared with them.
    assert bc[1] == LNS12.zero_code and int(b.sign[1]) == 0
    assert bc[2] == LNS12.zero_code and int(b.sign[2]) == 0


def test_convert_format_narrowing_rounds_half_up():
    """Narrowing divides the code grid by 2^(qf_src - qf_dst) with
    round-half-up: code 8 (= 0.5 ulp at Δqf=4) rounds to 1, code 7 to 0."""
    shift = LNS16.qf - LNS12.qf  # 4
    assert shift == 4
    a = _arr([8, 7, -8, 24], [0, 0, 0, 1])
    b = convert_format(a, LNS16, LNS12)
    np.testing.assert_array_equal(np.asarray(b.code), [1, 0, 0, 2])


def test_convert_format_widening_roundtrip_identity():
    """Widening is an exact left shift, so narrow → wide → narrow is the
    identity on every representable narrow code (and sign)."""
    codes = np.arange(LNS12.min_nonzero_code, LNS12.code_max + 1,
                      dtype=np.int32)
    signs = (codes % 2 == 0).astype(np.int8)
    a = _arr(codes, signs)
    for wide in (LNS16, LNS21):
        up = convert_format(a, LNS12, wide)
        back = convert_format(up, wide, LNS12)
        np.testing.assert_array_equal(np.asarray(back.code), codes)
        np.testing.assert_array_equal(np.asarray(back.sign), signs)
        # the widened magnitude decodes to the same value exactly
        np.testing.assert_array_equal(np.asarray(decode(a, LNS12)),
                                      np.asarray(decode(up, wide)))


def test_convert_format_value_roundtrip_via_floats():
    """Against the float codec: converting codes matches re-encoding the
    decoded values (up to the narrow format's own quantization)."""
    rng = np.random.default_rng(0)
    v = (rng.normal(size=64) * 3).astype(np.float32)
    a = encode(v, LNS16)
    b = convert_format(a, LNS16, LNS12)
    direct = encode(np.asarray(decode(a, LNS16)), LNS12)
    # round-half-up on the code grid vs round-nearest through log2 can
    # differ by at most one ulp of the narrow grid
    assert np.abs(np.asarray(b.code) - np.asarray(direct.code)).max() <= 1


def test_resolve_interpret_explicit_flag_wins_else_platform():
    """Interpret mode is decided in one place: an explicit flag wins, and
    None means compiled exactly when the platform is a TPU."""
    import jax
    from repro.core.lns import LNSMatmulBackend, resolve_interpret
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    be = LNSMatmulBackend(fmt=LNS16, spec=None, backend="pallas")
    assert be._interp() is resolve_interpret(None)
