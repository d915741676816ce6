"""Backward Pallas kernels (interpret mode) vs oracles + end-to-end training.

Three layers of guarantees, all **bit-exact** (integer code equality, not
tolerance):

1. ``lns_matmul_dx_pallas`` / ``lns_matmul_dw_pallas`` equal their
   sequential-order pure-jnp oracles (``ref.py``) across Δ engines, formats
   and non-multiple-of-block shapes.
2. The :class:`~repro.core.lns.LNSMatmulBackend` dispatcher produces the
   same codes on ``backend="emulate"`` and ``backend="pallas"`` for all
   three products (forward, dX, dW).
3. Training the paper MLP for N steps with ``matmul_backend="pallas"``
   reproduces the emulated run's weight codes exactly — the kernel path is
   a drop-in for the paper's training loop.
"""
import numpy as np
import pytest

import jax

from repro.core import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_EXACT,
                        DELTA_SOFTMAX, LNS12, LNS16, LNSMatmulBackend,
                        encode)
from repro.kernels.lns_matmul import (lns_matmul_dw_kernel,
                                      lns_matmul_dw_ref,
                                      lns_matmul_dx_kernel,
                                      lns_matmul_dx_ref,
                                      lns_matmul_trainable)
from repro.paper.mlp import MLPConfig, make_mlp

SPECS = {"exact": DELTA_EXACT, "lut": DELTA_DEFAULT,
         "softmax": DELTA_SOFTMAX, "bitshift": DELTA_BITSHIFT}


def _operands(rng, m, k, n, fmt, scale=1.0):
    X = (rng.normal(size=(m, k)) * scale).astype(np.float32)
    W = (rng.normal(size=(k, n)) * scale).astype(np.float32)
    DY = (rng.normal(size=(m, n)) * scale).astype(np.float32)
    return encode(X, fmt), encode(W, fmt), encode(DY, fmt)


def _check_dx(dy, w, fmt, spec, **blocks):
    out = lns_matmul_dx_kernel(dy, w, fmt=fmt, spec=spec, **blocks)
    rc, rs = lns_matmul_dx_ref(dy.code, dy.sign, w.code, w.sign,
                               fmt=fmt, spec=spec)
    np.testing.assert_array_equal(np.asarray(out.code), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(out.sign.astype("int32")),
                                  np.asarray(rs))


def _check_dw(x, dy, fmt, spec, **blocks):
    out = lns_matmul_dw_kernel(x, dy, fmt=fmt, spec=spec, **blocks)
    rc, rs = lns_matmul_dw_ref(x.code, x.sign, dy.code, dy.sign,
                               fmt=fmt, spec=spec)
    np.testing.assert_array_equal(np.asarray(out.code), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(out.sign.astype("int32")),
                                  np.asarray(rs))


@pytest.mark.parametrize("spec", list(SPECS.values()), ids=list(SPECS))
def test_backward_kernels_bitexact_all_delta_engines(rng, spec):
    x, w, dy = _operands(rng, 7, 13, 5, LNS16)
    _check_dx(dy, w, LNS16, spec, block_m=8, block_k=8, block_n=8)
    _check_dw(x, dy, LNS16, spec, block_k=8, block_n=8, block_m=8)


@pytest.mark.parametrize("fmt", [LNS16, LNS12], ids=["lns16", "lns12"])
def test_backward_kernels_bitexact_formats(rng, fmt):
    x, w, dy = _operands(rng, 9, 17, 11, fmt)
    _check_dx(dy, w, fmt, DELTA_DEFAULT, block_m=8, block_k=8, block_n=8)
    _check_dw(x, dy, fmt, DELTA_DEFAULT, block_k=8, block_n=8, block_m=8)


@pytest.mark.parametrize("m,k,n", [
    (8, 16, 8),       # exact multiples of the blocks
    (5, 7, 3),        # ragged, smaller than one block
    (20, 34, 12),     # ragged, multi-block on every axis
    (1, 9, 1),        # degenerate vector shapes
])
def test_backward_kernels_nonmultiple_shapes(rng, m, k, n):
    x, w, dy = _operands(rng, m, k, n, LNS16)
    _check_dx(dy, w, LNS16, DELTA_DEFAULT, block_m=8, block_k=8, block_n=8)
    _check_dw(x, dy, LNS16, DELTA_DEFAULT, block_k=8, block_n=8, block_m=8)


def test_backward_kernels_block_shape_invariance(rng):
    """The sequential-contraction semantics must not depend on tiling."""
    x, w, dy = _operands(rng, 10, 18, 6, LNS16)
    a = lns_matmul_dx_kernel(dy, w, fmt=LNS16, spec=DELTA_DEFAULT,
                             block_m=8, block_k=8, block_n=8)
    b = lns_matmul_dx_kernel(dy, w, fmt=LNS16, spec=DELTA_DEFAULT,
                             block_m=16, block_k=8, block_n=4)
    np.testing.assert_array_equal(np.asarray(a.code), np.asarray(b.code))
    c = lns_matmul_dw_kernel(x, dy, fmt=LNS16, spec=DELTA_DEFAULT,
                             block_k=8, block_n=8, block_m=8)
    d = lns_matmul_dw_kernel(x, dy, fmt=LNS16, spec=DELTA_DEFAULT,
                             block_k=4, block_n=16, block_m=8)
    np.testing.assert_array_equal(np.asarray(c.code), np.asarray(d.code))


@pytest.mark.parametrize("op", ["matmul", "matmul_dx", "matmul_dw"])
def test_dispatcher_emulate_vs_pallas_bitexact(rng, op):
    """The config-selected paths are interchangeable code-for-code."""
    x, w, dy = _operands(rng, 6, 14, 4, LNS16)
    args = {"matmul": (x, w), "matmul_dx": (dy, w),
            "matmul_dw": (x, dy)}[op]
    kw = dict(fmt=LNS16, spec=DELTA_DEFAULT,
              block_m=8, block_n=8, block_k=8)
    ze = getattr(LNSMatmulBackend(backend="emulate", **kw), op)(*args)
    zp = getattr(LNSMatmulBackend(backend="pallas", **kw), op)(*args)
    np.testing.assert_array_equal(np.asarray(ze.code), np.asarray(zp.code))
    np.testing.assert_array_equal(np.asarray(ze.sign), np.asarray(zp.sign))


def test_trainable_op_grads_track_float(rng):
    """jax.grad through the custom_vjp ⊞-MAC approximates the float VJP."""
    X = rng.normal(size=(6, 12)).astype(np.float32)
    W = rng.normal(size=(12, 4)).astype(np.float32)

    def loss(x, w):
        return lns_matmul_trainable(x, w, LNSMatmulBackend(
            fmt=LNS16, spec=DELTA_SOFTMAX, backend="pallas", block_m=8,
            block_n=8, block_k=8)).sum()

    gx, gw = jax.grad(loss, argnums=(0, 1))(X, W)
    ones = np.ones((6, 4), np.float32)
    np.testing.assert_allclose(np.asarray(gx), ones @ W.T,
                               rtol=0.1, atol=0.1)
    np.testing.assert_allclose(np.asarray(gw), X.T @ ones,
                               rtol=0.1, atol=0.1)


def test_mlp_training_emulate_vs_pallas_identical_weights(rng):
    """N-step paper-MLP training equivalence: same codes, same signs."""
    xb = rng.uniform(0, 1, size=(5, 12)).astype(np.float32)
    yb = rng.integers(0, 4, size=(5,))
    runs = {}
    for be in ("emulate", "pallas"):
        cfg = MLPConfig(n_in=12, n_hidden=9, n_out=4,
                        spec=f"lns16-train-{be}", matmul_block=8)
        model = make_mlp("lns", cfg)
        params = model.init(jax.random.PRNGKey(0))
        losses = []
        for _ in range(3):
            params, loss = model.train_step(params, xb, yb)
            losses.append(float(loss))
        runs[be] = (params, losses)
    pe, le = runs["emulate"]
    pp, lp = runs["pallas"]
    assert le == lp
    for k in pe:
        np.testing.assert_array_equal(np.asarray(pe[k].code),
                                      np.asarray(pp[k].code), err_msg=k)
        np.testing.assert_array_equal(np.asarray(pe[k].sign),
                                      np.asarray(pp[k].sign), err_msg=k)
    # the run must actually have moved the weights
    init = make_mlp("lns", MLPConfig(n_in=12, n_hidden=9, n_out=4,
                                     matmul_block=8)).init(
        jax.random.PRNGKey(0))
    assert (np.asarray(pe["w1"].code) != np.asarray(init["w1"].code)).any()
