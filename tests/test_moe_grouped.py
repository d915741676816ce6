"""The expert layer that holds a share of the experts, the grouped ⊞-MAC
kernels under it, and DeepSeek-V2's router, balance loss and YaRN rope.

CPU, tiny sizes (d_model 64, 8 experts), Pallas in interpret mode.
"""
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import LNS16, LNSMatmulBackend, encode
from repro.core.plan import NumericsPlan
from repro.core.spec import DELTA_NAMES
from repro.kernels.lns_matmul import lns_gmm_trainable
from repro.kernels.lns_matmul.grouped import (lns_gmm_dw_pallas,
                                              lns_gmm_dx_pallas,
                                              lns_gmm_pallas)
from repro.kernels.lns_matmul.lns_matmul import (lns_matmul_dw_pallas,
                                                 lns_matmul_dx_pallas,
                                                 lns_matmul_pallas)
from repro.nn import Runtime, init_params, loss_fn
from repro.nn.config import MoEConfig
from repro.nn.layers import softmax_mscale, yarn_inv_freq
from repro.nn.moe import (_balance_loss, _balance_sums, _route,
                          _shared_ffn, moe_layer)
from repro.optim import make_optimizer
from repro.optim.optimizers import SGDConfig
from repro.train import init_train_state, make_train_step

LUT20 = DELTA_NAMES["lut20"]
#: Rows per expert: uneven, one empty, one over the 8-row tile; 40 rows
#: in all, the last 7 routed to no expert here.
SIZES = (5, 0, 19, 9)
M, K, N = 40, 24, 20
#: The expert layer's tiny size: d_model 64, 8 experts of width 32.
D, E, DE = 64, 8, 32
PLAN = "lns16-train-pallas"


def _codes(rng, *shape):
    a = encode(jnp.asarray(rng.normal(size=shape), jnp.float32), LNS16)
    return a.code, a.sign.astype(jnp.int32)


def _same(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


@pytest.mark.parametrize("op", ["fwd", "dx", "dw"])
def test_grouped_kernel_equals_per_expert_plain_kernels(op):
    """Each expert's rows through the grouped kernel give, bit for bit,
    what one plain ⊞-MAC launch on those rows gives; unrouted rows and an
    empty expert's dW are the zero code."""
    rng = np.random.default_rng(0)
    x, dy = _codes(rng, M, K), _codes(rng, M, N)
    w = _codes(rng, len(SIZES), K, N)
    sizes = jnp.asarray(SIZES, jnp.int32)
    kw = dict(fmt=LNS16, spec=LUT20, interpret=True)
    blocks = dict(block_m=8, block_n=8, block_k=8)
    gblocks = dict(block_rows=8, block_n=8, block_k=8)
    grouped, plain = {
        "fwd": (lns_gmm_pallas, lns_matmul_pallas),
        "dx": (lns_gmm_dx_pallas, lns_matmul_dx_pallas),
        "dw": (lns_gmm_dw_pallas, lns_matmul_dw_pallas)}[op]
    a, b = (x, w) if op == "fwd" else (dy, w) if op == "dx" else (x, dy)
    got = grouped(*a, *b, sizes, **gblocks, **kw)
    ends = np.cumsum(SIZES)
    for e, (lo, hi) in enumerate(zip(ends - SIZES, ends)):
        if op == "dw":
            if lo == hi:
                assert (np.asarray(got[0][e]) == LNS16.zero_code).all()
                continue
            want = plain(x[0][lo:hi], x[1][lo:hi], dy[0][lo:hi],
                         dy[1][lo:hi], **blocks, **kw)
            assert _same((got[0][e], got[1][e]), want), e
        elif hi > lo:
            want = plain(a[0][lo:hi], a[1][lo:hi], w[0][e], w[1][e],
                         **blocks, **kw)
            assert _same((got[0][lo:hi], got[1][lo:hi]), want), e
    if op != "dw":
        assert (np.asarray(got[0][ends[-1]:]) == LNS16.zero_code).all()


def test_grouped_trainable_kernels_equal_emulation():
    """The differentiable grouped ⊞-MAC: forward, dX and dW on the
    kernels equal the per-expert emulated ⊞-MACs bit for bit."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(SIZES), K, N)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(M, N)), jnp.float32)
    sizes = jnp.asarray(SIZES, jnp.int32)

    def run(backend):
        be = LNSMatmulBackend(fmt=LNS16, spec=LUT20, backend=backend,
                              block_m=8, block_n=8, block_k=8)
        f = lambda x, w: lns_gmm_trainable(x, w, sizes, be)
        y, vjp = jax.vjp(f, x, w)
        return (y,) + vjp(g)

    for a, b in zip(run("pallas"), run("emulate")):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _cfg(numerics="fp32", **moe):
    m = dict(n_experts=E, top_k=6, n_shared=2, d_expert=DE,
             first_dense_layers=1, norm_topk_prob=False)
    m.update(moe)
    return reduced(get_config("deepseek-v2-lite-16b")).with_(
        numerics=numerics, remat="none", moe=MoEConfig(**m))


def _layer(seed=0, numerics="fp32", **moe):
    cfg = _cfg(numerics, **moe)
    p = jax.tree.map(lambda a: a[0],
                     init_params(jax.random.PRNGKey(seed), cfg)["layers"]
                     ["moe"])
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 16, D))
    pol = NumericsPlan.parse(numerics).runtime_for("layers.moe")
    return cfg, p, x, pol


def _plain_routed(p, x, cfg, ffn):
    """Every expert on every token, each token's chosen ones picked out
    and weighted by their gates in float32 (summed in top-k order)."""
    xf = x.reshape(-1, x.shape[-1])
    w, ids, _ = _route(p, xf, cfg.moe)
    y = jnp.stack([ffn(xf, e) for e in range(p["w_gate"].shape[0])])
    y = y[ids, jnp.arange(xf.shape[0])[:, None]].astype(jnp.float32)
    return jnp.sum(y * w[..., None], axis=1)


@pytest.mark.parametrize("numerics", ["fp32", PLAN])
def test_expert_layer_matches_plain_reference(numerics):
    """The grouped layer against every expert run densely over all the
    tokens.  A row's result depends on that row alone, so the two agree
    to float32 rounding; on the ⊞-MAC path a rounding that crosses an LNS
    rounding boundary moves an element by one code, 2^-10 in log2."""
    cfg, p, x, pol = _layer(numerics=numerics)

    def ffn(xf, e):
        h = jax.nn.silu(pol.linear(xf, p["w_gate"][e])) \
            * pol.linear(xf, p["w_up"][e])
        return pol.linear(h, p["w_down"][e])

    def shared(xf):
        h = jax.nn.silu(pol.linear(xf, p["shared_gate"])) \
            * pol.linear(xf, p["shared_up"])
        return pol.linear(h, p["shared_down"])

    y, _, stats = jax.jit(lambda p, x: moe_layer(p, x, cfg, pol))(p, x)
    xf = x.reshape(-1, D)
    want = jax.jit(lambda p, x: _plain_routed(p, x, cfg, ffn)
                   + shared(xf))(p, x).reshape(x.shape)
    rtol = 1e-5 if numerics == "fp32" else 2 ** (1 / 1024) - 1
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=rtol,
                               atol=1e-5)
    assert int(jnp.sum(stats["routed"])) == xf.shape[0] * cfg.moe.top_k
    assert int(stats["dropped"]) == 0


@pytest.mark.parametrize("held", [2, 4])
def test_expert_shares_sum_to_the_whole_layer(held):
    """Each share routes over all 8 experts and gives its own experts'
    part; the shares' routed parts, with the shared experts counted
    once, add up to the unsplit layer in float32."""
    cfg, p, x, pol = _layer(seed=3)
    whole, _, _ = moe_layer(p, x, cfg, pol)
    experts = ("w_gate", "w_up", "w_down")
    # The shared experts' output is 0 with a zero down projection.
    none = dict(p, shared_down=jnp.zeros_like(p["shared_down"]))
    routed, rows = jnp.zeros(x.shape, jnp.float32), 0
    for first in range(0, E, held):
        share = {k: (v[first:first + held] if k in experts else v)
                 for k, v in none.items()}
        y, _, stats = moe_layer(share, x, cfg, pol, first)
        routed, rows = routed + y, rows + int(jnp.sum(stats["routed"]))
    shared = _shared_ffn(p, x.reshape(-1, D), cfg, pol).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert rows == x.shape[0] * x.shape[1] * cfg.moe.top_k


@pytest.mark.parametrize("norm", [False, True])
def test_router_and_balance_loss_follow_deepseek_v2(norm):
    """Greedy top-k of the float32 softmax, renormalized only where the
    config says so, and the sequence-wise balance loss as DeepSeek-V2's
    reference code computes it."""
    cfg, p, x, _ = _layer(norm_topk_prob=norm, balance_coef=0.003)
    m = cfg.moe
    b, s, _ = x.shape
    w, ids, probs = _route(p, x.reshape(-1, D), m)
    pr = np.asarray(probs, np.float64)
    top = np.argsort(-pr, axis=-1)[:, :m.top_k]
    assert np.array_equal(np.sort(np.asarray(ids), -1), np.sort(top, -1))
    want = np.take_along_axis(pr, np.asarray(ids), -1)
    if norm:
        want = want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-6)
    # DeepseekV2MoEGate (seq_aux): counts scattered per sequence over
    # (seq_len · top_k / n_experts), times the mean probability.
    ids_s = np.asarray(ids).reshape(b, s * m.top_k)
    ce = np.stack([np.bincount(r, minlength=m.n_experts) for r in ids_s])
    ce = ce / (s * m.top_k / m.n_experts)
    want = (ce * pr.reshape(b, s, -1).mean(1)).sum(1).mean() * 0.003
    got = _balance_loss(*_balance_sums(ids, probs, b, m.n_experts), s, m)
    np.testing.assert_allclose(float(got), want, rtol=1e-5)


def _hf_yarn_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """DeepseekV2YarnRotaryEmbedding's inv_freq, transcribed."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                               dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask, low, high


@pytest.mark.parametrize("dim", [64, 8])
def test_yarn_frequencies_match_the_published_formula(dim):
    y = get_config("deepseek-v2-lite-16b").rope_scaling
    want, low, high = _hf_yarn_inv_freq(dim, 10_000.0, y.factor,
                                        y.original_max_position_embeddings,
                                        y.beta_fast, y.beta_slow)
    if dim == 64:   # V2-Lite's ramp: from dim ⌊10.47⌋ = 10 to ⌈22.5⌉ = 23
        assert (low, high) == (10, 23)
    np.testing.assert_allclose(np.asarray(yarn_inv_freq(dim, 10_000.0, y)),
                               want, rtol=1e-6)
    cfg = get_config("deepseek-v2-lite-16b")
    assert softmax_mscale(cfg) == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert softmax_mscale(cfg.with_(rope_scaling=None)) == 1.0


def test_step_counters_leave_the_weights_unchanged():
    """The MoE step's counters (rows per held expert and layer, dropped
    assignments) come out with the metrics; its gradients are those of
    the loss without them, bit for bit, and so are the weights."""
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    params["layers"]["moe"] = {
        k: (v[:, 4:] if k in ("w_gate", "w_up", "w_down") else v)
        for k, v in params["layers"]["moe"].items()}
    rt = Runtime(experts=(4, 4))
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 17), 0, 256)
    batch = {"tokens": tok[:, :-1], "labels": tok[:, 1:]}
    opt = SGDConfig(lr=0.1)
    state = init_train_state(params, opt)
    new, metrics = jax.jit(make_train_step(cfg, opt, rt))(state, batch)
    _, update = make_optimizer(opt)

    @jax.jit
    def plain_step(p, s):
        g = jax.grad(lambda q: loss_fn(q, batch, cfg, rt))(p)
        return update(p, g, s["opt"], s["step"])[0]

    plain = plain_step(params, state)
    for a, b in zip(jax.tree.leaves(new["params"]), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    routed = np.asarray(metrics["moe/routed"])
    assert routed.shape == (cfg.layers - 1, 4)
    assert 0 < routed.sum() <= 16 * cfg.moe.top_k * (cfg.layers - 1)
    assert np.asarray(metrics["moe/dropped"]).tolist() == [0] * (
        cfg.layers - 1)


def test_expert_parallel_mesh_paths_equal_one_device():
    """On 4 virtual devices the replicated path (each shard's part, then
    a psum) and the all-to-all path give the one-device layer."""
    code = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
import test_moe_grouped as t
from repro.nn.moe import MoERuntime, moe_block
cfg, p, x, pol = t._layer(seed=5, capacity_factor=8.0)
one = jax.jit(lambda p, x: moe_block(p, x, cfg, pol))
whole, aux, st = one(p, x)
mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
rt = MoERuntime(mesh)
ep = jax.jit(lambda p, x: moe_block(p, x, cfg, pol, rt))
for xs in (x, x[:, :1]):
    want = whole if xs.shape[1] > 1 else one(p, xs)[0]
    y, a, s = ep(p, xs)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert int(s["dropped"]) == 0
    assert int(jnp.sum(s["routed"])) == xs.shape[0] * xs.shape[1] * 6
np.testing.assert_allclose(float(ep(p, x)[1]), float(aux), rtol=1e-5)
print("ok")
"""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(os.path.dirname(here), "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [here, src, os.environ.get("PYTHONPATH", "")])}
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and r.stdout.strip().endswith("ok"), \
        r.stderr[-3000:]
