"""Shared layers: norms, MLPs, embeddings, rotary embedding.

All layers are pure functions over explicit param pytrees; ``init_*``
functions are pure in the PRNG key so ``jax.eval_shape`` can derive
ShapeDtypeStruct trees for the dry-run without allocating.

Weight matmuls route through *per-layer* resolved numerics runtimes
(``core.spec.LNSRuntime``): ``nn/model.py`` parses the config's
``numerics`` string as a ``core.plan.NumericsPlan`` and hands every
component (``layers.attn``, ``layers.mlp``, ``emb``, ``head``, ...) the
runtime its layer path resolves to — which is how the paper's LNS
arithmetic becomes a first-class, per-layer mode for every architecture.
``NumericsPolicy`` below is the legacy alias of that runtime type.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.numerics import NumericsPolicy  # = core.spec.LNSRuntime
from .config import ModelConfig, YarnConfig


# ----------------------------------------------------------- norms -------
def init_norm(cfg: ModelConfig, dtype):
    if cfg.norm_kind == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm_kind == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype),
                "bias": jnp.zeros((cfg.d_model,), dtype)}
    if cfg.norm_kind == "nonparam_ln":   # OLMo: no learnable params
        return {}
    raise ValueError(cfg.norm_kind)


def apply_norm(p, x, cfg: ModelConfig):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm_kind == "rmsnorm":
        nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (nrm * p["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    nrm = (xf - mu) * jax.lax.rsqrt(var + eps)
    if cfg.norm_kind == "layernorm":
        nrm = nrm * p["scale"].astype(jnp.float32) \
            + p["bias"].astype(jnp.float32)
    return nrm.astype(x.dtype)


def rms_head_norm(x, scale, eps: float = 1e-6):
    """Per-head RMS norm for qk-norm (Qwen3) — x: (..., d_head)."""
    xf = x.astype(jnp.float32)
    nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (nrm * scale.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------- mlp -------
def init_mlp(key, cfg: ModelConfig, d_hidden: int, dtype):
    d = cfg.d_model
    if cfg.mlp_kind == "glu":
        k1, k2, k3 = jax.random.split(key, 3)
        s_in = (2.0 / d) ** 0.5
        s_out = (2.0 / d_hidden) ** 0.5
        return {
            "w_gate": s_in * jax.random.normal(k1, (d, d_hidden), dtype),
            "w_up": s_in * jax.random.normal(k2, (d, d_hidden), dtype),
            "w_down": s_out * jax.random.normal(k3, (d_hidden, d), dtype),
        }
    k1, k2 = jax.random.split(key)
    return {
        "w_up": (2.0 / d) ** 0.5 * jax.random.normal(k1, (d, d_hidden), dtype),
        "w_down": (2.0 / d_hidden) ** 0.5
        * jax.random.normal(k2, (d_hidden, d), dtype),
    }


def _act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(kind)


def apply_mlp(p, x, cfg: ModelConfig, pol: NumericsPolicy):
    if cfg.mlp_kind == "glu":
        h = _act(pol.linear(x, p["w_gate"]), cfg.act) * pol.linear(x, p["w_up"])
    else:
        h = _act(pol.linear(x, p["w_up"]), cfg.act)
    return pol.linear(h, p["w_down"])


# ------------------------------------------------------- embeddings ------
def init_embeddings(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    v = cfg.padded_vocab
    p = {"tok": jax.random.normal(k1, (v, cfg.d_model), dtype)
         * cfg.d_model ** -0.5}
    if not cfg.tie_embeddings:
        p["head"] = jax.random.normal(
            k2, (cfg.d_model, v), dtype) * cfg.d_model ** -0.5
    return p


def embed_tokens(p, tokens, pol: NumericsPolicy, rt=None):
    """Vocab-parallel embedding lookup.

    With a mesh, the table is sharded (model, None) and a plain gather
    makes GSPMD replicate the (B, S, d) output on every device (measured
    17 GiB/device on the 256k-vocab train cells — §Perf iteration 4), so
    we do the Megatron-style masked local lookup in shard_map and
    reduce-scatter the psum over the sequence dim (matching SP layout).
    """
    w = pol.q_param(p["tok"])
    if rt is None or getattr(rt, "mesh", None) is None:
        return w[tokens]
    from jax.sharding import PartitionSpec as P
    tp = rt.mesh.shape[rt.model_axis]
    d_axes = tuple(rt.data_axes) or None
    scatter_seq = tokens.ndim > 1 and tokens.shape[1] % tp == 0

    def local(w_loc, t_loc):
        vloc = w_loc.shape[0]
        lo = jax.lax.axis_index(rt.model_axis) * vloc
        idx = t_loc - lo
        ok = (idx >= 0) & (idx < vloc)
        x = jnp.where(ok[..., None],
                      w_loc[jnp.clip(idx, 0, vloc - 1)], 0)
        if scatter_seq:
            return jax.lax.psum_scatter(x, rt.model_axis,
                                        scatter_dimension=1, tiled=True)
        return jax.lax.psum(x, rt.model_axis)

    out_spec = P(d_axes, rt.model_axis if scatter_seq else None, None)
    return jax.shard_map(
        local, mesh=rt.mesh,
        in_specs=(P(rt.model_axis, None), P(d_axes, None)),
        out_specs=out_spec, check_vma=False)(w, tokens)


def _mask_pad(logits, cfg: ModelConfig):
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
    return jnp.where(pad, jnp.asarray(-1e30, logits.dtype), logits)


def lm_logits(p, x, pol: NumericsPolicy, cfg: ModelConfig):
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    return _mask_pad(pol.linear(x, w), cfg)


# ----------------------------------------------------------- rotary ------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term ``0.1·mscale·ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_mscale(cfg: ModelConfig) -> float:
    """The factor YaRN puts on the attention softmax scale:
    ``mscale(factor, mscale_all_dim)²`` (1 without ``rope_scaling``)."""
    y = cfg.rope_scaling
    if y is None or not y.mscale_all_dim:
        return 1.0
    return yarn_mscale(y.factor, y.mscale_all_dim) ** 2


def yarn_inv_freq(d: int, theta: float, y: YarnConfig):
    """YaRN's inverse frequencies of a ``d``-dim rotary head, as DeepSeek-V2
    computes them: interpolated (÷ factor) and original frequencies
    blended by a linear ramp over the correction range — dims below it
    keep the original, dims above it are fully interpolated."""
    def dim_of(rot):
        return (d * math.log(y.original_max_position_embeddings
                             / (rot * 2 * math.pi))) / (2 * math.log(theta))

    lo = max(math.floor(dim_of(y.beta_fast)), 0)
    hi = min(math.ceil(dim_of(y.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    pos = jnp.arange(0, d, 2, dtype=jnp.float32) / d
    extra = 1.0 / (theta ** pos)
    inter = 1.0 / (y.factor * theta ** pos)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def apply_rope(x, positions, theta: float,
               scaling: Optional[YarnConfig] = None):
    """x: (B, S, H, D) with D even; positions: (B, S) int32.  Pairs are
    the two halves of each head; ``scaling`` adds YaRN."""
    d = x.shape[-1]
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        freqs = yarn_inv_freq(d, theta, scaling)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


# ----------------------------------------------- chunked cross-entropy ---
def chunked_ce_loss(x, emb_params, labels, pol: NumericsPolicy,
                    cfg: ModelConfig, chunk: int | None = None, rt=None):
    """Mean CE over (B, S) without materializing (B, S, V) at once.

    Scans over sequence chunks; logits/LSE computed in fp32 per chunk.
    The chunk stack is pinned to (batch→data, chunk-seq→model) so the
    reshape across the SP-sharded sequence does not round-trip through
    unsharded fp32 copies (§Perf iteration 7).
    """
    chunk = chunk or cfg.ce_chunk
    b, s, d = x.shape
    n = max(s // chunk, 1)
    c = s // n
    xs = x[:, :n * c].reshape(b, n, c, d).swapaxes(0, 1)      # (n, B, c, d)
    ys = labels[:, :n * c].reshape(b, n, c).swapaxes(0, 1)
    if rt is not None and getattr(rt, "mesh", None) is not None:
        from jax.sharding import PartitionSpec as P
        tp = rt.mesh.shape[rt.model_axis]
        d_axes = tuple(rt.data_axes) or None
        seq_ax = rt.model_axis if c % tp == 0 else None
        xs = rt.constrain(xs, P(None, d_axes, seq_ax, None))
        ys = rt.constrain(ys, P(None, d_axes, seq_ax))

    w = emb_params["tok"].T if cfg.tie_embeddings else emb_params["head"]

    def body(acc, inp):
        xc, yc = inp
        logits = _mask_pad(pol.linear(xc, w), cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - ll), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ys))
    return total / (b * n * c)
