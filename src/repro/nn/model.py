"""Model assembly: scan-over-layers transformers for all assigned families.

Entry points (all pure; params created by ``init_params`` — use
``jax.eval_shape(init_params, ...)`` for allocation-free dry-run specs):

  loss_fn(params, batch, cfg, rt)           train:   mean CE (+ MoE aux)
  prefill(params, tokens, cfg, rt)          prefill: last-pos logits + caches
  decode_step(params, tok, caches, pos,...) decode:  next logits + caches

Layer stacks are homogeneous and scanned (`jax.lax.scan`) so the HLO stays
small at any depth; heterogeneous prefixes (MoE first-dense layer, hybrid
tail) are unrolled in Python.  ``cfg.remat`` wraps each block in
``jax.remat``.  Residual activations are sequence-sharded (SP) between
blocks when a Runtime with a mesh is provided.

Numerics are a *per-layer* property: ``cfg.numerics`` parses as a
:class:`~repro.core.plan.NumericsPlan` whose glob rules match the dotted
layer paths in :func:`known_layer_paths` (``emb``, ``layers.attn``,
``layers.mlp``, ..., ``head``); each component receives the runtime its
resolved spec describes, and components whose specs are equal share one
cached runtime (a plan with no rules is exactly the old single-policy
behavior).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.numerics import get_plan
from .attention import (KVCache, gqa_attention, gqa_decode,
                        gqa_decode_paged, gqa_prefill_paged, init_gqa,
                        init_mla, make_cache, make_paged_cache,
                        mla_attention, mla_decode, mla_decode_paged,
                        mla_prefill_paged)
from .config import ModelConfig
from .layers import (apply_mlp, apply_norm, chunked_ce_loss, embed_tokens,
                     init_embeddings, init_mlp, init_norm, lm_logits)
from .moe import MoERuntime, init_moe, moe_block
from .ssm import (SSMCache, init_mamba2, make_ssm_cache, mamba2_decode,
                  mamba2_forward)


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Distribution context; mesh=None → single-device reference mode."""
    mesh: Optional[Any] = None
    data_axes: tuple = ("data",)
    model_axis: str = "model"
    sequence_parallel: bool = True
    experts: Optional[tuple] = None   # (first, count) of the routed experts
                                      # this device holds (MoERuntime)

    @property
    def moe_rt(self) -> MoERuntime:
        return MoERuntime(self.mesh, self.data_axes, self.model_axis,
                          self.experts)

    def constrain(self, x, spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    def sp_spec(self):
        return P(tuple(self.data_axes) or None,
                 self.model_axis if self.sequence_parallel else None, None)


# ----------------------------------------------- per-layer numerics ------
@dataclasses.dataclass(frozen=True)
class BlockPols:
    """The per-component numerics runtimes one block consumes.

    Resolved from the model's :class:`~repro.core.plan.NumericsPlan` at a
    layer-path prefix (``layers``, ``dense_layers``, ``enc_layers``,
    ``shared_attn``, ``tail_layers``): e.g. ``layers.attn`` /
    ``layers.mlp``.  Layers whose resolved specs are equal share one
    cached runtime, so a plan with no rules costs exactly one runtime for
    the whole stack.
    """
    attn: Any = None
    mlp: Any = None
    moe: Any = None
    mamba: Any = None
    xattn: Any = None


def _block_pols(plan, prefix: str, *kinds: str) -> BlockPols:
    return BlockPols(**{k: plan.runtime_for(f"{prefix}.{k}")
                        for k in kinds})


#: Layer paths the LM stack exposes to NumericsPlan glob patterns, per
#: config (for documentation and plan validation).  Only paths this
#: exact config actually instantiates are listed — e.g. a hybrid whose
#: depth divides ``attn_every`` has no ``tail_layers``, and a rule
#: matching only such a ghost path must fail validation, not silently
#: apply to nothing.
def known_layer_paths(cfg: ModelConfig) -> tuple:
    paths = ["emb", "head"]
    if cfg.frontend:
        paths.append("frontend")
    fam = cfg.family
    if fam in ("dense", "vlm"):
        paths += ["layers.attn", "layers.mlp"]
    elif fam == "moe":
        if cfg.moe.first_dense_layers > 0:
            paths += ["dense_layers.attn", "dense_layers.mlp"]
        paths += ["layers.attn", "layers.moe"]
    elif fam == "ssm":
        paths += ["layers.mamba"]
    elif fam == "hybrid":
        paths += ["layers.mamba", "shared_attn.attn", "shared_attn.mlp"]
        if cfg.layers % cfg.hybrid.attn_every:
            paths.append("tail_layers.mamba")
    elif fam in ("encdec", "audio"):
        paths += ["enc_layers.attn", "enc_layers.mlp", "layers.attn",
                  "layers.xattn", "layers.mlp"]
    return tuple(paths)


def _model_plan(cfg: ModelConfig):
    """The config's numerics plan, with its patterns checked against the
    family's layer paths (a typo'd pattern must fail loudly, not silently
    leave a layer on the default arithmetic)."""
    return get_plan(cfg.numerics).validate_paths(known_layer_paths(cfg))


# ------------------------------------------------------------- init ------
def _init_attn(key, cfg, dtype):
    if cfg.attn_kind == "mla":
        return init_mla(key, cfg, dtype)
    return init_gqa(key, cfg, dtype)


def _init_dense_layer(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn": _init_attn(k1, cfg, dtype),
        "mlp": init_mlp(k2, cfg, cfg.d_ff, dtype),
        "norm1": init_norm(cfg, dtype),
        "norm2": init_norm(cfg, dtype),
    }


def _init_moe_layer(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "attn": _init_attn(k1, cfg, dtype),
        "moe": init_moe(k2, cfg, dtype),
        "norm1": init_norm(cfg, dtype),
        "norm2": init_norm(cfg, dtype),
    }


def _init_ssm_layer(key, cfg: ModelConfig, dtype):
    return {"mamba": init_mamba2(key, cfg, dtype), "norm1": init_norm(cfg, dtype)}


def _init_xattn_layer(key, cfg: ModelConfig, dtype):
    """Decoder layer with cross-attention (enc-dec family)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "attn": _init_attn(k1, cfg, dtype),
        "xattn": init_gqa(k2, cfg, dtype),
        "mlp": init_mlp(k3, cfg, cfg.d_ff, dtype),
        "norm1": init_norm(cfg, dtype),
        "norm2": init_norm(cfg, dtype),
        "norm3": init_norm(cfg, dtype),
    }


def _stack(fn, key, n, *args):
    return jax.vmap(lambda k: fn(k, *args))(jax.random.split(key, n))


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    p: dict = {"emb": init_embeddings(keys[0], cfg, dtype),
               "final_norm": init_norm(cfg, dtype)}
    fam = cfg.family
    if fam in ("dense", "vlm"):
        p["layers"] = _stack(_init_dense_layer, keys[1], cfg.layers, cfg, dtype)
        if cfg.frontend:
            p["frontend_proj"] = jax.random.normal(
                keys[2], (cfg.d_model, cfg.d_model), dtype) * cfg.d_model ** -0.5
    elif fam == "moe":
        fd = cfg.moe.first_dense_layers
        p["dense_layers"] = _stack(_init_dense_layer, keys[1],
                                   max(fd, 1), cfg, dtype)
        p["layers"] = _stack(_init_moe_layer, keys[2],
                             max(cfg.layers - fd, 1), cfg, dtype)
    elif fam == "ssm":
        p["layers"] = _stack(_init_ssm_layer, keys[1], cfg.layers, cfg, dtype)
    elif fam == "hybrid":
        k = cfg.hybrid.attn_every
        groups = cfg.layers // k
        tail = cfg.layers - groups * k
        p["layers"] = _stack(_init_ssm_layer, keys[1],
                             max(groups * k, 1), cfg, dtype)
        if tail:
            p["tail_layers"] = _stack(_init_ssm_layer, keys[2], tail, cfg,
                                      dtype)
        p["shared_attn"] = _init_dense_layer(keys[3], cfg, dtype)
    elif fam in ("encdec", "audio"):
        e = cfg.encdec
        p["enc_layers"] = _stack(_init_dense_layer, keys[1],
                                 e.n_enc_layers, cfg, dtype)
        p["layers"] = _stack(_init_xattn_layer, keys[2],
                             e.n_dec_layers, cfg, dtype)
        if cfg.frontend:
            p["frontend_proj"] = jax.random.normal(
                keys[3], (cfg.d_model, cfg.d_model), dtype) * cfg.d_model ** -0.5
    else:
        raise ValueError(fam)
    return p


# ----------------------------------------------------------- blocks ------
def _attn_fwd(lp, x, cfg, pol, positions, rt=None):
    if cfg.attn_kind == "mla":
        return mla_attention(lp, x, cfg, pol, positions, rt)
    return gqa_attention(lp, x, cfg, pol, positions, rt)


def _attn_dec(lp, x, cfg, pol, cache, pos):
    if cfg.attn_kind == "mla":
        return mla_decode(lp, x, cfg, pol, cache, pos)
    return gqa_decode(lp, x, cfg, pol, cache, pos)


def _norm_sp(prm, x, cfg, rt):
    """Norm pinned to the SP layout: without the constraint GSPMD commutes
    the sequence all-gather above the norm and its fp32 intermediates run
    at full S×d (2 GiB each on the 35B/76B cells — §Perf iteration 5)."""
    return rt.constrain(apply_norm(prm, x, cfg), rt.sp_spec())


def _res(x, y):
    """Return a branch output in the residual stream's dtype.

    A no-op under a uniform plan; under mixed per-layer compute dtypes
    the residual dtype is owned by the embedding output, and every block
    branch casts back on re-entry (otherwise the scan carry dtype would
    depend on which layer ran last).
    """
    return y.astype(x.dtype)


def _dense_block(lp, x, cfg, bp: BlockPols, rt, positions):
    br = (lambda t: rt.constrain(t, rt.sp_spec())) if cfg.branch_sp \
        else (lambda t: t)
    if cfg.block_style == "parallel":      # command-r style
        h = _norm_sp(lp["norm1"], x, cfg, rt)
        a, cache = _attn_fwd(lp["attn"], h, cfg, bp.attn, positions, rt)
        f = apply_mlp(lp["mlp"], h, cfg, bp.mlp)
        x = x + br(_res(x, a)) + br(_res(x, f))
    else:
        a, cache = _attn_fwd(lp["attn"], _norm_sp(lp["norm1"], x, cfg, rt),
                             cfg, bp.attn, positions, rt)
        x = x + br(_res(x, a))
        x = x + br(_res(x, apply_mlp(lp["mlp"],
                                     _norm_sp(lp["norm2"], x, cfg, rt),
                                     cfg, bp.mlp)))
    return rt.constrain(x, rt.sp_spec()), cache


def _dense_block_decode(lp, x, cfg, bp: BlockPols, rt, cache, pos):
    if cfg.block_style == "parallel":
        h = apply_norm(lp["norm1"], x, cfg)
        a, cache = _attn_dec(lp["attn"], h, cfg, bp.attn, cache, pos)
        x = x + _res(x, a) + _res(x, apply_mlp(lp["mlp"], h, cfg, bp.mlp))
    else:
        a, cache = _attn_dec(lp["attn"], apply_norm(lp["norm1"], x, cfg),
                             cfg, bp.attn, cache, pos)
        x = x + _res(x, a)
        x = x + _res(x, apply_mlp(lp["mlp"], apply_norm(lp["norm2"], x, cfg),
                                  cfg, bp.mlp))
    return x, cache


def _moe_layer_fwd(lp, x, cfg, bp: BlockPols, rt, positions):
    a, cache = _attn_fwd(lp["attn"], _norm_sp(lp["norm1"], x, cfg, rt),
                         cfg, bp.attn, positions, rt)
    x = rt.constrain(x + _res(x, a), rt.sp_spec())
    y, aux, stats = moe_block(lp["moe"], _norm_sp(lp["norm2"], x, cfg, rt),
                              cfg, bp.moe, rt.moe_rt)
    return rt.constrain(x + _res(x, y), rt.sp_spec()), cache, aux, stats


def _ssm_block(lp, x, cfg, bp: BlockPols, rt):
    y, cache = mamba2_forward(lp["mamba"], _norm_sp(lp["norm1"], x, cfg, rt),
                              cfg, bp.mamba)
    return rt.constrain(x + _res(x, y), rt.sp_spec()), cache


def _maybe_remat(fn, cfg):
    return jax.remat(fn) if cfg.remat == "block" else fn


def _scan(body, init, xs, cfg: ModelConfig):
    """lax.scan, or a Python-unrolled equivalent when cfg.scan_layers is
    False (the roofline's 1-/2-layer lowers need unrolled bodies because
    XLA cost analysis counts a while body once)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, init, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in range(n):
        carry, y = body(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------- forward ------
def _embed_inputs(params, batch, cfg, plan, rt=None):
    """tokens (+ optional stub frontend embeds) → (B, S, d), loss mask."""
    tokens = batch["tokens"]
    x = embed_tokens(params["emb"], tokens, plan.runtime_for("emb"), rt)
    if cfg.frontend and "frontend_embeds" in batch:
        fpol = plan.runtime_for("frontend")
        fe = fpol.linear(batch["frontend_embeds"].astype(fpol.dtype),
                         params["frontend_proj"])
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
    return x


def _backbone(params, x, cfg: ModelConfig, rt: Runtime, positions,
              want_caches: bool = True):
    """Full-sequence pass through the layer stack → (x, caches, aux,
    stats): aux is the MoE balance loss, stats the MoE layers' counters
    stacked over layers (``moe/routed`` rows per held expert,
    ``moe/dropped`` assignments left out; empty for other families).

    ``want_caches=False`` (training) drops the per-layer KV/state outputs
    inside the scan body — otherwise the stacked (L, B, S, ...) caches
    survive through remat+grad and add O(L·B·S·kv·hd) HBM (+10-20 GiB per
    device on the 35B/76B train cells; EXPERIMENTS.md §Perf iteration 2).
    """
    plan = _model_plan(cfg)
    aux_total = jnp.float32(0.0)
    keep = (lambda c: c) if want_caches else (lambda c: None)
    caches, stats = {}, {}
    fam = cfg.family
    if fam in ("dense", "vlm"):
        bp = _block_pols(plan, "layers", "attn", "mlp")
        blk = _maybe_remat(
            lambda h, lp: _dense_block(lp, h, cfg, bp, rt, positions), cfg)

        def body(h, lp):
            h, cache = blk(h, lp)
            return h, keep(cache)

        x, kv = _scan(body, x, params["layers"], cfg)
        caches["layers"] = kv
    elif fam == "moe":
        fd = cfg.moe.first_dense_layers
        bpd = _block_pols(plan, "dense_layers", "attn", "mlp")
        dense_caches = []
        for i in range(fd):
            lp = jax.tree.map(lambda a: a[i], params["dense_layers"])
            x, c = _maybe_remat(
                lambda h, q: _dense_block(q, h, cfg, bpd, rt, positions),
                cfg)(x, lp)
            dense_caches.append(c)
        bp = _block_pols(plan, "layers", "attn", "moe")
        blk = _maybe_remat(
            lambda h, lp: _moe_layer_fwd(lp, h, cfg, bp, rt, positions), cfg)

        def body(h, lp):
            h, cache, aux, st = blk(h, lp)
            return h, (keep(cache), aux, st)

        x, (kv, auxs, st) = _scan(body, x, params["layers"], cfg)
        caches["layers"] = kv
        if dense_caches and want_caches:
            caches["dense_layers"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *dense_caches)
        aux_total = aux_total + jnp.sum(auxs)
        stats = {f"moe/{k}": v for k, v in st.items()}
    elif fam == "ssm":
        bp = _block_pols(plan, "layers", "mamba")
        blk = _maybe_remat(lambda h, lp: _ssm_block(lp, h, cfg, bp, rt), cfg)

        def body(h, lp):
            h, cache = blk(h, lp)
            return h, keep(cache)

        x, ssm = _scan(body, x, params["layers"], cfg)
        caches["layers"] = ssm
    elif fam == "hybrid":
        k = cfg.hybrid.attn_every
        groups = cfg.layers // k
        gp = jax.tree.map(
            lambda a: a[:groups * k].reshape((groups, k) + a.shape[1:]),
            params["layers"])
        bp_ssm = _block_pols(plan, "layers", "mamba")
        bp_attn = _block_pols(plan, "shared_attn", "attn", "mlp")
        ssm_blk = _maybe_remat(
            lambda h, lp: _ssm_block(lp, h, cfg, bp_ssm, rt), cfg)
        attn_blk = _maybe_remat(
            lambda h, lp: _dense_block(lp, h, cfg, bp_attn, rt, positions),
            cfg)

        def group_body(h, glp):
            def inner(hh, lp):
                hh, c = ssm_blk(hh, lp)
                return hh, keep(c)
            h, ssm_c = _scan(inner, h, glp, cfg)
            h, attn_c = attn_blk(h, params["shared_attn"])
            return h, (ssm_c, keep(attn_c))

        x, (ssm_c, attn_c) = _scan(group_body, x, gp, cfg)
        caches["layers"] = ssm_c
        caches["shared_attn"] = attn_c
        if "tail_layers" in params:
            bp_tail = _block_pols(plan, "tail_layers", "mamba")
            tail_blk = _maybe_remat(
                lambda h, lp: _ssm_block(lp, h, cfg, bp_tail, rt), cfg)

            def tail_body(h, lp):
                h2, c = tail_blk(h, lp)
                return h2, keep(c)
            x, tail_c = _scan(tail_body, x, params["tail_layers"], cfg)
            caches["tail_layers"] = tail_c
    else:
        raise ValueError(fam)
    return x, caches, aux_total, stats


def _encoder(params, enc_in, cfg, rt):
    bp = _block_pols(_model_plan(cfg), "enc_layers", "attn", "mlp")
    enc_cfg = cfg.with_(causal=False)
    positions = jnp.broadcast_to(
        jnp.arange(enc_in.shape[1])[None], enc_in.shape[:2])
    blk = _maybe_remat(
        lambda h, lp: _dense_block(lp, h, enc_cfg, bp, rt, positions)[0],
        cfg)

    def body(h, lp):
        return blk(h, lp), None

    x, _ = _scan(body, enc_in, params["enc_layers"], cfg)
    return x


def _decoder(params, x, enc_out, cfg, rt, positions,
             want_caches: bool = True):
    """Enc-dec decoder stack: self-attn + cross-attn + MLP per layer."""
    bp = _block_pols(_model_plan(cfg), "layers", "attn", "mlp", "xattn")
    keep = (lambda c: c) if want_caches else (lambda c: None)

    def block(h, lp):
        a, cache = _attn_fwd(lp["attn"], _norm_sp(lp["norm1"], h, cfg, rt),
                             cfg, bp.attn, positions, rt)
        h = h + _res(h, a)
        q = _norm_sp(lp["norm2"], h, cfg, rt)
        xa, xcache = _cross_attention(lp["xattn"], q, enc_out, cfg, bp.xattn,
                                      rt)
        h = h + _res(h, xa)
        h = h + _res(h, apply_mlp(lp["mlp"], _norm_sp(lp["norm3"], h, cfg, rt),
                                  cfg, bp.mlp))
        return rt.constrain(h, rt.sp_spec()), keep((cache, xcache))

    blk = _maybe_remat(block, cfg)

    def body(h, lp):
        return blk(h, lp)

    x, caches = _scan(body, x, params["layers"], cfg)
    return x, caches


def _cross_attention(lp, q_in, enc_out, cfg, pol, rt=None):
    """Non-causal attention of decoder queries over encoder memory,
    query-chunked (banded, 1 band) so scores never materialize (S, T)."""
    from .attention import _banded_causal, _head_sharded
    b, s, _ = q_in.shape
    t = enc_out.shape[1]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = pol.linear(q_in, lp["wq"]).reshape(b, s, h, hd)
    k = pol.linear(enc_out, lp["wk"]).reshape(b, t, kv, hd)
    v = pol.linear(enc_out, lp["wv"]).reshape(b, t, kv, hd)
    kr = jnp.repeat(k, h // kv, axis=2)
    vr = jnp.repeat(v, h // kv, axis=2)
    q = _head_sharded(q, rt)
    kr = _head_sharded(kr, rt)
    vr = _head_sharded(vr, rt)
    qg = q.reshape(b, s, h, 1, hd)
    o = _banded_causal(qg, kr, vr, hd ** -0.5, cfg.with_(causal=False))
    o = o.reshape(b, s, h * hd)
    return pol.linear(o, lp["wo"]), KVCache(k, v)


# ------------------------------------------------------------- API -------
def loss_fn(params, batch, cfg: ModelConfig, rt: Runtime = Runtime(),
            with_stats: bool = False):
    """Mean next-token CE (+ the MoE balance loss).  batch: tokens,
    labels[, embeds].  ``with_stats`` also returns the backbone's
    counters: ``(loss, stats)``."""
    plan = _model_plan(cfg)
    emb_pol = plan.runtime_for("emb")
    if cfg.family in ("encdec", "audio"):
        if cfg.frontend:
            fpol = plan.runtime_for("frontend")
            enc_in = fpol.linear(
                batch["frontend_embeds"].astype(fpol.dtype),
                params["frontend_proj"])
        else:
            enc_in = embed_tokens(params["emb"], batch["enc_tokens"],
                                  emb_pol, rt)
        enc_out = _encoder(params, rt.constrain(enc_in, rt.sp_spec()),
                           cfg, rt)
        x = embed_tokens(params["emb"], batch["tokens"], emb_pol, rt)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], x.shape[:2])
        x, _ = _decoder(params, x, enc_out, cfg, rt, positions,
                        want_caches=False)
        aux, stats = jnp.float32(0.0), {}
    else:
        x = _embed_inputs(params, batch, cfg, plan, rt)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], x.shape[:2])
        x, _, aux, stats = _backbone(params, x, cfg, rt, positions,
                                     want_caches=False)
    x = apply_norm(params["final_norm"], x, cfg)
    labels = batch["labels"]
    if x.shape[1] != labels.shape[1]:  # frontend prefix carries no loss
        x = x[:, x.shape[1] - labels.shape[1]:]
    loss = chunked_ce_loss(x, params["emb"], labels,
                           plan.runtime_for("head"), cfg, rt=rt) + aux
    return (loss, stats) if with_stats else loss


def prefill(params, batch, cfg: ModelConfig, rt: Runtime = Runtime()):
    """Run the full prompt; return last-position logits + caches."""
    plan = _model_plan(cfg)
    emb_pol = plan.runtime_for("emb")
    if cfg.family in ("encdec", "audio"):
        if cfg.frontend:
            fpol = plan.runtime_for("frontend")
            enc_in = fpol.linear(
                batch["frontend_embeds"].astype(fpol.dtype),
                params["frontend_proj"])
        else:
            enc_in = embed_tokens(params["emb"], batch["enc_tokens"],
                                  emb_pol, rt)
        enc_out = _encoder(params, enc_in, cfg, rt)
        x = embed_tokens(params["emb"], batch["tokens"], emb_pol, rt)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], x.shape[:2])
        x, caches = _decoder(params, x, enc_out, cfg, rt, positions)
        caches = {"layers": caches, "enc_out": enc_out}
    else:
        x = _embed_inputs(params, batch, cfg, plan, rt)
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], x.shape[:2])
        x, caches, _, _ = _backbone(params, x, cfg, rt, positions)
    x = apply_norm(params["final_norm"], x[:, -1:], cfg)
    return lm_logits(params["emb"], x, plan.runtime_for("head"), cfg), caches


def init_decode_caches(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.bfloat16, enc_len: int | None = None):
    """Empty fixed-capacity caches for decode (eval_shape-friendly)."""
    fam = cfg.family

    def stack_kv(n):
        one = make_cache(cfg, batch, max_len, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                            one)

    def stack_ssm(n):
        one = make_ssm_cache(cfg, batch, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                            one)

    if fam in ("dense", "vlm"):
        return {"layers": stack_kv(cfg.layers)}
    if fam == "moe":
        fd = cfg.moe.first_dense_layers
        return {"dense_layers": stack_kv(max(fd, 1)),
                "layers": stack_kv(max(cfg.layers - fd, 1))}
    if fam == "ssm":
        return {"layers": stack_ssm(cfg.layers)}
    if fam == "hybrid":
        k = cfg.hybrid.attn_every
        groups = cfg.layers // k
        tail = cfg.layers - groups * k
        out = {"layers": stack_ssm(groups * k),
               "shared_attn": stack_kv(groups)}
        if tail:
            out["tail_layers"] = stack_ssm(tail)
        return out
    if fam in ("encdec", "audio"):
        e = cfg.encdec
        enc_len = enc_len or max_len
        xkv = make_cache(cfg.with_(attn_kind="gqa"), batch, enc_len, dtype)
        return {
            "layers": (stack_kv(e.n_dec_layers),
                       jax.tree.map(
                           lambda a: jnp.broadcast_to(
                               a, (e.n_dec_layers,) + a.shape), xkv)),
            "enc_out": jnp.zeros((batch, enc_len, cfg.d_model), dtype),
        }
    raise ValueError(fam)


def decode_step(params, tok, caches, pos, cfg: ModelConfig,
                rt: Runtime = Runtime()):
    """One token for every sequence in the batch.

    tok: (B, 1) int32; pos: (B,) int32 current positions.
    Returns (logits (B, 1, V), new caches).
    """
    plan = _model_plan(cfg)
    x = embed_tokens(params["emb"], tok, plan.runtime_for("emb"), rt)
    fam = cfg.family
    new_caches = dict(caches)
    if fam in ("dense", "vlm", "moe"):
        def scan_dense(x, stack, cache, prefix):
            bp = _block_pols(plan, prefix, "attn", "mlp")

            def body(carry, inp):
                h = carry
                lp, c = inp
                h, c2 = _dense_block_decode(lp, h, cfg, bp, rt, c, pos)
                return h, c2
            x, kv = _scan(body, x, (stack, cache), cfg)
            return x, kv

        if fam == "moe":
            x, kv_d = scan_dense(x, params["dense_layers"],
                                 caches["dense_layers"], "dense_layers")
            new_caches["dense_layers"] = kv_d
            bp = _block_pols(plan, "layers", "attn", "moe")

            def body(carry, inp):
                h = carry
                lp, c = inp
                a, c2 = _attn_dec(lp["attn"],
                                  apply_norm(lp["norm1"], h, cfg), cfg,
                                  bp.attn, c, pos)
                h = h + _res(h, a)
                y, _, _ = moe_block(lp["moe"],
                                    apply_norm(lp["norm2"], h, cfg), cfg,
                                    bp.moe, rt.moe_rt)
                return h + _res(h, y), c2

            x, kv = _scan(body, x, (params["layers"],
                                           caches["layers"]), cfg)
            new_caches["layers"] = kv
        else:
            x, kv = scan_dense(x, params["layers"], caches["layers"],
                               "layers")
            new_caches["layers"] = kv
    elif fam == "ssm":
        bp = _block_pols(plan, "layers", "mamba")

        def body(h, inp):
            lp, c = inp
            y, c2 = mamba2_decode(lp["mamba"],
                                  apply_norm(lp["norm1"], h, cfg), cfg,
                                  bp.mamba, c)
            return h + _res(h, y), c2

        x, ssm = _scan(body, x, (params["layers"], caches["layers"]), cfg)
        new_caches["layers"] = ssm
    elif fam == "hybrid":
        k = cfg.hybrid.attn_every
        groups = cfg.layers // k
        gp = jax.tree.map(
            lambda a: a[:groups * k].reshape((groups, k) + a.shape[1:]),
            params["layers"])
        gc = jax.tree.map(
            lambda a: a.reshape((groups, k) + a.shape[1:]),
            caches["layers"])
        bp_ssm = _block_pols(plan, "layers", "mamba")
        bp_attn = _block_pols(plan, "shared_attn", "attn", "mlp")

        def group_body(h, inp):
            glp, gcache, attn_c = inp

            def inner(hh, iinp):
                lp, c = iinp
                y, c2 = mamba2_decode(lp["mamba"],
                                      apply_norm(lp["norm1"], hh, cfg), cfg,
                                      bp_ssm.mamba, c)
                return hh + _res(hh, y), c2

            h, ssm_c = _scan(inner, h, (glp, gcache), cfg)
            h, attn_c2 = _dense_block_decode(params["shared_attn"], h, cfg,
                                             bp_attn, rt, attn_c, pos)
            return h, (ssm_c, attn_c2)

        x, (ssm_c, attn_c) = _scan(
            group_body, x, (gp, gc, caches["shared_attn"]), cfg)
        new_caches["layers"] = jax.tree.map(
            lambda a: a.reshape((groups * k,) + a.shape[2:]), ssm_c)
        new_caches["shared_attn"] = attn_c
        if "tail_layers" in params:
            bp_tail = _block_pols(plan, "tail_layers", "mamba")

            def tail(h, inp):
                lp, c = inp
                y, c2 = mamba2_decode(lp["mamba"],
                                      apply_norm(lp["norm1"], h, cfg), cfg,
                                      bp_tail.mamba, c)
                return h + _res(h, y), c2
            x, tail_c = _scan(tail, x, (params["tail_layers"],
                                               caches["tail_layers"]), cfg)
            new_caches["tail_layers"] = tail_c
    elif fam in ("encdec", "audio"):
        enc_out = caches["enc_out"]
        bp = _block_pols(plan, "layers", "attn", "mlp", "xattn")

        def body(h, inp):
            lp, (c_self, c_cross) = inp
            a, c2 = _attn_dec(lp["attn"], apply_norm(lp["norm1"], h, cfg),
                              cfg, bp.attn, c_self, pos)
            h = h + _res(h, a)
            q = apply_norm(lp["norm2"], h, cfg)
            xa, _ = _cross_attention(lp["xattn"], q, enc_out, cfg, bp.xattn,
                                     rt)
            h = h + _res(h, xa)
            h = h + _res(h, apply_mlp(lp["mlp"], apply_norm(lp["norm3"], h, cfg),
                                      cfg, bp.mlp))
            return h, (c2, c_cross)

        x, kv = _scan(body, x, (params["layers"], caches["layers"]), cfg)
        new_caches["layers"] = kv
    else:
        raise ValueError(fam)
    x = apply_norm(params["final_norm"], x, cfg)
    return lm_logits(params["emb"], x, plan.runtime_for("head"), cfg), \
        new_caches


# ------------------------------------------------- paged serving ---------
#: Families the paged serving data plane supports: every per-layer cache
#: is a KVCache growing along the sequence dim.  SSM/hybrid state caches
#: are O(1) per slot (nothing to page) and enc-dec carries a static
#: cross-attention memory; those families serve via the dense reference
#: path (``repro.serve.engine.reference_generate``).
PAGED_FAMILIES = ("dense", "vlm", "moe")


class _InferPol:
    """Serving view of a layer's numerics runtime.

    Matmuls route through ``LNSRuntime.linear_infer`` — the fused
    forward-epilogue backend surface (``matmul_fused``) for Δ-spec'd
    kernel paths, bit-identical to ``linear``'s forward — so decode and
    prefill ride PR 5's one-pass kernels without the custom_vjp machinery
    training needs.  Everything else forwards to the wrapped runtime.
    """

    __slots__ = ("rt",)

    def __init__(self, rt):
        self.rt = rt

    def linear(self, x, w):
        return self.rt.linear_infer(x, w)

    def grouped_linear(self, x, w, sizes):
        return self.rt.grouped_linear(x, w, sizes)

    def q_param(self, w):
        return self.rt.q_param(w)

    def q_act(self, x):
        return self.rt.q_act(x)

    @property
    def dtype(self):
        return self.rt.dtype

    @property
    def name(self):
        return self.rt.name


def _infer_pols(bp: BlockPols) -> BlockPols:
    return BlockPols(**{
        f.name: (_InferPol(v) if v is not None else None)
        for f in dataclasses.fields(BlockPols)
        for v in [getattr(bp, f.name)]})


def init_paged_caches(cfg: ModelConfig, num_blocks: int, block_size: int,
                      dtype=jnp.bfloat16):
    """Empty paged decode caches: per-stack page pools, shared block ids.

    Every layer owns ``num_blocks`` physical blocks addressed by ONE
    block-table space (a slot's logical block *i* lives at the same
    physical id in every layer) — allocation happens once per logical
    block, in the serve-layer :class:`~repro.serve.paged_cache.BlockManager`.
    """
    fam = cfg.family
    if fam not in PAGED_FAMILIES:
        raise ValueError(
            f"family {fam!r} has no paged KV cache (supported: "
            f"{PAGED_FAMILIES}); serve it via the dense path "
            f"(init_decode_caches / reference_generate)")

    def stack(n):
        one = make_paged_cache(cfg, num_blocks, block_size, dtype)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape),
                            one)

    if fam == "moe":
        fd = cfg.moe.first_dense_layers
        return {"dense_layers": stack(max(fd, 1)),
                "layers": stack(max(cfg.layers - fd, 1))}
    return {"layers": stack(cfg.layers)}


def _attn_dec_paged(lp, x, cfg, pol, cache, bt, pos, active):
    if cfg.attn_kind == "mla":
        return mla_decode_paged(lp, x, cfg, pol, cache, bt, pos, active)
    return gqa_decode_paged(lp, x, cfg, pol, cache, bt, pos, active)


def _attn_prefill_paged(lp, x, cfg, pol, cache, bt_row, pos_base, n_valid):
    if cfg.attn_kind == "mla":
        return mla_prefill_paged(lp, x, cfg, pol, cache, bt_row, pos_base,
                                 n_valid)
    return gqa_prefill_paged(lp, x, cfg, pol, cache, bt_row, pos_base,
                             n_valid)


def _dense_block_decode_paged(lp, x, cfg, bp: BlockPols, cache, bt, pos,
                              active):
    if cfg.block_style == "parallel":
        h = apply_norm(lp["norm1"], x, cfg)
        a, cache = _attn_dec_paged(lp["attn"], h, cfg, bp.attn, cache, bt,
                                   pos, active)
        x = x + _res(x, a) + _res(x, apply_mlp(lp["mlp"], h, cfg, bp.mlp))
    else:
        a, cache = _attn_dec_paged(lp["attn"],
                                   apply_norm(lp["norm1"], x, cfg), cfg,
                                   bp.attn, cache, bt, pos, active)
        x = x + _res(x, a)
        x = x + _res(x, apply_mlp(lp["mlp"], apply_norm(lp["norm2"], x, cfg),
                                  cfg, bp.mlp))
    return x, cache


def decode_step_paged(params, tok, caches, bt, pos, active,
                      cfg: ModelConfig, rt: Runtime = Runtime()):
    """One token for every slot against the paged KV cache.

    tok: (B, 1) int32; bt: (B, W) block tables; pos: (B,) int32; active:
    (B,) bool — inactive slots (free, or mid-prefill) write to the null
    block and their logits are meaningless.  Matmuls run the fused-infer
    numerics path (:class:`_InferPol`).  Returns (logits (B, 1, V), new
    caches).
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(f"decode_step_paged: unsupported family "
                         f"{cfg.family!r} (supported: {PAGED_FAMILIES})")
    plan = _model_plan(cfg)
    x = embed_tokens(params["emb"], tok, _InferPol(plan.runtime_for("emb")),
                     rt)
    new_caches = dict(caches)

    def scan_dense(x, stack, cache, prefix):
        bp = _infer_pols(_block_pols(plan, prefix, "attn", "mlp"))

        def body(h, inp):
            lp, c = inp
            return _dense_block_decode_paged(lp, h, cfg, bp, c, bt, pos,
                                             active)

        return _scan(body, x, (stack, cache), cfg)

    if cfg.family == "moe":
        x, kv_d = scan_dense(x, params["dense_layers"],
                             caches["dense_layers"], "dense_layers")
        new_caches["dense_layers"] = kv_d
        bp = _infer_pols(_block_pols(plan, "layers", "attn", "moe"))

        def body(h, inp):
            lp, c = inp
            a, c2 = _attn_dec_paged(lp["attn"],
                                    apply_norm(lp["norm1"], h, cfg), cfg,
                                    bp.attn, c, bt, pos, active)
            h = h + _res(h, a)
            y, _, _ = moe_block(lp["moe"], apply_norm(lp["norm2"], h, cfg),
                                cfg, bp.moe, rt.moe_rt)
            return h + _res(h, y), c2

        x, kv = _scan(body, x, (params["layers"], caches["layers"]), cfg)
        new_caches["layers"] = kv
    else:
        x, kv = scan_dense(x, params["layers"], caches["layers"], "layers")
        new_caches["layers"] = kv
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["emb"], x,
                       _InferPol(plan.runtime_for("head")), cfg)
    return logits, new_caches


def prefill_chunk(params, tok, caches, bt_row, pos_base, n_valid,
                  cfg: ModelConfig, rt: Runtime = Runtime()):
    """One chunked-prefill step for ONE slot: splice C cache lines, return
    the logits at the last valid position.

    tok: (1, C) int32 — a prompt chunk at logical positions ``pos_base +
    arange(C)``, padded beyond ``n_valid`` so every chunk length shares
    one compiled graph.  KV lines are written directly into the slot's
    pages (cache splice) — prompt tokens never pass through the batched
    decode step, so a prefill never stalls other slots' decodes for more
    than one chunk's compute.  Returns (logits (1, 1, V), new caches);
    the logits are those of position ``pos_base + n_valid - 1`` (what the
    first sampled continuation token conditions on).
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(f"prefill_chunk: unsupported family "
                         f"{cfg.family!r} (supported: {PAGED_FAMILIES})")
    plan = _model_plan(cfg)
    x = embed_tokens(params["emb"], tok, _InferPol(plan.runtime_for("emb")),
                     rt)
    new_caches = dict(caches)

    def block_prefill(lp, h, bp, c):
        hn = apply_norm(lp["norm1"], h, cfg)
        if cfg.block_style == "parallel":
            a, c2 = _attn_prefill_paged(lp["attn"], hn, cfg, bp.attn, c,
                                        bt_row, pos_base, n_valid)
            h = h + _res(h, a) + _res(h, apply_mlp(lp["mlp"], hn, cfg,
                                                   bp.mlp))
        else:
            a, c2 = _attn_prefill_paged(lp["attn"], hn, cfg, bp.attn, c,
                                        bt_row, pos_base, n_valid)
            h = h + _res(h, a)
            h = h + _res(h, apply_mlp(lp["mlp"],
                                      apply_norm(lp["norm2"], h, cfg),
                                      cfg, bp.mlp))
        return h, c2

    def scan_dense(x, stack, cache, prefix):
        bp = _infer_pols(_block_pols(plan, prefix, "attn", "mlp"))

        def body(h, inp):
            lp, c = inp
            return block_prefill(lp, h, bp, c)

        return _scan(body, x, (stack, cache), cfg)

    if cfg.family == "moe":
        x, kv_d = scan_dense(x, params["dense_layers"],
                             caches["dense_layers"], "dense_layers")
        new_caches["dense_layers"] = kv_d
        bp = _infer_pols(_block_pols(plan, "layers", "attn", "moe"))

        def body(h, inp):
            lp, c = inp
            a, c2 = _attn_prefill_paged(lp["attn"],
                                        apply_norm(lp["norm1"], h, cfg),
                                        cfg, bp.attn, c, bt_row, pos_base,
                                        n_valid)
            h = h + _res(h, a)
            y, _, _ = moe_block(lp["moe"], apply_norm(lp["norm2"], h, cfg),
                                cfg, bp.moe, rt.moe_rt)
            return h + _res(h, y), c2

        x, kv = _scan(body, x, (params["layers"], caches["layers"]), cfg)
        new_caches["layers"] = kv
    else:
        x, kv = scan_dense(x, params["layers"], caches["layers"], "layers")
        new_caches["layers"] = kv
    # Only the last valid position's logits matter (they seed the first
    # decode step); slicing before the head matmul keeps the lm head at
    # (1, 1, d) regardless of chunk size.
    x = jax.lax.dynamic_slice_in_dim(x, jnp.maximum(n_valid - 1, 0), 1,
                                     axis=1)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = lm_logits(params["emb"], x,
                       _InferPol(plan.runtime_for("head")), cfg)
    return logits, new_caches
