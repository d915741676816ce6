"""Attention: GQA (query-chunked, causal-exact) and MLA (DeepSeek-V2).

Training/prefill attention is *query-chunked*: a Python loop over Q blocks
where block ``i`` attends only to keys ``[0, (i+1)·c)`` via static-size
slices — peak memory O(c·S) per block and **no wasted flops** on masked-out
blocks (unlike full-mask attention, which doubles causal FLOPs).  Scores and
softmax are fp32.

Decode uses a fixed-capacity KV cache updated with dynamic_update_slice and
a length mask.  MLA decode is *absorbed* (q projected into the latent space;
per-step cost O(S·lora) instead of re-up-projecting the cache).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..core.numerics import NumericsPolicy
from .config import ModelConfig
from .layers import apply_rope, rms_head_norm, softmax_mscale
from .paged import paged_gather, paged_write_chunk, paged_write_token


class KVCache(NamedTuple):
    k: jax.Array          # GQA: (B, S, KV, hd) | MLA: (B, S, lora)
    v: jax.Array          # GQA: (B, S, KV, hd) | MLA: (B, S, rope)


# ------------------------------------------------------------- GQA -------
def init_gqa(key, cfg: ModelConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": s * jax.random.normal(ks[0], (d, h * hd), dtype),
        "wk": s * jax.random.normal(ks[1], (d, kv * hd), dtype),
        "wv": s * jax.random.normal(ks[2], (d, kv * hd), dtype),
        "wo": (h * hd) ** -0.5 * jax.random.normal(ks[3], (h * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _sdpa_block(q, k, v, scale, mask):
    """q: (B,c,KV,G,hd), k/v: (B,t,KV,hd) → (B,c,KV,G,hd); fp32 softmax."""
    sc = jnp.einsum("bckgh,btkh->bkgct", q, k).astype(jnp.float32) * scale
    if mask is not None:
        sc = jnp.where(mask, sc, jnp.float32(-1e30))
    p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgct,btkh->bckgh", p, v)


def gqa_qkv(p, x, cfg: ModelConfig, pol: NumericsPolicy, positions):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = pol.linear(x, p["wq"]).reshape(b, s, h, hd)
    k = pol.linear(x, p["wk"]).reshape(b, s, kv, hd)
    v = pol.linear(x, p["wv"]).reshape(b, s, kv, hd)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def _banded_causal(qg, k, v, scale, cfg: ModelConfig):
    """Banded-causal SDPA: Python loop over ``attn_bands`` bands (static KV
    extent per band — exact FLOPs at band granularity, overhead ≤
    (nb+1)/nb of true causal) with a lax.scan over query chunks inside
    each band, so only ONE (c × band_end) score block is live at a time.
    A fully unrolled chunk loop lets XLA overlap chunk buffers, which blew
    past HBM on 32k prefill (see EXPERIMENTS.md §Perf iteration 1).
    """
    b, s, kvh, g, hd = qg.shape
    vd = v.shape[-1]          # may differ from hd (MLA: qk=192, v=128)
    c = min(cfg.q_chunk, s)
    nb = max(min(cfg.attn_bands, s // c), 1) if cfg.causal else 1
    per_band = s // nb
    assert per_band % c == 0 or per_band == 0, (s, nb, c)
    outs = []
    for j in range(nb):
        lo, hi = j * per_band, ((j + 1) * per_band if cfg.causal else s)
        kj, vj = k[:, :hi], v[:, :hi]
        qj = qg[:, lo:lo + per_band].reshape(b, per_band // c, c, kvh, g, hd)
        qj = jnp.moveaxis(qj, 1, 0)                     # (nc, B, c, ...)
        offs = lo + jnp.arange(per_band // c) * c

        def body(_, inp, kj=kj, vj=vj, hi=hi):
            qc, off = inp
            if cfg.causal:
                qpos = off + jnp.arange(c)
                mask = (qpos[:, None] >= jnp.arange(hi)[None, :])
                mask = mask[None, None, None]
            else:
                mask = None
            return None, _sdpa_block(qc, kj, vj, scale, mask)

        if cfg.attn_remat:
            # recompute scores/probs in backward: without this, every
            # band's fp32 probabilities are saved simultaneously
            # (Σ_j c·band_j ≈ S²(nb+1)/2nb per head — ~5 GiB/layer at 4k)
            body = jax.remat(body)
        _, oj = jax.lax.scan(body, None, (qj, offs))
        outs.append(jnp.moveaxis(oj, 0, 1).reshape(b, per_band, kvh, g, vd))
    return jnp.concatenate(outs, axis=1)


def _head_sharded(x, rt, heads_axis=2):
    """Pin the heads dim to the model axis (rt duck-typed: see model.Runtime).

    Without this, GQA with kv_heads < tp makes GSPMD tile scores over
    (kv × group) dims that K/V cannot match → 'involuntary full
    rematerialization' replication copies (EXPERIMENTS.md §Perf iter. 2).
    """
    if rt is None or getattr(rt, "mesh", None) is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = [None] * x.ndim
    spec[0] = tuple(rt.data_axes) or None
    spec[heads_axis] = rt.model_axis
    return rt.constrain(x, P(*spec))


def gqa_attention(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                  positions, rt=None) -> tuple[jax.Array, KVCache]:
    """Causal self-attention over a full sequence (train / prefill).

    K/V are repeated to the full head count: every arch's n_heads divides
    tp=16, so q/k/v/scores all shard cleanly over the model axis (the
    repeat is sharded — no per-device blowup), unlike the (kv, group)
    factorization.  Decode keeps the compact grouped cache.
    """
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q, k, v = gqa_qkv(p, x, cfg, pol, positions)
    kr = jnp.repeat(k, h // kv, axis=2)
    vr = jnp.repeat(v, h // kv, axis=2)
    q = _head_sharded(q, rt)
    kr = _head_sharded(kr, rt)
    vr = _head_sharded(vr, rt)
    qg = q.reshape(b, s, h, 1, hd)
    scale = hd ** -0.5
    o = _banded_causal(qg, kr, vr, scale, cfg)  # non-causal: 1 band, no mask
    o = o.reshape(b, s, h * hd)
    return pol.linear(o, p["wo"]), KVCache(k, v)


def gqa_decode(p, x, cfg: ModelConfig, pol: NumericsPolicy, cache: KVCache,
               pos) -> tuple[jax.Array, KVCache]:
    """One-token decode against a fixed-capacity cache.

    x: (B, 1, d); pos: (B,) current positions; cache arrays (B, S, KV, hd).
    """
    b, _, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    g = h // kv
    q, k_new, v_new = gqa_qkv(p, x, cfg, pol, pos[:, None])
    smax = cache.k.shape[1]
    # write new K/V at pos (per-batch dynamic index)
    idx = pos[:, None, None, None]
    arange = jnp.arange(smax)[None, :, None, None]
    k = jnp.where(arange == idx, k_new, cache.k)
    v = jnp.where(arange == idx, v_new, cache.v)
    qg = q.reshape(b, 1, kv, g, hd)
    valid = (jnp.arange(smax)[None, :] <= pos[:, None])
    mask = valid[:, None, None, None, :]
    o = _sdpa_block(qg, k, v, hd ** -0.5, mask).reshape(b, 1, h * hd)
    return pol.linear(o, p["wo"]), KVCache(k, v)


# --------------------------------------------------------- paged GQA -----
def gqa_decode_paged(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                     cache: KVCache, bt, pos, active
                     ) -> tuple[jax.Array, KVCache]:
    """One-token batched decode against a paged (block) KV cache.

    cache arrays: (NB, bs, KV, hd) shared page pool; bt: (B, W) block
    tables; pos: (B,) logical positions; active: (B,) bool — inactive
    slots write to the null block and their outputs carry no meaning.
    Attention runs over the gathered (B, W·bs) logical view with the same
    length mask as the dense path, so unallocated pages contribute
    exactly-zero softmax weight.
    """
    b, _, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q, k_new, v_new = gqa_qkv(p, x, cfg, pol, pos[:, None])
    k_pages = paged_write_token(cache.k, bt, pos, k_new[:, 0], active)
    v_pages = paged_write_token(cache.v, bt, pos, v_new[:, 0], active)
    k = paged_gather(k_pages, bt)                   # (B, W·bs, KV, hd)
    v = paged_gather(v_pages, bt)
    smax = k.shape[1]
    qg = q.reshape(b, 1, kv, h // kv, hd)
    mask = (jnp.arange(smax)[None, :] <= pos[:, None])[:, None, None, None]
    o = _sdpa_block(qg, k, v, hd ** -0.5, mask).reshape(b, 1, h * hd)
    return pol.linear(o, p["wo"]), KVCache(k_pages, v_pages)


def gqa_prefill_paged(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                      cache: KVCache, bt_row, pos_base, n_valid
                      ) -> tuple[jax.Array, KVCache]:
    """Chunked-prefill attention for ONE slot: splice then attend.

    x: (1, C, d) — a prompt chunk at logical positions ``pos_base +
    arange(C)`` (entries ≥ ``n_valid`` are padding so every chunk reuses
    one compiled graph).  The chunk's K/V lines are written directly into
    the slot's pages (no per-token decode loop), then the C queries attend
    causally over the gathered logical view — which already contains every
    previous chunk's lines, so cross-chunk attention needs no extra state.
    """
    _, c, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    lpos = pos_base + jnp.arange(c)
    q, k_new, v_new = gqa_qkv(p, x, cfg, pol, lpos[None])
    k_pages = paged_write_chunk(cache.k, bt_row, pos_base, k_new[0], n_valid)
    v_pages = paged_write_chunk(cache.v, bt_row, pos_base, v_new[0], n_valid)
    k = paged_gather(k_pages, bt_row[None])         # (1, W·bs, KV, hd)
    v = paged_gather(v_pages, bt_row[None])
    smax = k.shape[1]
    qg = q.reshape(1, c, kv, h // kv, hd)
    mask = (jnp.arange(smax)[None, :] <= lpos[:, None])[None, None, None]
    o = _sdpa_block(qg, k, v, hd ** -0.5, mask).reshape(1, c, h * hd)
    return pol.linear(o, p["wo"]), KVCache(k_pages, v_pages)


# ------------------------------------------------------------- MLA -------
def init_mla(key, cfg: ModelConfig, dtype):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    return {
        "wq": s * jax.random.normal(
            ks[0], (d, h * (m.nope_head_dim + m.rope_head_dim)), dtype),
        "w_dkv": s * jax.random.normal(
            ks[1], (d, m.kv_lora_rank + m.rope_head_dim), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_ukv": m.kv_lora_rank ** -0.5 * jax.random.normal(
            ks[2], (m.kv_lora_rank, h * (m.nope_head_dim + m.v_head_dim)),
            dtype),
        "wo": (h * m.v_head_dim) ** -0.5 * jax.random.normal(
            ks[3], (h * m.v_head_dim, d), dtype),
    }


def _mla_latents(p, x, cfg, pol, positions):
    """Compressed KV latents + positional key: (B,S,lora), (B,S,rope)."""
    m = cfg.mla
    dkv = pol.linear(x, p["w_dkv"])
    c_kv = rms_head_norm(dkv[..., :m.kv_lora_rank], p["kv_norm"])
    k_pe = dkv[..., m.kv_lora_rank:][:, :, None, :]   # single rope head
    k_pe = apply_rope(k_pe, positions, cfg.rope_theta,
                      cfg.rope_scaling)[:, :, 0, :]
    return c_kv, k_pe


def _mla_q(p, x, cfg, pol, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q = pol.linear(x, p["wq"]).reshape(
        b, s, h, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_pe = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_pe


def mla_attention(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                  positions, rt=None) -> tuple[jax.Array, KVCache]:
    """Full-sequence MLA (train / prefill): up-project then standard SDPA."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    c_kv, k_pe = _mla_latents(p, x, cfg, pol, positions)
    ukv = pol.linear(c_kv, p["w_ukv"]).reshape(
        b, s, h, m.nope_head_dim + m.v_head_dim)
    k_nope, v = ukv[..., :m.nope_head_dim], ukv[..., m.nope_head_dim:]
    q_nope, q_pe = _mla_q(p, x, cfg, pol, positions)
    k_pe_b = jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, m.rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], -1)
    k = jnp.concatenate([k_nope, k_pe_b], -1)
    q = _head_sharded(q, rt)
    k = _head_sharded(k, rt)
    v = _head_sharded(v, rt)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5 * softmax_mscale(cfg)
    qg = q.reshape(b, s, h, 1, q.shape[-1])  # reuse grouped SDPA, G=1
    o = _banded_causal(qg, k, v, scale, cfg)
    o = o.reshape(b, s, h * m.v_head_dim)
    return pol.linear(o, p["wo"]), KVCache(c_kv, k_pe)


def _mla_absorbed(p, x, cfg: ModelConfig, pol: NumericsPolicy, ck, kpe,
                  positions, mask):
    """Absorbed MLA attention of (B, Q, d) queries over latent caches.

    ck: (B, S, lora) compressed latents; kpe: (B, S, rope) positional
    keys; mask: bool broadcastable to (B, H, Q, S).  Per-query cost is
    O(S·(lora+rope)) per head — the MLA win; shared by one-token decode
    (Q=1, length mask) and chunked prefill (Q=C, causal mask).
    """
    m = cfg.mla
    b, qn = x.shape[0], x.shape[1]
    h = cfg.n_heads
    q_nope, q_pe = _mla_q(p, x, cfg, pol, positions)
    w_ukv = pol.q_param(p["w_ukv"]).reshape(
        m.kv_lora_rank, h, m.nope_head_dim + m.v_head_dim)
    w_uk = w_ukv[..., :m.nope_head_dim]             # (lora, H, nope)
    w_uv = w_ukv[..., m.nope_head_dim:]             # (lora, H, v)
    q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk)
    sc = jnp.einsum("bqhl,bsl->bhqs", q_lat, ck)
    sc = sc + jnp.einsum("bqhr,bsr->bhqs", q_pe, kpe)
    sc = sc.astype(jnp.float32) * ((m.nope_head_dim + m.rope_head_dim)
                                   ** -0.5 * softmax_mscale(cfg))
    sc = jnp.where(mask, sc, jnp.float32(-1e30))
    pr = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhqs,bsl->bqhl", pr, ck)
    o = jnp.einsum("bqhl,lhv->bqhv", ctx, w_uv).reshape(b, qn, -1)
    return pol.linear(o, p["wo"])


def mla_decode(p, x, cfg: ModelConfig, pol: NumericsPolicy, cache: KVCache,
               pos) -> tuple[jax.Array, KVCache]:
    """Absorbed one-token MLA decode on the latent cache.

    cache.k: (B, S, lora) compressed latents; cache.v: (B, S, rope) k_pe.
    """
    c_new, pe_new = _mla_latents(p, x, cfg, pol, pos[:, None])
    smax = cache.k.shape[1]
    arange = jnp.arange(smax)[None, :, None]
    ck = jnp.where(arange == pos[:, None, None], c_new, cache.k)
    kpe = jnp.where(arange == pos[:, None, None], pe_new, cache.v)
    mask = (jnp.arange(smax)[None, :] <= pos[:, None])[:, None, None, :]
    o = _mla_absorbed(p, x, cfg, pol, ck, kpe, pos[:, None], mask)
    return o, KVCache(ck, kpe)


def mla_decode_paged(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                     cache: KVCache, bt, pos, active
                     ) -> tuple[jax.Array, KVCache]:
    """Absorbed one-token MLA decode on paged latent caches.

    cache.k: (NB, bs, lora) latent pages; cache.v: (NB, bs, rope) k_pe
    pages; bt/pos/active as in :func:`gqa_decode_paged`.
    """
    c_new, pe_new = _mla_latents(p, x, cfg, pol, pos[:, None])
    ck_pages = paged_write_token(cache.k, bt, pos, c_new[:, 0], active)
    pe_pages = paged_write_token(cache.v, bt, pos, pe_new[:, 0], active)
    ck = paged_gather(ck_pages, bt)                 # (B, W·bs, lora)
    kpe = paged_gather(pe_pages, bt)
    smax = ck.shape[1]
    mask = (jnp.arange(smax)[None, :] <= pos[:, None])[:, None, None, :]
    o = _mla_absorbed(p, x, cfg, pol, ck, kpe, pos[:, None], mask)
    return o, KVCache(ck_pages, pe_pages)


def mla_prefill_paged(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                      cache: KVCache, bt_row, pos_base, n_valid
                      ) -> tuple[jax.Array, KVCache]:
    """Chunked-prefill MLA for one slot: splice latents, attend absorbed.

    Same contract as :func:`gqa_prefill_paged`; the chunk's compressed
    latents + positional keys are written straight into the slot's pages
    and the C queries run the absorbed attention causally over them.
    """
    _, c, _ = x.shape
    lpos = pos_base + jnp.arange(c)
    c_new, pe_new = _mla_latents(p, x, cfg, pol, lpos[None])
    ck_pages = paged_write_chunk(cache.k, bt_row, pos_base, c_new[0],
                                 n_valid)
    pe_pages = paged_write_chunk(cache.v, bt_row, pos_base, pe_new[0],
                                 n_valid)
    ck = paged_gather(ck_pages, bt_row[None])       # (1, W·bs, lora)
    kpe = paged_gather(pe_pages, bt_row[None])
    smax = ck.shape[1]
    mask = (jnp.arange(smax)[None, :] <= lpos[:, None])[None, None]
    o = _mla_absorbed(p, x, cfg, pol, ck, kpe, lpos[None], mask)
    return o, KVCache(ck_pages, pe_pages)


def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    """Empty per-layer KV cache (no allocation under eval_shape)."""
    if cfg.attn_kind == "mla":
        m = cfg.mla
        return KVCache(
            jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            jnp.zeros((batch, max_len, m.rope_head_dim), dtype))
    return KVCache(
        jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dtype),
        jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.d_head), dtype))


def make_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype):
    """Empty per-layer *paged* KV cache: a shared pool of KV blocks.

    Capacity is a token budget (``num_blocks · block_size`` lines, block 0
    reserved as the null sink) rather than a dense (B, max_len)
    allocation; slots map into it via block tables (see ``nn/paged.py``).
    """
    if cfg.attn_kind == "mla":
        m = cfg.mla
        return KVCache(
            jnp.zeros((num_blocks, block_size, m.kv_lora_rank), dtype),
            jnp.zeros((num_blocks, block_size, m.rope_head_dim), dtype))
    return KVCache(
        jnp.zeros((num_blocks, block_size, cfg.n_kv_heads, cfg.d_head),
                  dtype),
        jnp.zeros((num_blocks, block_size, cfg.n_kv_heads, cfg.d_head),
                  dtype))
