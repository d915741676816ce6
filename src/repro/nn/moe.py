"""Mixture-of-Experts FFN: shared + fine-grained routed experts (DeepSeek).

An expert layer is told which of the ``n_experts`` routed experts it holds
(``MoERuntime.experts``: the first id and how many; the weights' leading
axis holds exactly those), routes every token over all of them, and
computes the part of the result its own experts give (``expert_share``):

* the router: float32 logits and softmax over all experts, greedy top-k,
  the top-k weights renormalized only where the config says so
  (``norm_topk_prob``);
* the assignments that land on the held experts, sorted by expert and in
  ascending token order within each, run as rows of one grouped matmul
  per projection (``pol.grouped_linear``: the grouped ⊞-MAC on the LNS
  training path), with no token dropped;
* the outputs weighted by their gates in float32.

``moe_layer`` adds the shared experts once: on one device (no mesh) it is
the whole layer, and a layer that holds a share of the experts gives that
share's part, as one chip of an expert-parallel group would before its
exchange.  With a mesh the same core runs on each shard's E/tp experts:

* ``moe_ep_replicated`` — tokens replicated over the model axis (decode,
  short sequences): each shard's part, then a ``psum``;
* ``moe_ep`` — activations sequence-sharded over the model axis: a
  capacity-bounded **all-to-all** carries each assignment to the shard
  that holds its expert and back (assignments over a destination's
  capacity are dropped and counted), then the gate-weighted combine.

The balance loss is DeepSeek-V2's sequence-wise one (§2.1.2): per
sequence, ``α Σ_i f_i P_i`` with ``f_i`` the share of the sequence's
assignments to expert ``i`` times ``E / K`` and ``P_i`` its mean router
probability, averaged over sequences; ``α`` is ``balance_coef``.
Each block returns ``(y, aux, stats)``: ``stats["routed"]`` counts the
rows each held expert computed, ``stats["dropped"]`` the assignments
left out.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.numerics import NumericsPolicy
from .config import ModelConfig


@dataclasses.dataclass(frozen=True)
class MoERuntime:
    """How to execute the MoE block.

    ``experts`` is ``(first, count)``, the routed experts this device
    holds (None: all of them); without a mesh the block computes their
    part.  With a mesh each shard of the model axis holds E/tp experts.
    """
    mesh: Optional[object] = None
    data_axes: tuple = ("data",)   # batch axes (may include 'pod')
    model_axis: str = "model"
    experts: Optional[tuple] = None


def init_moe(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    d, de = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 7)
    s_in, s_out = d ** -0.5, de ** -0.5
    p = {
        "router": d ** -0.5 * jax.random.normal(
            ks[0], (d, m.n_experts), jnp.float32),
        "w_gate": s_in * jax.random.normal(ks[1], (m.n_experts, d, de), dtype),
        "w_up": s_in * jax.random.normal(ks[2], (m.n_experts, d, de), dtype),
        "w_down": s_out * jax.random.normal(
            ks[3], (m.n_experts, de, d), dtype),
    }
    if m.n_shared:
        sh = m.n_shared * de
        p["shared_gate"] = s_in * jax.random.normal(ks[4], (d, sh), dtype)
        p["shared_up"] = s_in * jax.random.normal(ks[5], (d, sh), dtype)
        p["shared_down"] = (sh ** -0.5) * jax.random.normal(
            ks[6], (sh, d), dtype)
    return p


def _route(p, xf, m):
    """Router over all experts: float32 logits (at full float32 precision)
    and softmax, greedy top-k.  Returns gate weights and expert ids
    (T, k), and the probabilities (T, E)."""
    logits = jnp.matmul(xf.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w, ids, probs


def _balance_sums(ids, probs, n_seq: int, n_experts: int):
    """Per sequence: assignments to each expert, and the sum of each
    expert's router probability over the tokens; (n_seq, E) each."""
    cnt = jnp.sum(jax.nn.one_hot(ids.reshape(n_seq, -1), n_experts,
                                 dtype=jnp.float32), axis=1)
    return cnt, jnp.sum(probs.reshape(n_seq, -1, n_experts), axis=1)


def _balance_loss(cnt, psum, seq_len: int, m):
    f = cnt / (seq_len * m.top_k / m.n_experts)
    return m.balance_coef * jnp.mean(jnp.sum(f * (psum / seq_len), axis=-1))


def _shared_ffn(p, x, cfg, pol):
    h = jax.nn.silu(pol.linear(x, p["shared_gate"])) \
        * pol.linear(x, p["shared_up"])
    return pol.linear(h, p["shared_down"])


def expert_share(p, xf, w, ids, first, pol: NumericsPolicy):
    """The held experts' part of the routed output.

    ``xf`` (T, d) tokens, ``w``/``ids`` (T, k) their gates and experts;
    the weights hold experts ``first .. first + held - 1``.  Returns the
    float32 (T, d) sum over each token's assignments to held experts of
    gate × expert output, and the rows each held expert computed (held,).
    """
    held = p["w_gate"].shape[0]
    t, k = ids.shape
    local = ids.reshape(-1) - first
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)
    # Stable: within an expert, assignments keep ascending token order,
    # the order its dW contracts over.
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    rows = xf[order // k]
    h = jax.nn.silu(pol.grouped_linear(rows, p["w_gate"], sizes)) \
        * pol.grouped_linear(rows, p["w_up"], sizes)
    y = pol.grouped_linear(h, p["w_down"], sizes)
    y = y[jnp.argsort(order)].reshape(t, k, -1).astype(jnp.float32)
    gate = jnp.where(mine.reshape(t, k), w, 0.0)
    return jnp.sum(y * gate[..., None], axis=1), sizes


def moe_layer(p, x, cfg: ModelConfig, pol: NumericsPolicy, first=0):
    """The layer on one device: the held experts' part (all experts when
    the weights hold all of them) plus the shared experts."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    w, ids, probs = _route(p, xf, m)
    y, sizes = expert_share(p, xf, w, ids, first, pol)
    if m.n_shared:
        y = y + _shared_ffn(p, xf, cfg, pol).astype(jnp.float32)
    aux = _balance_loss(*_balance_sums(ids, probs, b, m.n_experts), s, m)
    stats = {"routed": sizes, "dropped": jnp.zeros((), jnp.int32)}
    return y.astype(x.dtype).reshape(b, s, d), aux, stats


def _bucket_positions(keys, n_buckets):
    """Stable-sort ``keys`` and return (order, key_sorted, pos_in_bucket)."""
    order = jnp.argsort(keys, stable=True)
    ks = keys[order]
    oh = jax.nn.one_hot(jnp.clip(ks, 0, n_buckets - 1), n_buckets,
                        dtype=jnp.int32)
    pos = jnp.take_along_axis(
        jnp.cumsum(oh, axis=0), jnp.clip(ks, 0, n_buckets - 1)[:, None],
        axis=1)[:, 0] - 1
    return order, ks, pos


def _expert_specs(p, rt: MoERuntime):
    pspec = {k: P() for k in p}
    for kname in ("w_gate", "w_up", "w_down"):
        pspec[kname] = P(rt.model_axis, None, None)
    return pspec


# ------------------------------------------------- expert parallel -------
def moe_ep(p, x, cfg: ModelConfig, pol: NumericsPolicy, rt: MoERuntime):
    """Expert-parallel MoE via shard_map + all-to-all over the model axis.

    ``x`` must be laid out (batch → data axes, sequence → model axis, d).
    Expert weights are sharded E/tp over the model axis.
    """
    m = cfg.moe
    mesh = rt.mesh
    tp = mesh.shape[rt.model_axis]
    assert m.n_experts % tp == 0, (m.n_experts, tp)
    e_loc = m.n_experts // tp
    all_axes = tuple(rt.data_axes) + (rt.model_axis,)
    x_spec = P(tuple(rt.data_axes) or None, rt.model_axis, None)

    def local_fn(p_loc, x_loc):
        b, s, d = x_loc.shape
        xf = x_loc.reshape(-1, d)
        n = xf.shape[0]
        w, ids, probs = _route(p_loc, xf, m)
        cnt, psum = _balance_sums(ids, probs, b, m.n_experts)
        aux = _balance_loss(jax.lax.psum(cnt, rt.model_axis),
                            jax.lax.psum(psum, rt.model_axis), s * tp, m)
        aux = jax.lax.pmean(aux, tuple(rt.data_axes)) if rt.data_axes \
            else aux
        nk = n * m.top_k
        cap_send = int(-(-nk // tp) * m.capacity_factor)
        flat_ids = ids.reshape(-1)
        tok = jnp.repeat(jnp.arange(n), m.top_k)
        wgt = w.reshape(-1)
        dest = flat_ids // e_loc
        order, _, pos = _bucket_positions(dest, tp)
        keep = pos < cap_send
        slot = jnp.where(keep, dest[order] * cap_send + pos, tp * cap_send)
        # scatter into send buffers (+1 overflow row, dropped)
        send_x = jnp.zeros((tp * cap_send + 1, d), x_loc.dtype)
        send_x = send_x.at[slot].set(xf[tok[order]], mode="drop")
        send_e = jnp.full((tp * cap_send + 1,), -1, jnp.int32)
        send_e = send_e.at[slot].set(flat_ids[order], mode="drop")

        recv_x = jax.lax.all_to_all(
            send_x[:-1].reshape(tp, cap_send, d), rt.model_axis, 0, 0)
        recv_e = jax.lax.all_to_all(
            send_e[:-1].reshape(tp, cap_send), rt.model_axis, 0, 0)
        shard = jax.lax.axis_index(rt.model_axis)
        # Each received row is one assignment: the expert core with k = 1
        # and gate 1 gives its expert's output (0 for an empty slot).
        y_recv, sizes = expert_share(
            p_loc, recv_x.reshape(tp * cap_send, d),
            jnp.ones((tp * cap_send, 1), jnp.float32),
            recv_e.reshape(-1, 1), shard * e_loc, pol)
        y_back = jax.lax.all_to_all(
            y_recv.reshape(tp, cap_send, d), rt.model_axis, 0, 0)
        y_flat = y_back.reshape(tp * cap_send, d)
        got = jnp.where(keep[:, None],
                        y_flat[jnp.clip(slot, 0, tp * cap_send - 1)], 0.0)
        out = jnp.zeros((n, d), jnp.float32)
        out = out.at[tok[order]].add(got * wgt[order][:, None])
        if m.n_shared:
            out = out + _shared_ffn(p_loc, xf, cfg, pol).astype(jnp.float32)
        stats = {"routed": jax.lax.psum(sizes, tuple(rt.data_axes))
                 if rt.data_axes else sizes,
                 "dropped": jax.lax.psum(jnp.sum(~keep).astype(jnp.int32),
                                         all_axes)}
        return out.astype(x_loc.dtype).reshape(b, s, d), aux, stats

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(_expert_specs(p, rt), x_spec),
        out_specs=(x_spec, P(), {"routed": P(rt.model_axis),
                                 "dropped": P()}),
        check_vma=False)
    return fn(p, x)


def moe_ep_replicated(p, x, cfg: ModelConfig, pol: NumericsPolicy,
                      rt: MoERuntime):
    """EP without all-to-all, for token counts too small to sequence-shard
    (decode: seq=1).  Tokens are replicated over the model axis; each
    shard computes its experts' part (``expert_share``, nothing dropped)
    and the parts are psum-combined.  Shared experts are computed
    redundantly (replicated) and added once, outside the psum.
    """
    m = cfg.moe
    mesh = rt.mesh
    tp = mesh.shape[rt.model_axis]
    e_loc = m.n_experts // tp
    x_spec = P(tuple(rt.data_axes) or None, None, None)

    def local_fn(p_loc, x_loc):
        b, s, d = x_loc.shape
        xf = x_loc.reshape(-1, d)
        w, ids, probs = _route(p_loc, xf, m)
        aux = _balance_loss(*_balance_sums(ids, probs, b, m.n_experts), s,
                            m)
        aux = jax.lax.pmean(aux, tuple(rt.data_axes)) if rt.data_axes \
            else aux
        shard = jax.lax.axis_index(rt.model_axis)
        y, sizes = expert_share(p_loc, xf, w, ids, shard * e_loc, pol)
        y = jax.lax.psum(y, rt.model_axis)
        if m.n_shared:
            y = y + _shared_ffn(p_loc, xf, cfg, pol).astype(jnp.float32)
        stats = {"routed": jax.lax.psum(sizes, tuple(rt.data_axes))
                 if rt.data_axes else sizes,
                 "dropped": jnp.zeros((), jnp.int32)}
        return y.astype(x_loc.dtype).reshape(b, s, d), aux, stats

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(_expert_specs(p, rt), x_spec),
                       out_specs=(x_spec, P(), {"routed": P(rt.model_axis),
                                                "dropped": P()}),
                       check_vma=False)
    return fn(p, x)


def moe_block(p, x, cfg: ModelConfig, pol: NumericsPolicy,
              rt: Optional[MoERuntime] = None):
    """``(y, aux, stats)`` of one MoE layer on the runtime's devices."""
    if rt is None or rt.mesh is None:
        first, count = (rt.experts if rt is not None and rt.experts
                        else (0, cfg.moe.n_experts))
        if p["w_gate"].shape[0] != count:
            raise ValueError(
                f"the layer is told it holds {count} experts from {first}, "
                f"its weights hold {p['w_gate'].shape[0]}")
        return moe_layer(p, x, cfg, pol, first)
    tp = rt.mesh.shape[rt.model_axis]
    if x.shape[1] % tp != 0:     # decode / tiny sequences
        return moe_ep_replicated(p, x, cfg, pol, rt)
    return moe_ep(p, x, cfg, pol, rt)
