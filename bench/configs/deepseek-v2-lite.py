"""deepseek-v2-lite: the system under test, its plain reference, and its
counts.

``System`` drives the program's own train step
(``repro.train.make_train_step``) on weights this file makes from the
seed, with the expert layers told they hold experts 0-15 of 64.
``reference`` is the same stage written out here from the DeepSeek-V2
description (arXiv:2405.04434 and the published ``config.json``): MLA with
YaRN rope, a dense first layer, then MoE layers of a greedy top-6 softmax
router over 64 experts, the held experts' part, and 2 shared experts.
Float32 everywhere at ``precision="highest"``, except that every
projection is the 16-bit LNS ⊞-MAC with the lut20 Δ, in forward and
backward, as ``bench/lns_ref.py`` states it: the routed experts too, on
their grouped rows (each assignment of a held expert, in ascending token
order, against that expert's weights).  It imports nothing of the
program.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

import lns_ref as L
from common import leaf_id, sub_seed


def _dims(c: dict) -> dict:
    return dict(d=c["hidden_size"], f=c["intermediate_size"],
                de=c["moe_intermediate_size"], h=c["num_attention_heads"],
                nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
                vd=c["v_head_dim"], lora=c["kv_lora_rank"],
                e=c["n_routed_experts"], held=c["experts_held"],
                k=c["num_experts_per_tok"], sh=c["n_shared_experts"],
                n_moe=c["num_hidden_layers"] - c["first_k_dense_replace"],
                n_dense=c["first_k_dense_replace"], v=c["vocab_size"])


def shapes(c: dict) -> dict:
    """Parameter path → (shape, init std or None for ones), in the
    program's layout: layers stacked on a leading axis."""
    m = _dims(c)
    d, h = m["d"], m["h"]
    qd, lora, de = m["nope"] + m["rope"], m["lora"], m["de"]
    out = {"emb/tok": ((m["v"], d), d ** -0.5),
           "emb/head": ((d, m["v"]), d ** -0.5),
           "final_norm/scale": ((d,), None)}
    for stack, n in (("dense_layers", m["n_dense"]), ("layers", m["n_moe"])):
        out.update({
            f"{stack}/attn/wq": ((n, d, h * qd), d ** -0.5),
            f"{stack}/attn/w_dkv": ((n, d, lora + m["rope"]), d ** -0.5),
            f"{stack}/attn/kv_norm": ((n, lora), None),
            f"{stack}/attn/w_ukv": ((n, lora, h * (m["nope"] + m["vd"])),
                                    lora ** -0.5),
            f"{stack}/attn/wo": ((n, h * m["vd"], d), (h * m["vd"]) ** -0.5),
            f"{stack}/norm1/scale": ((n, d), None),
            f"{stack}/norm2/scale": ((n, d), None)})
    n, f = m["n_dense"], m["f"]
    out.update({"dense_layers/mlp/w_gate": ((n, d, f), (2.0 / d) ** 0.5),
                "dense_layers/mlp/w_up": ((n, d, f), (2.0 / d) ** 0.5),
                "dense_layers/mlp/w_down": ((n, f, d), (2.0 / f) ** 0.5)})
    n, held, sh = m["n_moe"], m["held"], m["sh"] * de
    out.update({
        "layers/moe/router": ((n, d, m["e"]), d ** -0.5),
        "layers/moe/w_gate": ((n, held, d, de), d ** -0.5),
        "layers/moe/w_up": ((n, held, d, de), d ** -0.5),
        "layers/moe/w_down": ((n, held, de, d), de ** -0.5),
        "layers/moe/shared_gate": ((n, d, sh), d ** -0.5),
        "layers/moe/shared_up": ((n, d, sh), d ** -0.5),
        "layers/moe/shared_down": ((n, sh, d), sh ** -0.5)})
    return out


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        *head, leaf = path.split("/")
        node = out
        for k in head:
            node = node.setdefault(k, {})
        node[leaf] = v
    return out


def _flat(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        out.update(_flat(v, p) if isinstance(v, dict) else {p: v})
    return out


def _key_items(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@functools.lru_cache(maxsize=None)
def _maker(key_items: tuple):
    c = dict(key_items)

    @jax.jit
    def make(key):
        flat = {}
        for path, (shape, std) in shapes(c).items():
            if std is None:
                flat[path] = jnp.ones(shape, jnp.float32)
            else:
                flat[path] = std * jax.random.normal(
                    jax.random.fold_in(key, leaf_id(path)), shape,
                    jnp.float32)
        return _nest(flat)
    return make


def make_params(c: dict, seed: int):
    """Float32 weights on the device, from the seed, in one jitted call."""
    return _maker(_key_items(c))(jax.random.key(sub_seed(seed, "weights")))


def _parts(path: str, a, held: int):
    """The pieces a leaf's norms are compared in: the leaf itself, but
    the router by column, each held expert's column over the MoE layers
    and the other experts' columns together.  A held expert's column
    takes its gradient from that expert's gate, which reads its output
    from the grouped forward, so one expert's fault shows there and is
    not hidden among sixty-four columns.  The other columns take only
    the softmax's and the balance loss's coupling: small, and read
    together."""
    if path != "layers/moe/router":
        return {path: a}
    out = {f"{path}.{e}": a[:, :, e] for e in range(held)}
    out[f"{path}.rest"] = a[:, :, held:]
    return out


@functools.partial(jax.jit, static_argnums=2)
def _part_dist(a, b, held: int):
    """‖a - b‖ per piece (float32 sums of squares)."""
    out = {}
    for path, x in _flat(a).items():
        y = _flat(b)[path]
        for name, d in _parts(path, x - y, held).items():
            out[name] = jnp.sqrt(jnp.sum(jnp.square(d)))
    return out


def _observe(c, seed, params, lr, which):
    p0 = make_params(c, seed)
    dist = {k: float(v) for k, v in
            _part_dist(params, p0, c["experts_held"]).items()}
    del p0
    if which == "grad":   # SGD without decay: g = (p0 - p1) / lr
        dist = {k: v / lr for k, v in dist.items()}
    return {"norms": dist}


@contextlib.contextmanager
def _first_expert_dropped():
    """A planted fault: each MoE layer leaves out its first held expert's
    output (its gate weights set to 0), for the check to catch."""
    from repro.nn import moe
    share = moe.expert_share

    def dropped(p, xf, w, ids, first, pol):
        return share(p, xf, jnp.where(ids == first, 0.0, w), ids, first, pol)

    moe.expert_share = dropped
    try:
        yield
    finally:
        moe.expert_share = share


# ----------------------------------------------------------- program --
class System:
    """The program's train step on this configuration.

    ``variant`` plants what the correctness check must catch: ``control``
    runs the program's own 12-bit LNS path, ``drop_expert`` leaves out one
    held expert's output, ``unchanged`` keeps the state the step was
    given; ``renorm`` (top-k weights renormalized) and ``yarn_off`` (rope
    without YaRN) run departures from the published model, for the
    readings."""

    def __init__(self, c: dict, cell: dict, seed: int, variant=None,
                 devices=None):
        from repro.configs import get_config
        from repro.core.plan import NumericsPlan
        from repro.nn import Runtime
        from repro.nn.config import YarnConfig
        from repro.nn.model import known_layer_paths
        from repro.optim.optimizers import SGDConfig
        from repro.train import init_train_state, make_train_step

        a, m = c["assumed"], _dims(c)
        plan = a["plan"]
        if variant == "control":
            default, rest = plan.split(";", 1)
            plan = f"{default},fmt=lns12;{rest}"
        mc = get_config("deepseek-v2-lite-16b")
        rs = c["rope_scaling"]
        yarn = YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"])
        if not (mc.family == "moe" and mc.attn_kind == "mla"
                and not mc.tie_embeddings and mc.act == c["hidden_act"]
                and mc.rope_scaling == yarn and mc.norm_kind == "rmsnorm"
                and c["q_lora_rank"] is None and c["topk_method"] == "greedy"
                and c["scoring_func"] == "softmax"
                and c["routed_scaling_factor"] == 1):
            raise ValueError("the program's deepseek-v2-lite-16b block is "
                             "not the one this file states")
        mla = mc.mla.__class__(kv_lora_rank=m["lora"],
                               rope_head_dim=m["rope"],
                               nope_head_dim=m["nope"], v_head_dim=m["vd"])
        moe = mc.moe.__class__(
            n_experts=m["e"], top_k=m["k"], n_shared=m["sh"],
            d_expert=m["de"], first_dense_layers=m["n_dense"],
            norm_topk_prob=(variant == "renorm") or c["norm_topk_prob"],
            balance_coef=a["aux_loss_alpha"])
        mc = mc.with_(n_layers=c["num_hidden_layers"], d_model=m["d"],
                      d_ff=m["f"], n_heads=m["h"],
                      n_kv_heads=c["num_key_value_heads"],
                      vocab_size=m["v"], rope_theta=float(c["rope_theta"]),
                      rope_scaling=None if variant == "yarn_off" else yarn,
                      norm_eps=c["rms_norm_eps"], mla=mla, moe=moe,
                      numerics=plan, remat=a["remat"])
        self.c, self.seed, self.variant = c, seed, variant
        self.lr = a["lr"]
        p = NumericsPlan.parse(plan)
        self._lanes = {q: p.runtime_for(q).lane
                       for q in known_layer_paths(mc)
                       if p.resolve(q).delta_spec is not None}
        opt = SGDConfig(lr=self.lr)
        self.state = init_train_state(make_params(c, seed), opt)
        donate = () if variant == "unchanged" else (0,)
        self._step = jax.jit(
            make_train_step(mc, opt, Runtime(experts=(0, m["held"]))),
            donate_argnums=donate)
        self.counters = None

    def lanes(self) -> dict:
        return self._lanes

    def step(self, batch):
        with (_first_expert_dropped() if self.variant == "drop_expert"
              else contextlib.nullcontext()):
            new, metrics = self._step(self.state, batch)
        if self.variant != "unchanged":
            self.state = new
        self.counters = {k: v for k, v in metrics.items() if k != "loss"}
        return metrics["loss"]

    def block(self):
        jax.block_until_ready(self.state)

    def observe(self, which: str) -> dict:
        return _observe(self.c, self.seed, self.state["params"], self.lr,
                        which)

    def free(self):
        self.state = self._step = self.counters = None


# --------------------------------------------------------- reference --
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_inv_freq(dim: int, base: float, rs: dict):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies, as published."""
    def corr_dim(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / (base ** pos)
    inter = 1.0 / (rs["factor"] * base ** pos)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def _rope(x, c):
    """YaRN rotary embedding on the two halves of each head; x: (B, S, H,
    D).  (HF first de-interleaves the (even, odd) pairs: with seeded
    weights that is a fixed permutation of the rope columns.)"""
    rs = c["rope_scaling"]
    d, s = x.shape[-1], x.shape[1]
    inv = _yarn_inv_freq(d, float(c["rope_theta"]), rs)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    m = (_mscale(rs["factor"], rs["mscale"])
         / _mscale(rs["factor"], rs["mscale_all_dim"]))
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _lns_linear(fmt, dl):
    """x (T, K) · w (K, N) as a 16-bit LNS ⊞-MAC in forward and in both
    backward products, the operands encoded to the LNS grid."""

    @jax.custom_vjp
    def lin(x, w):
        return L.decode(L.mac(L.encode(x, fmt), L.encode(w, fmt), dl), fmt)

    def fwd(x, w):
        xq, wq = L.encode(x, fmt), L.encode(w, fmt)
        return L.decode(L.mac(xq, wq, dl), fmt), (xq, wq)

    def bwd(res, g):
        xq, wq = res
        dy = L.encode(g, fmt)
        return (L.decode(L.mac(dy, L.transpose(wq), dl), fmt),
                L.decode(L.mac(L.transpose(xq), dy, dl), fmt))

    lin.defvjp(fwd, bwd)
    return lin


def _gmac(x, w, gid, dl):
    """Grouped ⊞-MAC: row r of x (M, K) against w[gid[r]] (G, K, N),
    ``acc ⊞= x[r, k] ⊡ w[gid[r], k, :]`` for k ascending from zero; a
    row with ``gid`` -1 gives zero."""
    f = dl.fmt
    m, n = x[0].shape[0], w[0].shape[2]
    g = jnp.clip(gid, 0, w[0].shape[0] - 1)
    ok = (gid >= 0)[:, None]
    acc = (jnp.full((m, n), f.zero, jnp.int32), jnp.zeros((m, n), jnp.int32))

    def step(acc, kk):
        xc, xs, wc, ws = kk
        prod = L.mul((xc[:, None], xs[:, None]), (wc[g], ws[g]), f)
        prod = (jnp.where(ok, prod[0], f.zero), jnp.where(ok, prod[1], 0))
        return L.add(acc, prod, dl), None

    xs_ = (x[0].T, x[1].T, jnp.swapaxes(w[0], 0, 1), jnp.swapaxes(w[1], 0, 1))
    out, _ = jax.lax.scan(step, acc, xs_)
    return out


def _gmac_dw(x, dy, gid, n_rows, n_groups, dl):
    """dW[g] = ⊞ over group g's rows, in the order given, of
    ``x[r]ᵀ ⊡ dy[r]``, from zero; only the first ``n_rows`` rows (the
    routed ones) are walked."""
    f = dl.fmt
    k, n = x[0].shape[1], dy[0].shape[1]
    acc = (jnp.full((n_groups, k, n), f.zero, jnp.int32),
           jnp.zeros((n_groups, k, n), jnp.int32))

    def body(r, acc):
        g = gid[r]
        prod = L.mul((x[0][r][:, None], x[1][r][:, None]),
                     (dy[0][r][None, :], dy[1][r][None, :]), f)
        new = L.add((acc[0][g], acc[1][g]), prod, dl)
        return acc[0].at[g].set(new[0]), acc[1].at[g].set(new[1])

    return jax.lax.fori_loop(0, n_rows, body, acc)


def _grouped_linear(fmt, dl):
    """Rows x (M, K) with their expert ``gid`` (G experts, -1: none) ·
    w (G, K, N): the grouped ⊞-MAC in forward, dX and dW."""

    @jax.custom_vjp
    def glin(x, w, gid, n_rows):
        return L.decode(_gmac(L.encode(x, fmt), L.encode(w, fmt), gid, dl),
                        fmt)

    def fwd(x, w, gid, n_rows):
        xq, wq = L.encode(x, fmt), L.encode(w, fmt)
        return (L.decode(_gmac(xq, wq, gid, dl), fmt),
                (xq, wq, gid, n_rows))

    def bwd(res, g):
        xq, wq, gid, n_rows = res
        dy = L.encode(g, fmt)
        wt = (jnp.swapaxes(wq[0], 1, 2), jnp.swapaxes(wq[1], 1, 2))
        return (L.decode(_gmac(dy, wt, gid, dl), fmt),
                L.decode(_gmac_dw(xq, dy, gid, n_rows, wq[0].shape[0], dl),
                         fmt), None, None)

    glin.defvjp(fwd, bwd)
    return glin


def _snap(fmt):
    """Snap to the LNS grid; the gradient passes straight through."""

    @jax.custom_vjp
    def snap(w):
        return L.decode(L.encode(w, fmt), fmt)

    snap.defvjp(lambda w: (snap(w), None), lambda _, g: (g,))
    return snap


def _mla(at, y, c, lin):
    m = _dims(c)
    b, s, _ = y.shape
    h, nope, rope, vd = m["h"], m["nope"], m["rope"], m["vd"]
    eps = c["rms_norm_eps"]
    q = lin(y, at["wq"]).reshape(b, s, h, nope + rope)
    kv = lin(y, at["w_dkv"])
    c_kv = _rms(kv[..., :m["lora"]], at["kv_norm"], eps)
    k_pe = _rope(kv[..., m["lora"]:][:, :, None, :], c)
    q_pe = _rope(q[..., nope:], c)
    ukv = lin(c_kv, at["w_ukv"]).reshape(b, s, h, nope + vd)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    kf = jnp.concatenate(
        [ukv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    rs = c["rope_scaling"]
    scale = ((nope + rope) ** -0.5
             * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, ukv[..., nope:])
    return lin(o.reshape(b, s, h * vd), at["wo"])


def _ffn(w_gate, w_up, w_down, y, lin):
    return lin(jax.nn.silu(lin(y, w_gate)) * lin(y, w_up), w_down)


def _moe(mp, y, c, lin, glin, held_first: int = 0):
    """The MoE layer's held part plus its shared experts, and the
    sequence-wise balance loss."""
    m = _dims(c)
    b, s, d = y.shape
    k, e, held = m["k"], m["e"], m["held"]
    yf = y.reshape(-1, d)
    t = yf.shape[0]
    probs = jax.nn.softmax(yf @ mp["router"], axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    # DeepSeek-V2's sequence-wise balance loss (seq_aux).
    ce = jnp.sum(jax.nn.one_hot(ids.reshape(b, s * k), e), axis=1) \
        / (s * k / e)
    aux = c["assumed"]["aux_loss_alpha"] * jnp.mean(
        jnp.sum(ce * jnp.mean(probs.reshape(b, s, e), axis=1), axis=1))
    # The held experts' assignments, grouped by expert, tokens ascending.
    eid = ids.reshape(-1) - held_first
    mine = (eid >= 0) & (eid < held)
    order = jnp.argsort(jnp.where(mine, eid, held), stable=True)
    gid = jnp.where(mine, eid, -1)[order]
    n_rows = jnp.sum(mine)
    rows = yf[order // k]
    hdn = jax.nn.silu(glin(rows, mp["w_gate"], gid, n_rows)) \
        * glin(rows, mp["w_up"], gid, n_rows)
    out = glin(hdn, mp["w_down"], gid, n_rows)
    out = out[jnp.argsort(order)].reshape(t, k, d)
    routed = jnp.sum(out * jnp.where(mine.reshape(t, k), w, 0.0)[..., None],
                     axis=1)
    shared = _ffn(mp["shared_gate"], mp["shared_up"], mp["shared_down"], yf,
                  lin)
    return (routed + shared).reshape(b, s, d), aux


def ref_loss(params, tokens, labels, c: dict, fmt_name: str = "lns16"):
    fmt = L.FORMATS[fmt_name]
    dl = L.delta(fmt_name, c["assumed"]["delta"])
    lin2 = _lns_linear(fmt, dl)
    glin = _grouped_linear(fmt, dl)

    def lin(x, w):
        return lin2(x.reshape(-1, x.shape[-1]), w).reshape(
            x.shape[:-1] + (w.shape[-1],))

    eps = c["rms_norm_eps"]
    m = _dims(c)
    x = _snap(fmt)(params["emb"]["tok"])[tokens]
    aux = 0.0
    for stack, n in (("dense_layers", m["n_dense"]), ("layers", m["n_moe"])):
        for i in range(n):
            lp = jax.tree.map(lambda t: t[i], params[stack])
            x = x + _mla(lp["attn"], _rms(x, lp["norm1"]["scale"], eps), c,
                         lin)
            y = _rms(x, lp["norm2"]["scale"], eps)
            if stack == "dense_layers":
                mp = lp["mlp"]
                x = x + _ffn(mp["w_gate"], mp["w_up"], mp["w_down"], y, lin)
            else:
                out, a = _moe(lp["moe"], y, c, lin, glin)
                x, aux = x + out, aux + a
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = x @ params["emb"]["head"]
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll) + aux


def reference(c: dict, cell: dict, seed: int, batches: list,
              fmt_name: str = "lns16") -> dict:
    """The first ``len(batches)`` SGD steps of the plain model: each
    step's loss, the gradient norm per piece at step 1, and the change
    norm per piece after the last step."""
    lr = c["assumed"]["lr"]

    @jax.jit
    def step(p, tokens, labels):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(ref_loss)(p, tokens, labels, c,
                                                   fmt_name)
        return jax.tree.map(lambda w, gw: w - lr * gw, p, g), loss

    p = make_params(c, seed)
    out = {"losses": []}
    for i, b in enumerate(batches):
        p, loss = step(p, b["tokens"], b["labels"])
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = _observe(c, seed, p, lr, "grad")
    out["change"] = _observe(c, seed, p, lr, "change")
    return out


# ------------------------------------------------------------- counts --
def counts(c: dict, traffic, chips: int = 1) -> dict:
    """Per step: tokens; the plain ⊞-MAC launches (M, K, N); the grouped
    ones (rows, K, N, G) from the expected routed rows, T·k·held/E a
    layer, whatever the routing of a step; and the model operations (a
    ⊞-MAC or a multiply-add counts 2; forward plus backward; the float32
    head and attention included)."""
    m = _dims(c)
    d, h = m["d"], m["h"]
    qd, lora, vd = m["nope"] + m["rope"], m["lora"], m["vd"]
    t = traffic.items_per_step
    b, s = traffic.batch_rows, traffic.seq
    attn = [(d, h * qd), (d, lora + m["rope"]), (lora, h * (m["nope"] + vd)),
            (h * vd, d)]
    shared = m["sh"] * m["de"]
    proj = attn * (m["n_dense"] + m["n_moe"]) \
        + [(d, m["f"]), (d, m["f"]), (m["f"], d)] * m["n_dense"] \
        + [(d, shared), (d, shared), (shared, d)] * m["n_moe"]
    calls = []
    for k, n in proj:
        calls += [(t, k, n), (t, n, k), (k, t, n)]
    rows = t * m["k"] * m["held"] // m["e"]
    gmm = []
    for k, n in [(d, m["de"]), (d, m["de"]), (m["de"], d)] * m["n_moe"]:
        gmm += [(rows, k, n, m["held"])] * 3     # forward, dX, dW
    macs = sum(x * y * z for x, y, z in calls) \
        + sum(r * k * n for r, k, n, _ in gmm)
    head = 3 * 2 * t * d * m["v"]
    scores = (m["n_dense"] + m["n_moe"]) * 3 * 2 * b * h * s * s * (qd + vd)
    router = 3 * 2 * t * d * m["e"] * m["n_moe"]
    return {"items_per_step": t, "mac_calls": calls, "gmm_calls": gmm,
            "macs": macs, "model_ops": 2 * macs + head + scores + router}
