"""qwen3-1.7b: the system under test, its plain reference, and its counts.

``System`` drives the program's own train step
(``repro.train.make_train_step``) on weights this file makes from the
seed.  ``reference`` is the same model written out here from the Qwen3
description: float32 everywhere at ``precision="highest"``, except that
every projection is the 16-bit LNS ⊞-MAC with the lut20 Δ, in forward and
backward, as ``bench/lns_ref.py`` states it, and the embedding table is
snapped to the LNS grid.  It imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import lns_ref as L
from common import leaf_id, sub_seed


def shapes(c: dict) -> dict:
    """Parameter path → (shape, init std or None for ones), in the
    program's layout: layers stacked on a leading axis, the vocabulary
    padded to a multiple of 256 rows."""
    d, f, h, kv, hd = (c["hidden_size"], c["intermediate_size"],
                       c["num_attention_heads"], c["num_key_value_heads"],
                       c["head_dim"])
    n, v = c["num_hidden_layers"], -(-c["vocab_size"] // 256) * 256
    return {
        "emb/tok": ((v, d), d ** -0.5),
        "final_norm/scale": ((d,), None),
        "layers/attn/wq": ((n, d, h * hd), d ** -0.5),
        "layers/attn/wk": ((n, d, kv * hd), d ** -0.5),
        "layers/attn/wv": ((n, d, kv * hd), d ** -0.5),
        "layers/attn/wo": ((n, h * hd, d), (h * hd) ** -0.5),
        "layers/attn/q_norm": ((n, hd), None),
        "layers/attn/k_norm": ((n, hd), None),
        "layers/mlp/w_gate": ((n, d, f), (2.0 / d) ** 0.5),
        "layers/mlp/w_up": ((n, d, f), (2.0 / d) ** 0.5),
        "layers/mlp/w_down": ((n, f, d), (2.0 / f) ** 0.5),
        "layers/norm1/scale": ((n, d), None),
        "layers/norm2/scale": ((n, d), None),
    }


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
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, str))))
    return _maker(items)(jax.random.key(sub_seed(seed, "weights")))


@jax.jit
def _leaf_dist(a, b):
    """Per-leaf ‖a - b‖ (float32 sums of squares per leaf)."""
    return jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)


def _observe(c, seed, params, lr, which):
    p0 = make_params(c, seed)
    dist = {k: float(v) for k, v in _flat(_leaf_dist(params, p0)).items()}
    del p0
    if which == "grad":   # SGD without decay: g = (p0 - p1) / lr
        dist = {k: v / lr for k, v in dist.items()}
    return {"norms": dist}


# ----------------------------------------------------------- program --
class System:
    """The program's train step on this configuration.

    ``variant`` plants what the correctness check must catch: ``control``
    runs the program's own 12-bit LNS path, ``half_batch`` feeds half the
    rows, ``unchanged`` keeps the state the step was given."""

    def __init__(self, c: dict, cell: dict, seed: int, variant=None,
                 devices=None):
        from repro.configs import get_config
        from repro.core.plan import NumericsPlan
        from repro.nn.model import known_layer_paths
        from repro.optim.optimizers import SGDConfig
        from repro.train import init_train_state, make_train_step

        a = c["assumed"]
        plan = a["plan"]
        if variant == "control":
            default, rest = plan.split(";", 1)
            plan = f"{default},fmt=lns12;{rest}"
        mc = get_config(c["name"])
        if not (mc.tie_embeddings == c["tie_word_embeddings"]
                and mc.qk_norm and mc.norm_kind == "rmsnorm"
                and mc.mlp_kind == "glu" and mc.act == c["hidden_act"]):
            raise ValueError(f"the program's {c['name']} block is not the "
                             "one this file states")
        mc = mc.with_(n_layers=c["num_hidden_layers"],
                      d_model=c["hidden_size"],
                      d_ff=c["intermediate_size"],
                      n_heads=c["num_attention_heads"],
                      n_kv_heads=c["num_key_value_heads"],
                      d_head=c["head_dim"], vocab_size=c["vocab_size"],
                      rope_theta=float(c["rope_theta"]), numerics=plan,
                      remat=a["remat"])
        self.c, self.seed, self.variant = c, seed, variant
        self.lr = a["lr"]
        p = NumericsPlan.parse(plan)
        self._lanes = {q: p.runtime_for(q).lane
                       for q in known_layer_paths(mc)
                       if p.resolve(q).delta_spec is not None}
        opt = SGDConfig(lr=self.lr)
        self.state = init_train_state(make_params(c, seed), opt)
        donate = () if variant == "unchanged" else (0,)
        self._step = jax.jit(make_train_step(mc, opt), donate_argnums=donate)

    def lanes(self) -> dict:
        return self._lanes

    def step(self, batch):
        if self.variant == "half_batch":
            batch = {k: v[: len(v) // 2] for k, v in batch.items()}
        new, metrics = self._step(self.state, batch)
        if self.variant != "unchanged":
            self.state = new
        return metrics["loss"]

    def block(self):
        jax.block_until_ready(self.state)

    def observe(self, which: str) -> dict:
        return _observe(self.c, self.seed, self.state["params"], self.lr,
                        which)

    def free(self):
        self.state = self._step = None


# --------------------------------------------------------- reference --
def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary embedding on the two halves of each head (Qwen3, HF
    ``rotate_half``); x: (B, S, H, D)."""
    d, s = x.shape[-1], x.shape[1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
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


def _snap(fmt):
    """Snap to the LNS grid; the gradient passes straight through."""

    @jax.custom_vjp
    def snap(w):
        return L.decode(L.encode(w, fmt), fmt)

    snap.defvjp(lambda w: (snap(w), None), lambda _, g: (g,))
    return snap


def ref_loss(params, tokens, labels, c: dict, fmt_name: str = "lns16"):
    fmt = L.FORMATS[fmt_name]
    dl = L.delta(fmt_name, c["assumed"]["delta"])
    lin2 = _lns_linear(fmt, dl)

    def lin(x, w):
        return lin2(x.reshape(-1, x.shape[-1]), w).reshape(
            x.shape[:-1] + (w.shape[-1],))

    b, s = tokens.shape
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, qk_eps = c["rms_norm_eps"], c["assumed"]["qk_norm_eps"]
    tok = params["emb"]["tok"]
    x = _snap(fmt)(tok)[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(c["num_hidden_layers"]):
        lp = jax.tree.map(lambda t: t[i], params["layers"])
        at = lp["attn"]
        y = _rms(x, lp["norm1"]["scale"], eps)
        q = _rms(lin(y, at["wq"]).reshape(b, s, h, hd), at["q_norm"], qk_eps)
        k = _rms(lin(y, at["wk"]).reshape(b, s, kv, hd), at["k_norm"],
                 qk_eps)
        v = lin(y, at["wv"]).reshape(b, s, kv, hd)
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        k, v = jnp.repeat(k, h // kv, axis=2), jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(b, s, h * hd)
        x = x + lin(o, at["wo"])
        y = _rms(x, lp["norm2"]["scale"], eps)
        m = lp["mlp"]
        x = x + lin(jax.nn.silu(lin(y, m["w_gate"])) * lin(y, m["w_up"]),
                    m["w_down"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = x @ tok.T
    pad = jnp.arange(tok.shape[0]) >= c["vocab_size"]
    logits = jnp.where(pad, -1e30, logits)
    ll = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - ll)


def reference(c: dict, cell: dict, seed: int, batches: list,
              fmt_name: str = "lns16") -> dict:
    """The first ``len(batches)`` SGD steps of the plain model: each
    step's loss, the gradient norm per leaf at step 1, and the change
    norm per leaf after the last step."""
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
    """Per step: tokens, the ⊞-MAC launches (M, K, N) with their
    contraction K, and the model operations (a ⊞-MAC or a multiply-add
    counts 2; forward plus backward; the float32 head and attention
    included)."""
    d, f, h, kv, hd = (c["hidden_size"], c["intermediate_size"],
                       c["num_attention_heads"], c["num_key_value_heads"],
                       c["head_dim"])
    t = traffic.items_per_step
    b, s = traffic.batch_rows, traffic.seq
    proj = [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d),
            (d, f), (d, f), (f, d)]
    calls = []
    for _ in range(c["num_hidden_layers"]):
        for k, n in proj:
            calls += [(t, k, n),      # forward: contraction over K
                      (t, n, k),      # dX: dY (T, N) ⊞-MAC Wᵀ, over N
                      (k, t, n)]      # dW: Xᵀ ⊞-MAC dY, over the T tokens
    macs = sum(m * k * n for m, k, n in calls)
    head = 3 * 2 * t * d * c["vocab_size"]
    attn = c["num_hidden_layers"] * 3 * 2 * 2 * b * h * s * s * hd
    return {"items_per_step": t, "mac_calls": calls, "macs": macs,
            "model_ops": 2 * macs + head + attn}
