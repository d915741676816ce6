"""The one generator of the benchmark's traffic.

A traffic mix is a data file, ``bench/traffic/<name>.json``, whose ``kind``
names one of the generators below and whose other keys are its parameters.
Inputs are made on the host from ``--seed`` alone; the program receives
only the arrays.  Every seed gives the same sizes: a seed changes which
tokens or samples come, never how many.
"""
from __future__ import annotations

import numpy as np

from common import load_json, sub_seed


def _smooth(img, n):
    """Separable box blur over the last two axes, wrapping at the edges."""
    for _ in range(n):
        img = (img + np.roll(img, 1, -2) + np.roll(img, -1, -2)
               + np.roll(img, 1, -1) + np.roll(img, -1, -1)) / 5.0
    return img


def mnist_like(n: int, classes: int, separation: float, seed: int):
    """MNIST-shaped samples: 784 pixels on the 8-bit grid in [0, 1], about
    three quarters of them exact zeros, one smooth prototype per class
    shifted by up to 2 pixels and noised.  (The statistics of the paper
    repo's offline stand-in for MNIST, generated vectorized.)"""
    rng = np.random.default_rng(seed)
    protos = _smooth(rng.normal(size=(classes, 28, 28)), 3)
    lo = protos.min(axis=(1, 2), keepdims=True)
    protos = (protos - lo) / (np.ptp(protos, axis=(1, 2), keepdims=True)
                              + 1e-9)
    y = rng.integers(0, classes, size=n)
    sx = rng.integers(-2, 3, size=n)
    sy = rng.integers(-2, 3, size=n)
    r = np.arange(28)
    rows = (r[None, :] - sx[:, None]) % 28
    cols = (r[None, :] - sy[:, None]) % 28
    base = protos[y] * separation
    imgs = base[np.arange(n)[:, None, None], rows[:, :, None],
                cols[:, None, :]]
    imgs = imgs + rng.normal(size=imgs.shape)
    thresh = np.quantile(imgs, 0.75, axis=(1, 2), keepdims=True)
    imgs = np.maximum(imgs - thresh, 0.0)
    imgs = imgs / (imgs.max(axis=(1, 2), keepdims=True) + 1e-9)
    x = (np.round(imgs * 255) / 255.0).reshape(n, 784).astype(np.float32)
    return x, y.astype(np.int32)


class LMTokens:
    """``batch`` sequences of ``seq`` tokens per step, ids uniform over the
    vocabulary; step ``i``'s rows come from (seed, i), so every step's rows
    differ and a step's rows do not depend on the steps before it."""

    def __init__(self, p: dict, vocab: int, seed: int):
        self.batch_rows, self.seq, self.vocab = p["batch"], p["seq"], vocab
        self.seed = seed
        self.items_per_step = self.batch_rows * self.seq

    def batch(self, i: int) -> dict:
        rng = np.random.default_rng(sub_seed(self.seed, "tokens", i))
        t = rng.integers(0, self.vocab, size=(self.batch_rows, self.seq + 1),
                         dtype=np.int32)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}


class MNISTLike:
    """Minibatches of ``batch`` samples from ``n_train`` MNIST-shaped ones,
    drawn without replacement in a new seeded permutation every epoch, as
    the paper's training loop draws them."""

    def __init__(self, p: dict, seed: int):
        self.x, self.y = mnist_like(p["n_train"], p["classes"],
                                    p["separation"], sub_seed(seed, "data"))
        self.batch_rows = p["batch"]
        self.items_per_step = self.batch_rows
        self.per_epoch = len(self.x) // self.batch_rows
        self.seed = seed
        self._order = {}

    def _perm(self, epoch: int):
        if epoch not in self._order:
            self._order = {epoch: np.random.default_rng(
                sub_seed(self.seed, "order", epoch)).permutation(len(self.x))}
        return self._order[epoch]

    def batch(self, i: int) -> dict:
        e, s = divmod(i, self.per_epoch)
        rows = self._perm(e)[s * self.batch_rows:(s + 1) * self.batch_rows]
        return {"x": self.x[rows], "y": self.y[rows]}


def make(name: str, cfg: dict, seed: int):
    """The generator of traffic mix ``name`` for configuration ``cfg``."""
    p = load_json("traffic", name + ".json")
    if p["kind"] == "lm_tokens":
        return LMTokens(p, cfg["vocab_size"], seed)
    if p["kind"] == "mnist_like":
        return MNISTLike(p, seed)
    raise ValueError(f"traffic {name!r}: unknown kind {p['kind']!r}")
