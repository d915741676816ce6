"""The inputs are a function of the seed alone, and every seed gives the
same sizes."""
import numpy as np
import pytest

import traffic
from common import load_json, sub_seed


@pytest.mark.parametrize("name,cfg", [
    ("tokens-4x128", {"vocab_size": 151936}),
    ("mnist-b5", {}), ("mnist-b256", {})])
def test_same_seed_same_inputs(name, cfg):
    big = 2**31 + 12345
    a, b = traffic.make(name, cfg, big), traffic.make(name, cfg, big)
    c = traffic.make(name, cfg, 7)
    for i in (0, 1, 2, 5000):
        ba, bb, bc = a.batch(i), b.batch(i), c.batch(i)
        for k in ba:
            assert np.array_equal(ba[k], bb[k])
            assert ba[k].shape == bc[k].shape and ba[k].dtype == bc[k].dtype
        assert any(not np.array_equal(ba[k], bc[k]) for k in ba)


def test_checked_steps_use_rows_that_all_differ():
    g = traffic.make("mnist-b5", {}, 3)
    seen = [tuple(r) for i in range(3) for r in g.batch(i)["x"]]
    assert len(set(seen)) == 15
    t = traffic.make("tokens-4x128", {"vocab_size": 151936}, 3)
    rows = [tuple(r) for i in range(3) for r in t.batch(i)["tokens"]]
    assert len(set(rows)) == 12


def test_mnist_like_statistics():
    p = load_json("traffic", "mnist-b256.json")
    x, y = traffic.mnist_like(2000, p["classes"], p["separation"], 1)
    assert x.shape == (2000, 784) and x.dtype == np.float32
    assert 0.0 <= x.min() and x.max() <= 1.0
    assert np.allclose(np.round(x * 255), x * 255, atol=1e-4)
    assert 0.7 < float(np.mean(x == 0)) < 0.8
    assert set(np.unique(y)) == set(range(10))


def test_sub_seed_takes_seeds_past_32_bits():
    assert sub_seed(2**40 + 1, "w") != sub_seed(1, "w")
    assert 0 <= sub_seed(2**63 - 1, "w") < 2**31
