"""``LNSMLP.train_step`` donates the state a training loop hands back.

The step reuses the buffers of the params (and momentum) passed to it
only when they are the live arrays its own last call returned; any other
input is kept, and the step allocates new outputs.  Pinned here, on the
CPU (where JAX honours donation):

* **Same results** — a loop that hands its state back trains the same
  weight codes, signs and losses, step for step, as the same loop through
  ``train_step_metrics`` (which never donates).
* **What is donated** — the arrays handed back are deleted; the ``init``
  arrays, state passed again, and a state with one leaf swapped are not.
* **One program** — the step compiles once, whether or not the state
  was handed back: what is kept is copied on the device and the copy
  donated.
* **The span** — each call is one ``repro.train_step`` host span whose
  ``donated`` argument says whether the caller's arrays were donated.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lns import LNSArray
from repro.paper.mlp import LNSMLP, MLPConfig

B, N_IN, N_OUT = 5, 12, 4


def _mlp(backend="pallas", momentum=0.0):
    return LNSMLP(MLPConfig(n_in=N_IN, n_hidden=9, n_out=N_OUT, lr=0.01,
                            weight_decay=0.01, momentum=momentum,
                            spec=f"lns16-train-{backend}", matmul_block=8))


def _batches(steps, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.uniform(0, 1, size=(B, N_IN)).astype(np.float32),
             rng.integers(0, N_OUT, size=(B,))) for _ in range(steps)]


def _host(tree):
    """A host copy that leaves the device arrays donatable: on the CPU a
    plain ``np.asarray`` may share the buffer, which then cannot be
    donated."""
    return jax.tree_util.tree_map(lambda a: np.asarray(jnp.copy(a)), tree)


def _leaves(*trees):
    return jax.tree_util.tree_leaves(trees)


def _assert_same(a, b):
    for x, y in zip(_leaves(a), _leaves(b), strict=True):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("momentum", [0.0, 0.9], ids=["sgd", "momentum"])
@pytest.mark.parametrize("backend", ["pallas", "emulate"])
def test_handed_back_state_is_donated_and_trains_the_same(backend,
                                                           momentum):
    mlp = _mlp(backend, momentum)
    p0 = mlp.init(jax.random.PRNGKey(0))
    m0 = mlp.init_momentum(p0)
    batches = _batches(4)

    want, p, m = [], p0, m0
    for xb, yb in batches:
        (*state, loss), _ = mlp.train_step_metrics(p, xb, yb, m)
        p, m = state if m0 is not None else (state[0], None)
        want.append(_host((p, m, loss)))

    compiled = LNSMLP._train_step._cache_size()
    p, m = p0, m0
    for i, (xb, yb) in enumerate(batches):
        handed = _leaves(p, m)
        out = mlp.train_step(p, xb, yb, m)
        p, m = (out[0], out[1]) if m0 is not None else (out[0], None)
        _assert_same(_host((p, m, out[-1])), want[i])
        assert all(a.is_deleted() == (i > 0) for a in handed), i
    assert (mlp.calls, mlp.donated_calls) == (4, 3)
    assert not any(a.is_deleted() for a in _leaves(p0, m0))
    assert LNSMLP._train_step._cache_size() == compiled + 1


def test_state_passed_again_is_kept():
    """A caller that keeps what it passes (a timing probe, the benchmark's
    ``unchanged`` fault) gets the same step each time, and its params
    stay readable."""
    mlp = _mlp()
    p0 = mlp.init(jax.random.PRNGKey(0))
    xb, yb = _batches(1)[0]
    outs = [_host(mlp.train_step(p0, xb, yb)) for _ in range(3)]
    assert (mlp.calls, mlp.donated_calls) == (3, 0)
    assert not any(a.is_deleted() for a in _leaves(p0))
    _assert_same(outs[1], outs[0])
    _assert_same(outs[2], outs[0])


@pytest.mark.parametrize("where", ["params", "momentum"])
def test_state_with_a_leaf_swapped_is_kept(where):
    """One leaf replaced by a copy, in the returned dict itself: nothing
    of it is donated, and the step equals the one from fresh arrays."""
    mlp = _mlp(momentum=0.9)
    p = mlp.init(jax.random.PRNGKey(0))
    m = mlp.init_momentum(p)
    (xb, yb), (xb2, yb2) = _batches(2)
    p, m, _ = mlp.train_step(p, xb, yb, m)
    tree = p if where == "params" else m
    tree["b2"] = LNSArray(jnp.copy(tree["b2"].code), tree["b2"].sign)
    want = _host(mlp.train_step_metrics(p, xb2, yb2, m)[0])
    handed = _leaves(p, m)
    got = _host(mlp.train_step(p, xb2, yb2, m))
    assert mlp.donated_calls == 0
    assert not any(a.is_deleted() for a in handed)
    _assert_same(got, want)


def test_train_step_span_says_whether_it_donated(tmp_path):
    from jax.profiler import ProfileData
    mlp = _mlp()
    batches = _batches(3)
    p = mlp.init(jax.random.PRNGKey(0))
    for xb, yb in batches[:2]:  # the step compiles outside the trace
        p, _ = mlp.train_step(p, xb, yb)
    p = mlp.init(jax.random.PRNGKey(0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        for xb, yb in batches:
            p, loss = mlp.train_step(p, xb, yb)
        jax.block_until_ready(loss)
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    spans = sorted(
        (ev.start_ns, [v for k, v in ev.stats if k == "donated"])
        for plane in ProfileData.from_file(files[0]).planes
        if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
        if ev.name == "repro.train_step")
    assert [d for _, d in spans] == [[0], [1], [1]]
