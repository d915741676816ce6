"""Ahead-of-time compiles of the ⊞-MAC kernels for a TPU v5e.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology and compiles it, which raises
what the chip's compiler would raise (unsupported primitives, blocks that
break the (8, 128) tiling rule, VMEM overflow).  Results and times come
only from a run on the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
each import every test file.
"""
import functools
import json
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (DELTA_BITSHIFT, DELTA_DEFAULT, DELTA_SOFTMAX, LNS16,
                        LogSGDConfig, UpdateEpilogue, beta_code)
from repro.kernels.lns_boxsum.lns_boxsum import lns_boxsum_pallas
from repro.kernels.lns_matmul.lns_matmul import (
    FwdEpilogue, lns_matmul_dw_partials_pallas, lns_matmul_dw_pallas,
    lns_matmul_dw_update_pallas, lns_matmul_dx_pallas,
    lns_matmul_fused_pallas, lns_matmul_pallas)
from repro.kernels.lns_matmul.grouped import (lns_gmm_dw_pallas,
                                              lns_gmm_dx_pallas,
                                              lns_gmm_pallas)
from repro.kernels.lns_matmul.update import lns_fused_update_pallas

#: Every Δ kind the compiled lane takes.  lut640 unrolls ~400 breakpoints
#: of compare-select per ⊞, the largest kernel body there is; lut20 reads
#: its table with one lane gather.
SPECS = {"lut20": DELTA_DEFAULT, "lut640": DELTA_SOFTMAX,
         "bitshift": DELTA_BITSHIFT}

#: (M, K, N): the paper MLP's hidden layer at batch 64, and one qwen3-1.7b
#: MLP projection (d_model 2048 → d_ff 6144) at 256 tokens.
SHAPES = {"mlp": (64, 784, 100), "qwen3": (256, 2048, 6144)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed, library held, ...
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: a
    compile for a device that is not attached is written there but can
    never be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_case(kind, spec, m, k, n):
    """(fn of int32 planes, their shapes) for one kernel launch."""
    kw = dict(fmt=LNS16, spec=spec, interpret=False)
    sgd = UpdateEpilogue.from_sgd(
        LogSGDConfig(lr=0.01, momentum=0.9, weight_decay=0.01), LNS16)
    if kind == "fwd":
        return (lambda xc, xs, wc, ws: lns_matmul_pallas(xc, xs, wc, ws, **kw),
                [(m, k), (m, k), (k, n), (k, n)])
    if kind == "dx":
        return (lambda dc, ds, wc, ws: lns_matmul_dx_pallas(dc, ds, wc, ws,
                                                            **kw),
                [(m, n), (m, n), (k, n), (k, n)])
    if kind == "dw":
        return (lambda xc, xs, dc, ds: lns_matmul_dw_pallas(xc, xs, dc, ds,
                                                            **kw),
                [(m, k), (m, k), (m, n), (m, n)])
    if kind == "fused_fwd":
        ep = FwdEpilogue(bias=True, llrelu_beta=beta_code(0.01, LNS16),
                         emit_z_sign=True)
        return (lambda xc, xs, wc, ws, bc, bs: lns_matmul_fused_pallas(
                    xc, xs, wc, ws, epilogue=ep, bias_code=bc, bias_sign=bs,
                    **kw),
                [(m, k), (m, k), (k, n), (k, n), (n,), (n,)])
    if kind == "dw_update":
        return (lambda xc, xs, dc, ds, wc, ws, mc, ms:
                lns_matmul_dw_update_pallas(
                    xc, xs, dc, ds, w_code=wc, w_sign=ws, m_code=mc,
                    m_sign=ms, epilogue=sgd, **kw),
                [(m, k), (m, k), (m, n), (m, n)] + [(k, n)] * 4)
    if kind == "dw_partials":
        return (lambda xc, xs, dc, ds: lns_matmul_dw_partials_pallas(
                    xc, xs, dc, ds, num_segments=4, **kw),
                [(m, k), (m, k), (m, n), (m, n)])
    if kind == "boxsum":
        # The DP combine's fold: every weight entry over 4 segment slots.
        return (lambda c, s: lns_boxsum_pallas(c, s, **kw),
                [(k * n, 4)] * 2)
    if kind == "fused_update":
        return (lambda wc, ws, gc, gs, mc, ms: lns_fused_update_pallas(
                    wc, ws, gc, gs, m_code=mc, m_sign=ms,
                    epilogue=sgd, **kw),
                [(k, n)] * 6)
    raise ValueError(kind)


KINDS = ("fwd", "dx", "dw", "fused_fwd", "dw_update", "dw_partials",
         "boxsum", "fused_update")


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("spec", list(SPECS))
@pytest.mark.parametrize("kind", KINDS)
def test_kernel_compiles_for_v5e(one_chip, kind, spec, shape):
    fn, shapes = _kernel_case(kind, SPECS[spec], *SHAPES[shape])
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dw_partials_one_sample_segments_compile(one_chip):
    """grad_segments == batch: one-row segments are padded per segment
    with the zero code to the chip's 8-row tile."""
    fn = functools.partial(lns_matmul_dw_partials_pallas, num_segments=64,
                           fmt=LNS16, spec=DELTA_DEFAULT, interpret=False)
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in [(64, 784), (64, 784), (64, 100), (64, 100)]]
    jax.jit(fn).lower(*args).compile()


#: What each launch at the MLP shape (M, K, N) = (64, 784, 100) records:
#: output rows, columns and contraction depth as given, then as padded
#: for the grid (rows and columns to 128-wide blocks; the contraction to
#: 128-deep blocks, or to the 8-row tile when it is shorter).
EXTENTS = {
    "fwd": (64, 100, 784, 128, 128, 896),
    "dx": (64, 784, 100, 128, 896, 104),
    "dw": (784, 100, 64, 896, 128, 64),
    "fused_fwd": (64, 100, 784, 128, 128, 896),
    "dw_update": (784, 100, 64, 896, 128, 64),
    "dw_partials": (784, 100, 64, 896, 128, 64),
}


def _launch_metadata(one_chip, fn, shapes):
    """The ``kernel_metadata`` of each launch in ``fn``'s compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [json.loads(m) for m in re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*'
        r"kernel_metadata=(\{[^{}]*\})", text)]


@pytest.mark.parametrize("kind", KINDS)
def test_kernel_launch_carries_its_kind_and_extents(one_chip, kind):
    """The compiled custom call names its kind (and, for a ⊞-MAC, its
    extents) in ``kernel_metadata``, which a profiler trace shows in the
    launch's event name.  (The get-tuple-elements that unpack its
    outputs carry a copy; they run nothing on the device.)"""
    fn, shapes = _kernel_case(kind, SPECS["lut20"], *SHAPES["mlp"])
    want = {"kind": kind, "delta": "lut-gather"}
    if kind in EXTENTS:
        want.update(zip(("r", "c", "ct", "rp", "cp", "ctp"),
                        map(str, EXTENTS[kind])))
    assert _launch_metadata(one_chip, fn, shapes) == [want]


@pytest.mark.parametrize("spec,path", [("lut20", "lut-gather"),
                                       ("lut640", "lut-chain"),
                                       ("bitshift", "bitshift")])
def test_kernel_launch_names_its_delta_path(one_chip, spec, path):
    """A compiled launch names the way it evaluates Δ±: lut20's table
    fits one lane gather, lut640's keeps the compare-select chain."""
    fn, shapes = _kernel_case("fwd", SPECS[spec], *SHAPES["mlp"])
    assert [md["delta"] for md in _launch_metadata(one_chip, fn,
                                                   shapes)] == [path]


def test_paper_mlp_donating_step_aliases_its_params(one_chip):
    """The paper MLP's donating train step (784-100-10, batch 5) compiles
    for the chip with each of its 8 parameter planes written in place of
    the one it was given; without the alias the runtime would allocate
    new output buffers all the same.  The keeping step aliases none."""
    from repro.paper.mlp import LNSMLP, MLPConfig
    mlp = LNSMLP(MLPConfig(lr=0.01, weight_decay=0.01,
                           spec="lns16-train-pallas,interpret=off"))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(mlp.init, jax.random.PRNGKey(0)))
    xb = jax.ShapeDtypeStruct((5, 784), jnp.float32, sharding=one_chip)
    yb = jax.ShapeDtypeStruct((5,), jnp.int32, sharding=one_chip)

    def aliases(step):
        text = step.lower(mlp, params, xb, yb).compile().as_text()
        return {(int(o), int(i)) for o, i in re.findall(
            r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", text)}

    n = len(jax.tree_util.tree_leaves(params))
    assert n == 8
    assert aliases(LNSMLP._train_step_donate) == {(i, i) for i in range(n)}
    assert aliases(LNSMLP._train_step_keep) == set()


#: deepseek-v2-lite's expert layer on one chip: 16 held experts, d_model
#: 2048 and d_expert 1408, a bound of 6,144 routed rows (1,024 tokens,
#: top-6); each kind's extents as given, then padded (rows to whole
#: 128-row tiles per expert: ceil(6144 / 128) + 16 tiles).
GMM_G, GMM_M, GMM_D, GMM_DE = 16, 6144, 2048, 1408
GMM_CASES = {
    "gmm_fwd": (lns_gmm_pallas, [(GMM_M, GMM_D), (GMM_G, GMM_D, GMM_DE)],
                (GMM_M, GMM_DE, GMM_D, 8192, GMM_DE, GMM_D)),
    "gmm_dx": (lns_gmm_dx_pallas, [(GMM_M, GMM_DE), (GMM_G, GMM_D, GMM_DE)],
               (GMM_M, GMM_D, GMM_DE, 8192, GMM_D, GMM_DE)),
    "gmm_dw": (lns_gmm_dw_pallas, [(GMM_M, GMM_D), (GMM_M, GMM_DE)],
               (GMM_D, GMM_DE, GMM_M, GMM_D, GMM_DE, 8192)),
}


@pytest.mark.parametrize("kind", list(GMM_CASES))
def test_grouped_kernel_compiles_for_v5e(one_chip, kind):
    """The grouped ⊞-MAC launches at the cell's shapes compile for the
    chip, and name their kind, group count and extents."""
    fn, (a, b), ext = GMM_CASES[kind]
    want = {"kind": kind, "delta": "lut-gather", "g": str(GMM_G)}
    want.update(zip(("r", "c", "ct", "rp", "cp", "ctp"), map(str, ext)))
    assert _launch_metadata(one_chip, lambda ac, as_, bc, bs, sizes: fn(
        ac, as_, bc, bs, sizes, fmt=LNS16, spec=DELTA_DEFAULT,
        interpret=False), (a, a, b, b, (GMM_G,))) == [want]
