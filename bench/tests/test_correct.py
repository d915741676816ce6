"""The check that decides ``correct``, driven through a whole run on the
CPU at sizes a CPU holds: the look for a chip is skipped, everything else
runs, the Pallas kernels in the interpreter.

Sound runs of the program read correct.  The control (the program's own
12-bit LNS path in place of the 16-bit one the configuration states) and
each fault a training cell can have (a step that returns its state
unchanged; half of the batch left out; the exchange between chips left
out) read not correct.
"""
import json
import os
import subprocess
import sys

import pytest

import run

#: Sizes a CPU holds: qwen3's block and head at small widths, one layer.
#: At d_model 256, with the published d_ff / d_model of 3, the lns12
#: control's gaps of norms read ~0.58, as at full width on the chip
#: (~0.55); at d_model 64 they read ~0.05, under the cell's limits.
SMALL = {
    "qwen3-1.7b.train-lut20": {
        "hidden_size": 256, "intermediate_size": 768,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "vocab_size": 512, "num_hidden_layers": 1},
    "paper-mlp.online-b5": {"n_hidden": 16},
    "paper-mlp.dp4-b256": {"n_hidden": 16},
}
SEED = 2**33 + 17


def _run(cell, variant=None):
    return run.run_cell(cell, SEED, 0.2, False, variant=variant,
                        require_compiled=False,
                        config_overrides=SMALL[cell])


def _run_4(cell, variant):
    """A run on 4 virtual CPU devices, in a process of its own."""
    here = os.path.dirname(os.path.abspath(__file__))
    code = (f"import conftest, json, run; r = run.run_cell({cell!r}, {SEED},"
            f" 0.2, False, variant={variant!r}, require_compiled=False, "
            f"config_overrides={SMALL[cell]!r}); print(json.dumps("
            f"{{'correct': r['correct'], 'checks': r['checks']}}))")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, "-c", code], cwd=here, env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["qwen3-1.7b.train-lut20",
                                  "paper-mlp.online-b5"])
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell,variant", [
    ("qwen3-1.7b.train-lut20", "control"),
    ("qwen3-1.7b.train-lut20", "unchanged"),
    ("qwen3-1.7b.train-lut20", "half_batch"),
    ("paper-mlp.online-b5", "control"),
    ("paper-mlp.online-b5", "unchanged"),
    ("paper-mlp.online-b5", "half_batch"),
    ("paper-mlp.dp4-b256", "no_exchange"),
])
def test_control_and_faults_are_not_correct(cell, variant):
    r = _run(cell, variant)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("variant,correct", [(None, True),
                                             ("unchanged", False),
                                             ("half_batch", False)])
def test_four_device_mesh(variant, correct):
    r = _run_4("paper-mlp.dp4-b256", variant)
    assert r["correct"] is correct, r["checks"]
