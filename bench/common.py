"""Shared pieces of the benchmark harness: where its files are, how a piece
is found by its name, seeds, host spans and the compile clock.

Importing this module touches no accelerator: JAX is imported inside the
functions that need it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import zlib

#: ``bench/``, and the checkout that holds it.
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """Import ``bench/<parts>.py`` by path (names may hold ``-`` and
    ``.``, as the configurations' and metrics' names do)."""
    path = os.path.join(BENCH, *parts[:-1], parts[-1] + ".py")
    name = "bench_" + "_".join(parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names(kind: str, suffix: str):
    """The names of the pieces of one kind: ``workloads``, ``metrics``..."""
    d = os.path.join(BENCH, kind)
    return sorted(f[:-len(suffix)] for f in os.listdir(d)
                  if f.endswith(suffix))


def sub_seed(seed: int, *salt) -> int:
    """A 31-bit seed drawn from a run's ``--seed`` (any whole number, up to
    64 bits) and a salt naming what it seeds."""
    h = zlib.crc32(repr(salt).encode())
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, h]
    import numpy as np
    return int(np.random.default_rng(words).integers(0, 2**31 - 1))


def leaf_id(path: str) -> int:
    """A stable number for a parameter's path, to fold into a PRNG key."""
    return zlib.crc32(path.encode()) & 0x7FFFFFFF


@contextlib.contextmanager
def span(name: str):
    """A host span in the profiler's trace (a no-op cost when no trace is
    being taken)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class CompileClock:
    """Seconds JAX spent lowering and compiling programs, from its own
    monitoring events, and how many programs the persistent compile cache
    supplied or missed."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.events = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.events += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at ``JAX_COMPILATION_CACHE_DIR``
    when that is set and otherwise at ``.jax_cache/`` in the checkout: a
    fixed path, so every run of one checkout finds the programs of the
    runs before it.  Every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
