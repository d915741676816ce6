"""Per-(spec, op, shape) block-size autotuner for the LNS Pallas kernels.

Block shapes never change the kernels' *semantics* — the sequential-MAC
contraction is tiling-invariant (pinned by the block-shape-invariance
tests) — only their speed: grid volume, padding waste, VMEM residency and
pipelining all move with the tile sizes, and the best choice depends on
the op, the problem shape, the Δ table and the execution mode.  Nobody
should pick them by hand per call site; this module is the single place
block shapes are chosen for every caller that says ``blocks=auto`` (a
:class:`~repro.core.spec.NumericsSpec` axis, also per-layer via
:class:`~repro.core.plan.NumericsPlan` rules like
``hidden=blocks:256x128x128``).

Resolution order (:func:`lookup`):

1. in-memory cache;
2. persistent JSON cache under ``.lns_autotune/`` (override with
   ``LNS_AUTOTUNE_DIR``).  One file per environment — the key hashes the
   jax version, backend platform and device kind, so a cache produced on
   one machine never feeds another — and each entry records the git
   commit + wall time it was measured at (provenance for bench review);
3. measured search over a VMEM-budget-pruned candidate grid
   (:func:`candidate_blocks`), timed like ``benchmarks/kernel_bench.py``
   times kernels, then persisted.

Measurement only happens *outside* jit traces: the kernels resolve their
blocks at trace time (shapes are static), where timing a candidate is
impossible, so a trace-time miss falls back to the deterministic
:func:`heuristic_blocks` (best-ranked candidate, no persistence) and an
eager :func:`prime_matmul` / :func:`lookup` call — e.g. from the kernel
bench, the quickstart, or a warmup hook — fills the real cache.  Set
``LNS_AUTOTUNE_DISABLE=1`` to force the heuristic everywhere.

Shape convention: every op is described as ``(R, C, CT)`` — output rows,
output columns, contraction length — matching ``_launch_mac``:

====================  =============  ==========================
op                    (R, C, CT)     kernel block kwargs
====================  =============  ==========================
``fwd``               (M, N, K)      block_m, block_n, block_k
``dx``                (M, K, N)      block_m, block_k, block_n
``dw``                (K, N, M)      block_k, block_n, block_m
``dw_partials``       (K, N, seg)    block_k, block_n (CT fixed)
``boxsum``            (M, 1, K)      block_m, block_k
====================  =============  ==========================
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import subprocess
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..core.delta import DeltaSpec
from ..core.formats import LNSFormat
from ..core.lns import resolve_interpret
from .lns_matmul.lns_matmul import tile, tiling

OPS = ("fwd", "dx", "dw", "dw_partials", "boxsum")

#: Per-grid-step VMEM budget for candidate pruning: half of the ~16 MiB
#: per-core budget, leaving room for double buffering and the Δ LUT.
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024

DEFAULT_CACHE_DIR = ".lns_autotune"

_AXIS_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)

#: Fallback when a shape admits no candidate under the budget (never the
#: case for sane budgets; kept total so lookup() cannot fail).
FALLBACK_BLOCKS = (128, 128, 128)

#: entry key → ((block_r, block_c, block_ct), max_candidates, reps) —
#: the search depth rides along so a shallow in-process tune can be
#: superseded by a deeper request (same rule as the disk cache).
_MEM: dict = {}
_DISK: dict = {}         # cache path → loaded entries dict


def vmem_bytes(op: str, blocks) -> int:
    """Worst-case per-grid-step VMEM of one kernel launch (int32 planes).

    Budgets for the *fused* variants of each op, since autotuned blocks
    feed those launches too: the dw slots hold resident weight/momentum
    tiles plus updated-weight/momentum outputs next to the accumulator
    (10 (R, C) planes total with momentum on); the fwd slots hold the
    epilogue's bias row and z_sign output next to out + acc (≈6 planes).
    Boxsum holds one (R, CT) code/sign pair + (R,) accumulators.
    """
    br, bc, bct = blocks
    if op == "boxsum":
        return 4 * 2 * (br * bct + 2 * br)
    out_planes = 10 if op in ("dw", "dw_partials") else 6
    return 4 * (2 * br * bct + 2 * bct * bc + out_planes * br * bc)


def _axis_candidates(dim: int, align: int):
    """Block edges for one axis that its tiling rule admits, as fitted
    tiles (:func:`~repro.kernels.lns_matmul.lns_matmul.tile`)."""
    return sorted({tile(v, dim, align)
                   for v in _AXIS_CANDIDATES + (dim,)
                   if v >= dim or v % align == 0})


def candidate_blocks(op: str, shape, *, interpret: "bool | None" = None,
                     vmem_budget: int = DEFAULT_VMEM_BUDGET,
                     max_candidates: int = 8):
    """VMEM-budget-pruned, ranked ``(block_r, block_c, block_ct)`` grid.

    Compiled launches (``interpret`` resolving False) only get tiles the
    chip's (8, 128) rule admits: output rows and columns in multiples of
    128, the contraction in multiples of 8 (or whole axes, padded).
    Ranking is a static cost proxy — fewer grid steps first (per-step
    launch/index overhead dominates small problems), then less padding
    waste, then larger contraction blocks (longer in-VMEM MAC runs) —
    truncated to ``max_candidates`` so a cold measured search stays
    cheap.  The proxy orders *candidates to try*; the measured search
    picks the winner.
    """
    if op not in OPS:
        raise ValueError(f"unknown autotune op {op!r}; expected one of "
                         f"{OPS}")
    sub, lane = tiling(resolve_interpret(interpret))
    r, c, ct = shape
    col_cands = [1] if c <= 1 else _axis_candidates(c, lane)
    ct_cands = [ct] if op == "dw_partials" else _axis_candidates(ct, sub)
    scored = []
    for br in _axis_candidates(r, lane):
        for bc in col_cands:
            for bct in ct_cands:
                if vmem_bytes(op, (br, bc, bct)) > vmem_budget:
                    continue
                gr, gc_, gct = -(-r // br), -(-c // bc), -(-ct // bct)
                grid = gr * gc_ * gct
                waste = (gr * br * gc_ * bc * gct * bct) / float(
                    max(1, r * c * ct))
                scored.append(((grid, waste, -bct, br, bc),
                               (br, bc, bct)))
    scored.sort()
    ranked, seen = [], set()
    for _, b in scored:
        if b not in seen:
            seen.add(b)
            ranked.append(b)
    return ranked[:max_candidates] or [FALLBACK_BLOCKS]


def heuristic_blocks(op: str, shape, **kw):
    """Deterministic no-measurement choice: the best-ranked candidate.

    What ``blocks=auto`` resolves to on a cache miss inside a jit trace
    (where timing is impossible) — typically full-shape blocks whenever
    they fit the VMEM budget.
    """
    return candidate_blocks(op, shape, **kw)[0]


# ------------------------------------------------------------------------
# Env / commit stamping + persistent cache
# ------------------------------------------------------------------------

def env_stamp() -> dict:
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device": getattr(dev, "device_kind", str(dev)),
    }


def _env_key() -> str:
    blob = json.dumps(env_stamp(), sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


@functools.lru_cache(maxsize=1)
def _git_commit() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=5, cwd=os.path.dirname(__file__))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def cache_dir() -> str:
    return os.environ.get("LNS_AUTOTUNE_DIR", DEFAULT_CACHE_DIR)


def cache_path() -> str:
    return os.path.join(cache_dir(), f"cache-{_env_key()}.json")


def _delta_key(spec: DeltaSpec) -> str:
    return f"{spec.kind}:{spec.d_max!r}:{spec.r!r}"


def entry_key(op: str, shape, fmt: LNSFormat, spec: DeltaSpec,
              interpret: bool) -> str:
    r, c, ct = shape
    return (f"{op}|{r}x{c}x{ct}|{fmt.name}|{_delta_key(spec)}"
            f"|interpret={bool(interpret)}")


# Files already warned about this process (one RuntimeWarning per file,
# not one per lookup).
_WARNED_CORRUPT: set = set()


def _quarantine(path: str, err: Exception) -> None:
    """Move an unparsable cache file aside as ``<path>.corrupt`` so the
    next lookup re-tunes into a fresh file instead of failing forever
    (e.g. a crash mid-``_persist`` leaving a torn JSON)."""
    try:
        os.replace(path, path + ".corrupt")
    except OSError:
        pass  # read-only FS: still fall through to re-tune in memory
    if path not in _WARNED_CORRUPT:
        _WARNED_CORRUPT.add(path)
        warnings.warn(
            f"autotune cache {path} is corrupt ({err}); quarantined as "
            f"{path}.corrupt and re-tuning", RuntimeWarning, stacklevel=3)


def _load_disk() -> dict:
    path = cache_path()
    if path not in _DISK:
        entries = {}
        try:
            with open(path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError(f"expected object, got {type(data).__name__}")
            if data.get("env") == env_stamp():
                entries = data.get("entries", {})
        except OSError:
            pass  # missing file: first run in this env
        except ValueError as e:
            _quarantine(path, e)
        _DISK[path] = entries
    return _DISK[path]


def _persist(key: str, blocks, ms: float, search: dict) -> None:
    path = cache_path()
    entries = _load_disk()
    entries[key] = {"blocks": list(blocks), "ms": ms,
                    "commit": _git_commit(), "time": time.time(),
                    "search": search}
    try:
        os.makedirs(cache_dir(), exist_ok=True)
        with open(path, "w") as f:
            json.dump({"env": env_stamp(), "entries": entries}, f,
                      indent=1, sort_keys=True)
    except OSError:
        pass  # read-only FS etc.: the in-memory cache still holds the win


def clear_caches() -> None:
    """Drop the in-memory caches (tests; the JSON files stay)."""
    _MEM.clear()
    _DISK.clear()


# ------------------------------------------------------------------------
# Measurement
# ------------------------------------------------------------------------

def _can_measure() -> bool:
    """Timing is meaningful only in eager code: inside a jit trace even a
    fresh constant is a tracer."""
    if os.environ.get("LNS_AUTOTUNE_DISABLE"):
        return False
    return not isinstance(jnp.zeros(()), jax.core.Tracer)


def _measure_ms(fn, reps: int = 3) -> float:
    """Best-of-``reps`` wall time in ms (min is robust to interference —
    one background hiccup inflates a mean and misranks candidates)."""
    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def _bench_launcher(op: str, shape, blocks, fmt: LNSFormat,
                    spec: DeltaSpec, interpret: bool):
    """A zero-arg timed callable running the real kernel at ``blocks``.

    Times the *unfused* kernel of each op; the fused launches
    (``matmul_fused`` / ``matmul_dw_update``) consume the same entries.
    This is a deliberate approximation: the flush epilogue is O(output
    tile) work applied once per tile, against O(CT × tile) MAC work per
    tile, so block *ranking* is dominated by the shared MAC loop — and
    the VMEM pruning (:func:`vmem_bytes`) already budgets for the fused
    variants' extra resident planes, so every candidate is launchable
    either way.  If a future epilogue grows comparable to the MAC cost,
    key entries by epilogue presence instead of sharing them.
    """
    from ..core.lns import encode
    from .lns_boxsum import lns_boxsum_kernel
    from .lns_matmul import (lns_matmul_dw_kernel,
                             lns_matmul_dw_partials_kernel,
                             lns_matmul_dx_kernel, lns_matmul_kernel)
    r, c, ct = shape
    br, bc, bct = blocks
    rng = np.random.default_rng(0)

    def enc(*s):
        return encode(rng.normal(size=s).astype(np.float32), fmt)

    if op == "fwd":
        a, b = enc(r, ct), enc(ct, c)
        return lambda: lns_matmul_kernel(
            a, b, fmt=fmt, spec=spec, block_m=br, block_n=bc, block_k=bct,
            interpret=interpret).code
    if op == "dx":
        dy, w = enc(r, ct), enc(c, ct)
        return lambda: lns_matmul_dx_kernel(
            dy, w, fmt=fmt, spec=spec, block_m=br, block_k=bc, block_n=bct,
            interpret=interpret).code
    if op == "dw":
        x, dy = enc(ct, r), enc(ct, c)
        return lambda: lns_matmul_dw_kernel(
            x, dy, fmt=fmt, spec=spec, block_k=br, block_n=bc, block_m=bct,
            interpret=interpret).code
    if op == "dw_partials":
        # CT is one segment; time a canonical 2-segment batch.
        x, dy = enc(2 * ct, r), enc(2 * ct, c)
        return lambda: lns_matmul_dw_partials_kernel(
            x, dy, num_segments=2, fmt=fmt, spec=spec, block_k=br,
            block_n=bc, interpret=interpret).code
    if op == "boxsum":
        x = enc(r, ct)
        return lambda: lns_boxsum_kernel(
            x, fmt=fmt, spec=spec, block_m=br, block_k=bct,
            interpret=interpret).code
    raise ValueError(f"unknown autotune op {op!r}")


def tune(op: str, shape, *, fmt: LNSFormat, spec: DeltaSpec,
         interpret: "bool | None" = None,
         vmem_budget: int = DEFAULT_VMEM_BUDGET, max_candidates: int = 8,
         reps: int = 3, measure_fn=None, verbose: bool = False):
    """Measured search; returns ``(best_blocks, {blocks: ms})``.

    ``measure_fn(op, shape, blocks) -> ms`` overrides the real timing
    (tests inject deterministic stubs).  Does not consult or write any
    cache — :func:`lookup` wraps this with the cache discipline.
    """
    interpret = resolve_interpret(interpret)
    results = {}
    for blocks in candidate_blocks(op, shape, interpret=interpret,
                                   vmem_budget=vmem_budget,
                                   max_candidates=max_candidates):
        if measure_fn is not None:
            ms = float(measure_fn(op, shape, blocks))
        else:
            ms = _measure_ms(
                _bench_launcher(op, shape, blocks, fmt, spec, interpret),
                reps=reps)
        results[blocks] = ms
        if verbose:
            r, c, ct = blocks
            print(f"[autotune] {op} {shape}: {r}x{c}x{ct} → {ms:.2f} ms")
    best = min(results, key=results.get)
    return best, results


def lookup(op: str, shape, *, fmt: LNSFormat, spec: DeltaSpec,
           interpret: "bool | None" = None, measure: "bool | None" = None,
           measure_fn=None, vmem_budget: int = DEFAULT_VMEM_BUDGET,
           max_candidates: int = 8, reps: int = 3, verbose: bool = False):
    """The blocks ``blocks=auto`` resolves to for one kernel launch.

    Memory cache → persistent JSON cache → measured search (persisted).
    ``measure=None`` auto-detects: measure only outside jit traces and
    when ``LNS_AUTOTUNE_DISABLE`` is unset; a non-measurable miss returns
    :func:`heuristic_blocks` *without* caching it, so a later eager call
    can still fill the real entry.

    Persisted entries record the search depth that produced them; an
    entry from a *shallower* search (fewer candidates or reps) than
    requested does not satisfy a measurable lookup — it is re-tuned and
    overwritten — so a quick demo tune can never pin the blocks a full
    bench search would have chosen.  (When measurement is impossible, a
    shallow measured entry still beats the heuristic.)
    """
    interpret = resolve_interpret(interpret)
    key = entry_key(op, shape, fmt, spec, interpret)
    cached = _MEM.get(key)
    if cached is not None and cached[1] >= max_candidates \
            and cached[2] >= reps:
        return cached[0]
    entry = _load_disk().get(key)
    if entry is not None:
        search = entry.get("search", {})
        if (search.get("max_candidates", 0) >= max_candidates
                and search.get("reps", 0) >= reps):
            blocks = tuple(entry["blocks"])
            _MEM[key] = (blocks, search.get("max_candidates", 0),
                         search.get("reps", 0))
            return blocks
    if measure is None:
        measure = _can_measure()
    if not measure:
        # Not measurable here: a shallow *measured* entry still beats
        # the heuristic, but is never promoted to the caches.
        if cached is not None:
            return cached[0]
        if entry is not None:
            return tuple(entry["blocks"])
        return heuristic_blocks(op, shape, interpret=interpret,
                                vmem_budget=vmem_budget,
                                max_candidates=max_candidates)
    best, results = tune(op, shape, fmt=fmt, spec=spec,
                         interpret=interpret, vmem_budget=vmem_budget,
                         max_candidates=max_candidates, reps=reps,
                         measure_fn=measure_fn, verbose=verbose)
    _MEM[key] = (best, max_candidates, reps)
    _persist(key, best, results[best],
             {"max_candidates": max_candidates, "reps": reps,
              "vmem_budget": vmem_budget})
    return best


def prime_matmul(m: int, k: int, n: int, *, fmt: LNSFormat,
                 spec: DeltaSpec, interpret: "bool | None" = None,
                 **tune_kw):
    """Eagerly tune the three ⊞-MAC products of one (M, K) × (K, N) layer.

    Call this *outside* jit (model setup, bench warmup) so the jitted
    train step finds measured entries instead of the heuristic fallback.
    Returns ``{op: blocks}``.
    """
    shapes = {"fwd": (m, n, k), "dx": (m, k, n), "dw": (k, n, m)}
    return {op: lookup(op, s, fmt=fmt, spec=spec, interpret=interpret,
                       **tune_kw)
            for op, s in shapes.items()}
