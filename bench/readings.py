#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, over many seeds, for the
program and for what the check must catch, in one process on the chip.

    python3 bench/readings.py --workload <cell> --seeds 12 --first-seed <n> \\
        --variants program,control,half_batch

``program`` is a sound run; ``control`` the program's own 12-bit LNS path
in place of the 16-bit one the configuration states; ``half_batch``,
``unchanged`` and ``no_exchange`` the faults a training cell can have
(``no_exchange`` planted in the reference put in the program's place).
Each run prints one JSON line with its seed, variant and numbers, and
both sides' losses and per-leaf norms (``sides``).  The
limits in ``bench/workloads/<cell>.json`` are set between the largest
reading of sound runs and the smallest of the control and the faults.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from common import ROOT, enable_compile_cache, load_json  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)

    cell = load_json("workloads", args.workload + ".json")
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    enable_compile_cache()
    import run
    for variant in args.variants.split(","):
        v = None if variant == "program" else variant
        for i in range(args.seeds):
            seed = args.first_seed + i
            t = time.perf_counter()
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               t0=t, variant=v,
                               require_compiled=v != "no_exchange")
            print(json.dumps({
                "cell": args.workload, "variant": variant, "seed": seed,
                "chips": cell["chips"], "correct": res["correct"],
                "numbers": {k: c["value"] for k, c in res["checks"].items()},
                "sides": res["sides"],
                "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
