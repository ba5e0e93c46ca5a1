"""Readings of the correctness check for a cell, over many seeds in one
process: the program as the benchmark runs it, and the program with one
of its lower-precision paths switched on (the control). Limits in
``bench/cells/<cell>.json`` are set from these readings.

  python bench/tests/readings.py --workload falcon3-7b.decode \\
      --seeds 11,12,13 --seconds 30 [--control act_int4] [--rate 4,6,8]

Runs on the chip (the benchmark's own runs never call it). Each seed
builds its own weights and engine; one process keeps the set-up of the
process and the chip out of every seed but the first. ``--rate``
overrides an open-loop mix's arrival rate, for the knee sweep. Prints one
JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    ap.add_argument("--rate", default=None,
                    help="open-loop rates, one per seed (comma-separated)")
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(ROOT / ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.lib.harness import run_cell
    from bench.lib.spec import Spec

    spec = Spec()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = ([float(r) for r in args.rate.split(",")] if args.rate
             else [None] * len(seeds))
    mix = spec.traffic(spec.workload(args.workload)["traffic"])
    for seed, rate in zip(seeds, rates):
        if rate is not None:
            spec.traffic = lambda name, _m=dict(mix, rate_per_s=rate): _m
        out = run_cell(spec, args.workload, seed, args.seconds,
                       bool(args.trace), control=args.control)
        print(json.dumps({"seed": seed, "control": args.control,
                          "rate": rate, **out}), flush=True)
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
