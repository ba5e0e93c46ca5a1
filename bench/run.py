"""Run one cell of the benchmark once and print its result line.

  python bench/run.py --workload falcon3-1b.chat --seed 7 --seconds 30 --trace 0

Builds the cell's model from the seed on the chip, serves the cell's
traffic through ``Engine`` for ``--seconds`` seconds, checks what it
served against the plain reference, and prints one JSON object as the
last line of standard output. ``--trace 1`` records a profiler trace of
part of the window and reports the per-layer metrics instead of the
end-to-end ones. Without a TPU (or with fewer chips than the cell asks
for) it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_compile_cache"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_work" / "tpu_logs"))
    import jax

    # the persistent compilation cache lives at a fixed path in the
    # checkout, so the second run of a cell there compiles nothing
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.lib.harness import NoChip, run_cell
    from bench.lib.spec import Spec

    try:
        out = run_cell(Spec(), args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    check = out["check"]
    for name, v in check.items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
