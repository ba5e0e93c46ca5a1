"""Record the small trace that ``test_trace.py`` reduces: the tiny test
cell of ``bench/tests/data`` served on a TPU for two seconds with
``--trace 1``, one second of it traced.

  python bench/tests/record_trace.py OUT_DIR

Copies the ``.xplane.pb`` to ``OUT_DIR/tiny.xplane.pb`` and prints what
the reduction reads from it. Needs a TPU.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
DATA = Path(__file__).resolve().parent / "data"


def main() -> int:
    out_dir = Path(sys.argv[1])
    from bench.lib import trace
    from bench.lib.harness import SPAN_NAMES, run_cell
    from bench.lib.spec import Spec

    work = ROOT / ".bench_work" / "record"
    res = run_cell(Spec(DATA / "BENCHMARK.json", DATA), "tiny.open", 5, 2.0,
                   True, work_dir=work)
    out_dir.mkdir(parents=True, exist_ok=True)
    dst = out_dir / "tiny.xplane.pb"
    shutil.copy(trace.find(work / "trace"), dst)
    t = trace.reduce(dst, SPAN_NAMES)
    print(json.dumps({"result": res, "programs": {
        k: t.program_time(k) for k in t.programs},
        "window_s": t.window_s, "busy_s": t.busy_s,
        "ops": t.top_ops(20), "gaps": t.top_gaps()}, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
