"""The trace reduction on a small trace recorded on a TPU v5e
(``data/tiny.xplane.pb``, made by ``record_trace.py``: the tiny test
cell, one traced second), and its interval arithmetic on hand-made
intervals.

  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from bench.lib import trace  # noqa: E402
from bench.lib.readers import PROGRAMS  # noqa: E402
from bench.models.llama_bitnet import KERNELS  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SPANS = ("submit", "run_iteration", "observe", "wait")
LAYERS = 2  # data/configs/tiny.json


def test_merge_and_gap_labels():
    busy = trace.merge([(1.0, 2.0), (1.5, 3.0), (4.0, 5.0)])
    assert busy == [(1.0, 3.0), (4.0, 5.0)]
    spans = [("run_iteration", 0.0, 3.5), ("wait", 3.6, 6.0)]
    gaps = trace.label_gaps(busy, (0.0, 6.0), spans)
    # (3, 4) is half under run_iteration, 0.4 under wait
    assert gaps == [("run_iteration", 1.0), ("run_iteration", 1.0),
                    ("wait", 1.0)]


@pytest.fixture(scope="module")
def recorded():
    return trace.reduce(DATA / "tiny.xplane.pb", SPANS)


def test_window_busy_and_gaps(recorded):
    t = recorded
    assert 0.5 < t.window_s < 2.0
    assert 0 < t.busy_s < t.window_s
    idle = sum(s for _, s in t.gaps)
    assert idle == pytest.approx(t.window_s - t.busy_s, rel=1e-9)
    assert {label for label, _ in t.gaps} <= set(SPANS) | {"idle"}


def test_programs_and_kernels(recorded):
    t = recorded
    steps, step_s = t.program_time(PROGRAMS["decode"])
    assert steps > 0 and step_s > 0
    # four ternary projections (qkv, o, gate|up, down) a layer, one
    # flash-decode a layer, in every decode step
    calls, kernel_s = t.kernel_time(KERNELS["ternary_matmul"],
                                    PROGRAMS["decode"])
    assert calls == 4 * LAYERS * steps
    assert 0 < kernel_s < step_s
    calls, _ = t.kernel_time(KERNELS["flash_decode"], PROGRAMS["decode"])
    assert calls == LAYERS * steps
    assert t.top_ops(3) and len(t.top_gaps()) <= 10
