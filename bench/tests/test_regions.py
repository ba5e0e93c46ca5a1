"""The region and phase reduction (``bench/lib/regions.py``) on a small
trace recorded on a TPU v5e with the engine's scopes and spans in it
(``data/tiny_scoped.xplane.pb``, made by ``record_trace.py``: the tiny
test cell, one traced second), on the trace recorded before the program
had them (``data/tiny.xplane.pb``), and on hand-made intervals.

  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

from __future__ import annotations

import importlib.util
import shutil
import sys
import types
from pathlib import Path

import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from bench.lib import regions, trace  # noqa: E402
from bench.models import llama_bitnet  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
SPANS = ("submit", "run_iteration", "observe", "wait")
NEW_METRICS = ("kv_write_ms.tok_s", "unattributed_ms.tok_s",
               "iteration_host_ms.tok_s")
LEAVES = llama_bitnet.LEAF_REGIONS


def test_region_is_the_innermost_leaf():
    assert regions.region(
        "jit(step)/while/body/closed_call/kv_write/dynamic_update_slice"
    ) == "kv_write"
    qkv = ("jit(step)/layers/while/body/closed_call/qkv_proj/"
           "jit(ternary_matmul_actq)/ternary_matmul_actq_pallas/pallas_call")
    assert regions.region(qkv, LEAVES) == "qkv_proj"
    # a model's own leaf is a region only for that model
    assert regions.region(qkv) == regions.UNATTRIBUTED
    assert regions.region("jit(step)/sample", LEAVES) == "sample"
    assert regions.region("jit(step)/layers/while/body/dynamic_slice",
                          LEAVES) == regions.UNATTRIBUTED
    assert regions.region("", LEAVES) == regions.UNATTRIBUTED


def test_nested_gap_labels():
    busy = trace.merge([(1.0, 2.0), (4.0, 5.0), (5.5, 6.0)])
    spans = [("run_iteration", 0.0, 6.0), ("engine.sweep", 0.0, 0.3),
             ("engine.dispatch", 2.0, 2.5), ("engine.sync", 2.5, 6.0),
             ("observe", 6.0, 7.0)]
    gaps = regions.label_gaps(busy, (0.0, 7.0), spans)
    # (0, 1): engine.sweep covers under half, so run_iteration; (2, 4)
    # and (5, 5.5): run_iteration and engine.sync each cover most of it,
    # and engine.sync is the inner one
    assert gaps == [("run_iteration", 1.0), ("engine.sync", 2.0),
                    ("engine.sync", 0.5), ("observe", 1.0)]
    assert regions.label_gaps([], (0.0, 1.0), []) == [("idle", 1.0)]
    # spans that do not nest: as the reduction labels them
    flat = [("run_iteration", 0.0, 3.5), ("wait", 3.6, 6.0)]
    assert regions.label_gaps(busy, (0.0, 7.0), flat) == trace.label_gaps(
        busy, (0.0, 7.0), flat)


@pytest.fixture(scope="module")
def scoped():
    return regions.read(DATA / "tiny_scoped.xplane.pb", SPANS, LEAVES)


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(DATA / "tiny_scoped.xplane.pb", SPANS)


def test_same_clock_as_the_reduction(scoped, reduced):
    assert scoped.window == reduced.window
    assert sorted((n, a, b) for n, a, b, _ in scoped.ops) == sorted(
        (o.name, o.start, o.end) for o in reduced.ops)
    assert scoped.programs == reduced.programs


@pytest.mark.parametrize("program", ["step", "chunk_step"])
def test_regions_account_for_the_step(scoped, reduced, program):
    split = regions.step_split(scoped, program)
    n, total = reduced.program_time(program)
    assert split["executions"] == n > 0
    assert split["step"] == pytest.approx(total / n, rel=1e-9)
    parts = sum(v for k, v in split.items()
                if k not in ("step", "executions"))
    assert parts == pytest.approx(split["step"], rel=0.01)
    # a fusion counts in its root operation's region: the chunk step's
    # sampling fuses into its bookkeeping
    assert {"qkv_proj", "kv_write", "attention", "o_proj", "mlp",
            "lm_head", "bookkeeping"} <= set(split)
    assert ("sample" in split) == (program == "step")
    assert all(v >= 0 for v in split.values())


def test_phases_and_gaps(scoped, reduced):
    phases = regions.phase_seconds(scoped)
    assert {"sweep", "admit", "dispatch", "sync", "harvest"} <= set(phases)
    assert all(v >= 0 for v in phases.values())
    gaps = regions.gaps_by_phase(scoped, n=100)
    idle = reduced.window_s - reduced.busy_s
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    labels = {label for label, _ in gaps}
    assert labels <= ({"idle"} | set(SPANS)
                      | {regions.PHASE_PREFIX + p for p in phases})
    assert labels & {regions.PHASE_PREFIX + p for p in phases}


def test_trace_without_scopes_reads_nothing():
    """The trace of a program without regions or phases: the reduction
    calls every operation unattributed and gives no split or phases."""
    sc = regions.read(DATA / "tiny.xplane.pb", SPANS, LEAVES)
    assert {r for *_, r in sc.ops} == {regions.UNATTRIBUTED}
    assert regions.step_split(sc) is None
    assert regions.phase_seconds(sc) is None


def _reader(metric):
    path = METRICS / f"{metric}.py"
    spec = importlib.util.spec_from_file_location("m_" + metric.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers(tmp_path, monkeypatch, metric):
    """Each reader finds the run's trace where ``run_cell`` leaves it,
    reads a number from the scoped trace, nothing from the unscoped one,
    and nothing from a trace whose window is not the run's."""
    read = _reader(metric)
    monkeypatch.setattr(regions, "RUN_TRACE_DIR", tmp_path)
    for name, expect in (("tiny_scoped", True), ("tiny", False)):
        src = DATA / f"{name}.xplane.pb"
        dst = tmp_path / f"{name}" / "x.xplane.pb"
        shutil.rmtree(tmp_path, ignore_errors=True)
        dst.parent.mkdir(parents=True)
        shutil.copy(src, dst)
        run = types.SimpleNamespace(trace=trace.reduce(dst, SPANS),
                                    model=llama_bitnet)
        value = read(run)
        assert (value is not None and value > 0) == expect, (name, value)
        other = types.SimpleNamespace(model=llama_bitnet,
                                      trace=types.SimpleNamespace(
            window=(run.trace.window[0] + 1.0, run.trace.window[1])))
        assert read(other) is None
    assert read(types.SimpleNamespace(trace=None)) is None
