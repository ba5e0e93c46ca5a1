"""The harness end to end on the CPU, at the tiny test cells of
``bench/tests/data``, with the look for a chip skipped: a sound run is
correct and compiles nothing in its window, also where every request is
still in flight at the window's close (``tiny.batch``) and where the
engine neither pages nor chunks prefill (``tiny.batch_whole``); a run
whose decode step alters each token where it is produced is not, nor is
one whose decode step hands back the KV cache it was given, unchanged,
and neither is the check's control; without a TPU the command exits
non-zero and prints no result. A traced run on a contiguous cache, with
chunked or whole-prompt admission, is correct and records each decode
call's true cache lengths; a whole-prompt mix that would admit in the
window is refused before weights are made.
Also: every seed gets the same work from the traffic generator.

  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

from __future__ import annotations

import io
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import traffic  # noqa: E402


@pytest.fixture(scope="module")
def spec():
    from bench.lib.spec import Spec

    return Spec(DATA / "BENCHMARK.json", DATA)


def _run(spec, workload, tmp_path, **kw):
    from bench.lib.harness import run_cell

    return run_cell(spec, workload, 2**31 + 7, 3.0, False,
                    require_chip=False, work_dir=tmp_path, **kw)


def alter_tokens(engine, sess):
    """Break the timed path: every decode step hands on the token after
    the one it sampled."""
    real = sess.ctx.step_fn
    vocab = engine.cfg.vocab_size

    def step(params, state):
        out = real(params, state)
        return out._replace(tok=(out.tok + 1) % vocab)

    sess.ctx.step_fn = step


CELLS = ["tiny.open", "tiny.closed", "tiny.batch", "tiny.batch_whole"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(spec, workload, tmp_path):
    log = io.StringIO()
    out = _run(spec, workload, tmp_path, log=log)
    gap = out["check"]["logit_gap"]
    assert out["correct"], gap
    assert out["failed"] == 0 and out["attempted"] > 0
    if workload.startswith("tiny.batch"):  # no request finished: the check
        assert out["attempted"] == 3  # read the tokens of those in flight
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "check"
    assert re.search(r"compiles in window 0,", log.getvalue()), log.getvalue()


def keep_cache(engine, sess):
    """Break the timed path: every decode step returns the KV cache it
    was given, as if it had appended nothing."""
    import jax
    import jax.numpy as jnp

    real = sess.ctx.step_fn

    def step(params, state):
        kept = jax.tree.map(jnp.copy, state.cache)  # the step donates state
        return real(params, state)._replace(cache=kept)

    sess.ctx.step_fn = step


@pytest.mark.parametrize("fault", [alter_tokens, keep_cache],
                         ids=["altered_tokens", "cache_unchanged"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(spec, workload, fault, tmp_path):
    out = _run(spec, workload, tmp_path, fault=fault)
    gap = out["check"]["logit_gap"]
    assert not out["correct"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(spec, workload, tmp_path):
    """The check's control: the program with int4 activations where the
    configuration states int8 (``model.CONTROLS``)."""
    out = _run(spec, workload, tmp_path, control="act_int4")
    gap = out["check"]["logit_gap"]
    assert not out["correct"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("workload", ["tiny.batch_contiguous",
                                      "tiny.batch_whole"])
def test_traced_decode_lengths_are_the_cache_lengths(spec, workload,
                                                     tmp_path, monkeypatch):
    """On a contiguous cache, chunked or whole-prompt: a traced run is
    correct, its metrics are finite, and each decode call it records
    holds, for each decoding slot, the device cache's length before the
    call + 1 (the lengths the kernels' rooflines price)."""
    import jax
    from bench.lib import harness

    tracers = []

    class Checked(harness.Tracer):
        def __init__(self, sess, *args):
            super().__init__(sess, *args)
            tracers.append(self)
            self.device = []
            traced_step = sess.ctx.step_fn

            def step(params, state):
                n = len(self.calls["decode"])
                cache = jax.tree.leaves(
                    state.cache, is_leaf=lambda x: hasattr(x, "lengths"))[0]
                lens = np.asarray(cache.lengths).reshape(-1, len(
                    sess.ctx.seq_mirror))[0]
                live = np.asarray(state.allocated) & ~np.asarray(state.done)
                out = traced_step(params, state)
                if len(self.calls["decode"]) > n:
                    self.device.append(sorted(int(x) + 1 for x in lens[live]))
                return out

            sess.ctx.step_fn = step

    monkeypatch.setattr(harness, "Tracer", Checked)
    from bench.lib.harness import run_cell

    out = run_cell(spec, workload, 2**31 + 11, 3.0, True,
                   require_chip=False, work_dir=tmp_path)
    assert out["correct"], out["check"]
    assert out["metrics"]
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())
    (tracer,) = tracers
    recorded = [sorted(c) for c in tracer.calls["decode"]]
    assert recorded and all(len(c) == 3 for c in recorded)
    assert recorded == tracer.device


@pytest.mark.parametrize("change", [
    dict(loop="open", rate_per_s=4.0),
    dict(first_wave_in_setup=False),
    dict(clients=5),
    dict(prefill_chunk=32, pool_pages=64),
], ids=["open_loop", "first_wave_in_window", "more_clients_than_slots",
        "pool_without_paging"])
def test_traffic_a_layout_cannot_serve_is_refused(change, tmp_path,
                                                  monkeypatch):
    """A whole-prompt mix that would admit in the window, and a non-paged
    mix that sizes a page pool, are refused at set-up, before the model's
    weights are made."""
    from bench.lib.spec import Spec

    mix = traffic.load(DATA / "traffic" / "batch_whole.json")
    for key, value in change.items():
        (mix["engine"] if key in mix["engine"] or key == "pool_pages"
         else mix)[key] = value
    spec = Spec(DATA / "BENCHMARK.json", DATA)
    monkeypatch.setattr(spec, "traffic", lambda name: mix)
    monkeypatch.setattr(spec, "model",
                        lambda conf: pytest.fail("weights were made"))
    with pytest.raises(ValueError, match="prefix_sharing|fixed batch"):
        _run(spec, "tiny.batch_whole", tmp_path)


def test_chunked_traffic_for_a_model_that_cannot_chunk_is_refused(
        spec, tmp_path, monkeypatch):
    """Where the engine would fall back to whole-prompt admission, a mix
    that states a prefill chunk is refused before the warm-up."""
    from repro.serving.engine import Engine

    monkeypatch.setattr(Engine, "_chunked_capable", lambda self: False)
    with pytest.raises(ValueError, match="cannot chunk prefill"):
        _run(spec, "tiny.batch_contiguous", tmp_path)


def test_sample_takes_finished_and_in_flight_requests():
    """The check samples what the window served: finished requests and
    those still in flight at its close, the longest first, never a
    failed one or one that was given no token."""
    from bench.lib import check
    from bench.lib.client import Record

    def rec(i, n_prompt, served, outcome):
        return Record(index=i, prompt_len=n_prompt, max_new=64,
                      outcome=outcome, served=None if served is None
                      else np.zeros(served, np.int32))

    recs = [rec(0, 100, 40, "finished"), rec(1, 900, 30, None),
            rec(2, 50, 60, "expired"), rec(3, 70, None, None),
            rec(4, 80, 0, None), rec(5, 60, 20, None)]
    picked = check.sample(recs, seed=2**33 + 1)
    assert picked[0].index == 1
    assert sorted(r.index for r in picked) == [0, 1, 5]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "falcon3-7b.decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_seed_gets_the_same_work():
    mix = traffic.load(DATA / "traffic" / "open.json")
    a = traffic.Mix(mix, 1, 30.0, 2048).scheduled()
    b = traffic.Mix(mix, 2**33 + 5, 30.0, 2048).scheduled()
    assert sorted(len(p.prompt) for p in a) == sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert a[-1].due == pytest.approx(b[-1].due)
    again = traffic.Mix(mix, 1, 30.0, 2048).scheduled()
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
    closed = traffic.load(DATA / "traffic" / "closed.json")
    c = traffic.Mix(closed, 3, 30.0, 2048)
    d = traffic.Mix(closed, 4, 30.0, 2048)
    # the batch the window opens with, then one stream
    for n in (closed["clients"], traffic.CLOSED_STREAM):
        sa = sorted(c.next_request().max_new for _ in range(n))
        sb = sorted(d.next_request().max_new for _ in range(n))
        assert sa == sb
