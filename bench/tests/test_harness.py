"""The harness end to end on the CPU, at the tiny test cells of
``bench/tests/data``, with the look for a chip skipped: a sound run is
correct, also where every request is still in flight at the window's
close (``tiny.batch``); a run whose decode step alters each token where it is produced
is not, nor is one whose decode step hands back the KV cache it was
given, unchanged, and neither is the check's control; without a TPU the command
exits non-zero and prints no result.
Also: every seed gets the same work from the traffic generator.

  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

from __future__ import annotations

import os
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


CELLS = ["tiny.open", "tiny.closed", "tiny.batch"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(spec, workload, tmp_path):
    out = _run(spec, workload, tmp_path)
    gap = out["check"]["logit_gap"]
    assert out["correct"], gap
    assert out["failed"] == 0 and out["attempted"] > 0
    if workload == "tiny.batch":  # no request finished: the check read
        assert out["attempted"] == 3  # the tokens of those in flight
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "check"


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
