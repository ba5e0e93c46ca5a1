"""What the engine counts about itself (docs/serving.md, "Observability").

A tiny paged engine on the CPU, driven the way a serving client drives a
session (``submit_to_session`` + ``run_iteration``):

  * the ``ServeStats`` work counters equal what a wrapper around the
    decode and chunk steps records from the session's host mirrors (the
    benchmark's ``Tracer`` wrapper), and reconcile with the requests'
    own budgets and prompts;
  * ``traces`` counts each program's trace once, and nothing in a
    second session of the same engine;
  * ``host_s`` holds the ``run_iteration`` phases, within the
    iterations' wall time;
  * ``FinishedRequest.t_admit <= t_first <= t_finish``, each inside the
    ``run_iteration`` in which the client first sees that event.
"""

import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serving.engine import Engine
from repro.serving.scheduler import Request

PHASES = {"sweep", "admit", "grow", "dispatch", "sync", "harvest"}


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("falcon3-1b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **kw):
    cfg, params = model
    return Engine(cfg, params, hot_cap=8, max_len=96, prefill_chunk=8,
                  paged=True, prefix_sharing=False, slots=2, sync_every=4,
                  clock=time.perf_counter, **kw)


def _requests(vocab, shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=rng.integers(0, vocab, n).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(shapes)]


SHAPES = [(5, 7), (20, 3), (13, 9), (30, 5), (9, 11)]


def test_counters_match_a_step_wrapper(model):
    eng = _engine(model)
    ctx = eng.start_session([])
    calls = {"decode": [], "chunk": []}
    k_in_iter = [0]
    real_step, real_chunk = ctx.step_fn, eng._get_chunk_step()

    def step(params, state):
        # as the benchmark's Tracer: the k-th dispatch of an iteration
        # decodes every slot past its prefill with budget left beyond k
        k = k_in_iter[0]
        live = [s for s in ctx.sched.active_slots()
                if s not in ctx.prefilling and ctx.remaining[s] > k]
        calls["decode"].append([ctx.seq_mirror[s] + k + 1 for s in live])
        k_in_iter[0] += 1
        return real_step(params, state)

    def chunk(params, state, toks, n_valid, *rest):
        calls["chunk"].append(int(np.asarray(n_valid).sum()))
        return real_chunk(params, state, toks, n_valid, *rest)

    ctx.step_fn = step
    eng._chunk_step_fn = chunk
    reqs = _requests(model[0].vocab_size, SHAPES)
    for r in reqs:
        eng.submit_to_session(ctx, r)
    while not ctx.sched.idle():
        k_in_iter[0] = 0
        eng.run_iteration(ctx)
    st = ctx.stats
    assert st.decode_dispatches == len(calls["decode"]) > 0
    assert st.decode_slot_steps == sum(map(len, calls["decode"]))
    assert st.kv_tokens_attended == sum(map(sum, calls["decode"]))
    assert st.chunk_dispatches == len(calls["chunk"]) > 0
    assert st.prefill_tokens == sum(calls["chunk"])
    # and with what the requests asked for: one decode dispatch per
    # output token, every prompt token prefilled once (no sharing)
    assert st.decode_slot_steps == sum(m for _, m in SHAPES)
    assert st.prefill_tokens == sum(n for n, _ in SHAPES)
    assert len(ctx.finished) == len(SHAPES)


def test_traces_count_each_program_once(model):
    eng = _engine(model)
    first = eng.serve(_requests(model[0].vocab_size, SHAPES))
    traced = eng.last_stats.traces
    assert {"step", "chunk_step", "paged_admit", "set_table"} <= set(traced)
    assert set(traced.values()) == {1}
    again = eng.serve(_requests(model[0].vocab_size, SHAPES, seed=1))
    assert eng.last_stats.traces == {}
    assert len(first) == len(again) == len(SHAPES)


def test_phase_seconds_within_iteration_time(model):
    eng = _engine(model)
    ctx = eng.start_session(_requests(model[0].vocab_size, SHAPES))
    while not ctx.sched.idle():
        eng.run_iteration(ctx)
    host_s = ctx.stats.host_s
    assert set(host_s) == PHASES  # no integrity scrub configured
    assert all(v >= 0 for v in host_s.values())
    assert sum(host_s.values()) <= sum(ctx.monitor.times)


def test_timestamps_fall_in_the_client_iterations(model):
    """Drive a session as ``bench/lib/client.py`` does: submit, one
    ``run_iteration``, then observe the slot table, the slots still
    prefilling and the terminal records; each request's admission, first
    token and finish are attributed to the iteration that first shows
    them. A closed loop submits a new request as each one finishes."""
    eng = _engine(model)
    clock = time.perf_counter
    ctx = eng.start_session([])
    todo = _requests(model[0].vocab_size, SHAPES + [(7, 4), (25, 6)])
    for r in todo[:3]:
        eng.submit_to_session(ctx, r)
    todo = todo[3:]
    seen = {}  # rid -> {"admit"|"first"|"finish": (t0, t1)}
    n_fin = 0
    while not ctx.sched.idle():
        t0 = clock()
        eng.run_iteration(ctx)
        t1 = clock()
        for s, req in enumerate(ctx.sched.slot_req):
            if req is None:
                continue
            ev = seen.setdefault(req.rid, {})
            ev.setdefault("admit", (t0, t1))
            if s not in ctx.prefilling:
                ev.setdefault("first", (t0, t1))
        for fin in ctx.finished[n_fin:]:
            ev = seen.setdefault(fin.rid, {})
            for k in ("admit", "first", "finish"):
                ev.setdefault(k, (t0, t1))
            if todo:
                eng.submit_to_session(ctx, todo.pop(0))
        n_fin = len(ctx.finished)
    assert not todo and len(ctx.finished) == len(SHAPES) + 2
    for fin in ctx.finished:
        assert fin.outcome == "finished"
        assert fin.t_admit <= fin.t_first <= fin.t_finish
        for k, t in (("admit", fin.t_admit), ("first", fin.t_first),
                     ("finish", fin.t_finish)):
            lo, hi = seen[fin.rid][k]
            assert lo <= t <= hi, (fin.rid, k)
