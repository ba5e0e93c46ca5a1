"""The client: drives one ``Engine`` session through its public session API
(``start_session``, ``submit_to_session``, ``run_iteration``) and times
each request on the host clock.

An open loop submits every request at its due time from a generator
thread, whatever the engine is doing; the serving loop hands what has
arrived to the session before each iteration. After the window it stops
sending and drains what is in flight. A closed loop gives each client
its next request as soon as its last one finished, and stops at the end
of the window.

The engine records no timestamps of its own yet, so the client reads a
few host-side fields of the live session after each iteration (none of
them costs a device read): the slot table (``ctx.sched.slot_req``) for
admission, the set of slots still prefilling (``ctx.prefilling``) for
the first token, the session's terminal records (``ctx.finished``), and
the budget mirror (``ctx.remaining``) for the tokens of requests still
in flight at the window's edges.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib.traffic import Mix, Planned


@dataclasses.dataclass
class Record:
    """One request as the client saw it (host clock, seconds)."""

    index: int
    prompt_len: int
    max_new: int
    due: float = 0.0
    submit: Optional[float] = None
    admit: Optional[float] = None
    first: Optional[float] = None
    finish: Optional[float] = None
    outcome: Optional[str] = None
    prompt: Optional[np.ndarray] = None
    tokens: Optional[np.ndarray] = None
    # tokens served so far: ``tokens`` once finished, else what a request
    # still in flight at the window's close had been given (for the check)
    served: Optional[np.ndarray] = None


class Spans:
    """Host spans of the client's own calls into the engine, kept in
    memory and, while a trace runs, written into it as annotations."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.spans: List[tuple] = []  # (name, start, end)
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = self.clock()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.append((name, t0, self.clock()))


class Session:
    """One engine session and the records of every request it was given."""

    def __init__(self, engine, engine_conf: dict,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.clock = clock
        self.spans = Spans(clock)
        self.ctx = engine.start_session(
            [], slots=engine_conf["slots"],
            sync_every=engine_conf["sync_every"])
        self.records: Dict[int, Record] = {}
        self._seen_finished = 0
        self.on_finish: Optional[Callable[[Record, float], None]] = None
        self.iteration_hook: Optional[Callable[[], None]] = None

    # -- requests -------------------------------------------------------

    def submit(self, p: Planned, due: float) -> None:
        from repro.serving.scheduler import Request

        rec = Record(index=p.index, prompt_len=len(p.prompt),
                     max_new=p.max_new, due=due, prompt=p.prompt)
        self.records[p.index] = rec
        with self.spans("submit"):
            self.engine.submit_to_session(self.ctx, Request(
                rid=p.index, tokens=p.prompt.copy(), max_new_tokens=p.max_new))
        rec.submit = self.clock()

    def idle(self) -> bool:
        return self.ctx.sched.idle()

    def iterate(self) -> None:
        """One ``run_iteration``, then what the host can see of it."""
        if self.iteration_hook is not None:
            self.iteration_hook()
        t0 = self.clock()
        with self.spans("run_iteration"):
            self.engine.run_iteration(self.ctx)
        t1 = self.clock()
        with self.spans("observe"):
            self._observe(t0, t1)

    def _observe(self, started: float, ended: float) -> None:
        ctx = self.ctx
        for s, req in enumerate(ctx.sched.slot_req):
            if req is None:
                continue
            rec = self.records[req.rid]
            if rec.admit is None:
                rec.admit = started
            if rec.first is None and s not in ctx.prefilling:
                rec.first = ended
        new = ctx.finished[self._seen_finished:]
        self._seen_finished = len(ctx.finished)
        for fin in new:
            rec = self.records[fin.rid]
            rec.admit = rec.admit if rec.admit is not None else started
            rec.first = rec.first if rec.first is not None else ended
            rec.finish = ended
            rec.outcome = fin.outcome
            rec.tokens = rec.served = np.asarray(fin.tokens, np.int32)
            if self.on_finish is not None:
                self.on_finish(rec, ended)

    def read_in_flight(self) -> None:
        """Once the window has closed: give each request that holds a slot
        and is past its prefill the tokens served to it so far, read from
        the session's device state (a preempted request's earlier tokens,
        folded into its prompt, come first)."""
        ctx = self.ctx
        out = np.asarray(ctx.state.out)
        n_gen = np.asarray(ctx.state.n_gen)
        for s, req in enumerate(ctx.sched.slot_req):
            if req is None or s in ctx.prefilling:
                continue
            rec = self.records[req.rid]
            if rec.finish is None:
                prior = np.asarray(req.tokens, np.int32)[rec.prompt_len:]
                rec.served = np.concatenate([prior, out[s, :n_gen[s]]])

    # -- counts at the window's edges ------------------------------------

    def produced(self) -> Dict[str, int]:
        """Output tokens emitted and prompt tokens prefilled so far, by
        every request of the session (finished or in flight)."""
        ctx = self.ctx
        out = prompt = 0
        live = set()
        for s, req in enumerate(ctx.sched.slot_req):
            if req is None:
                continue
            live.add(req.rid)
            rec = self.records[req.rid]
            folded = req.prompt_len - rec.prompt_len
            if s in ctx.prefilling:
                prompt += ctx.prefilling[s][1]
                out += folded
            else:
                prompt += req.prompt_len
                out += folded + req.max_new_tokens - ctx.remaining[s]
        for rec in self.records.values():
            if rec.finish is not None and rec.index not in live:
                out += len(rec.tokens)
                prompt += rec.prompt_len
        return {"output": out, "prompt": prompt}


def run_open(sess: Session, mix: Mix, seconds: float) -> dict:
    """Open loop: a generator thread hands each request over at its due
    time; the serving loop submits what has arrived before each
    iteration. Returns the window's edges and the generator's lateness."""
    arrived: "queue.SimpleQueue" = queue.SimpleQueue()
    plan = mix.scheduled()
    t_open = sess.clock()
    lag: Dict[int, float] = {}

    def generate():
        for p in plan:
            due = t_open + p.due
            wait = due - sess.clock()
            if wait > 0:
                time.sleep(wait)
            lag[p.index] = sess.clock() - due
            arrived.put((p, due))
        arrived.put(None)

    gen = threading.Thread(target=generate, name="bench-generator",
                           daemon=True)
    gen.start()
    done = False
    try:
        while True:
            while True:
                try:
                    item = arrived.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    done = True
                else:
                    sess.submit(*item)
            if not sess.idle():
                sess.iterate()
                continue
            if done:
                break
            with sess.spans("wait"):
                item = arrived.get()
            if item is None:
                done = True
            else:
                sess.submit(*item)
    finally:
        gen.join(timeout=seconds + 60)
    return {"open": t_open, "close": t_open + seconds, "end": sess.clock(),
            "gen_lag": lag}


def fill_first_wave(sess: Session, mix: Mix, clients: int) -> None:
    """Set-up of a closed loop: one request per client, prefilled until
    every slot decodes, so the window opens in steady decode."""
    for _ in range(clients):
        sess.submit(mix.next_request(), sess.clock())
    while not sess.idle() and (sess.ctx.prefilling or sess.ctx.sched.queue):
        sess.iterate()


def run_closed(sess: Session, mix: Mix, clients: int, seconds: float,
               first_wave: bool) -> dict:
    """Closed loop: each finished request is followed at once by its
    client's next. The window closes with the first iteration that ends
    past ``seconds``; counts are taken at both edges."""
    pending: List[float] = []
    sess.on_finish = lambda rec, t: pending.append(t)
    if not first_wave:
        t = sess.clock()
        pending.extend([t] * clients)
    t_open = sess.clock()
    start = sess.produced()
    while True:
        for due in pending:
            sess.submit(mix.next_request(), due)
        pending.clear()
        sess.iterate()
        if sess.clock() - t_open >= seconds:
            break
    t_close = sess.clock()
    return {"open": t_open, "close": t_close, "end": t_close,
            "start_counts": start, "end_counts": sess.produced(),
            "gen_lag": {}}
