"""One run of one cell: set-up, the measured window, the check.

``run_cell`` builds the weights on the device from the seed, builds the
``Engine`` the cell's mix asks for, warms every program the window will
use, runs the window, reads the device's peak memory, frees the program's
state and runs the correctness check. With ``trace`` it records a
profiler trace of part of the window and reads the per-layer metrics from
it; without, it reads the end-to-end metrics.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench.lib import check, client, trace, traffic
from bench.lib.peaks import peaks
from bench.lib.spec import Spec

SPAN_NAMES = ("submit", "run_iteration", "observe", "wait")
TRACE_START = 0.25  # share of the window before the trace starts
TRACE_SECONDS = 4.0  # longest traced stretch


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run: with its model module
    (``bench/models/``), that module's sizes and the shapes and dtypes of
    the parameter tree the engine served (``ShapeDtypeStruct`` leaves)."""

    cell: str
    records: List[client.Record]
    window: dict
    setup_s: float
    model: ModuleType
    sizes: dict
    served: Any
    slots: int
    chunk: int
    kv_itemsize: int
    trace: Optional[trace.Trace] = None
    calls: Optional[Dict[str, list]] = None
    peaks: Optional[dict] = None

    def judged(self) -> List[client.Record]:
        """The requests the window's latencies are taken over: every one
        due in an open loop's window (drained after it), or every one
        finished inside a closed loop's window."""
        w = self.window
        if "start_counts" in w:
            return [r for r in self.records if r.finish is not None
                    and w["open"] <= r.finish <= w["close"]]
        return [r for r in self.records if w["open"] <= r.due <= w["close"]]


class CompileCounter:
    """Counts programs lowered (compiled or read from the persistent
    cache) while ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.active and name == self.EVENT:
            self.count += 1


class Tracer:
    """Starts the profiler a while into the window and stops it at an
    iteration boundary, recording every decode and chunk call made in
    between (from the session's host-side mirrors, no device read). A
    decode call's lengths are its slots' cache lengths after the append,
    in every layout."""

    def __init__(self, sess: client.Session, seconds: float, out: Path):
        self.sess, self.out = sess, out
        self.start_at = None
        self.stop_after = min(TRACE_SECONDS, 0.5 * seconds)
        self.lead = TRACE_START * seconds
        self.on = False
        self.done = False
        self.calls: Dict[str, list] = {"decode": [], "chunk": []}
        self._step_in_iter = 0
        self._ann = None
        eng, ctx = sess.engine, sess.ctx
        real_step, real_chunk = ctx.step_fn, eng._chunk_step_fn

        def step(params, state):
            if self.on:
                k = self._step_in_iter
                live = [s for s in ctx.sched.active_slots()
                        if s not in ctx.prefilling and ctx.remaining[s] > k]
                self.calls["decode"].append(
                    [ctx.seq_mirror[s] + k + 1 for s in live])
            self._step_in_iter += 1
            return real_step(params, state)

        def chunk(params, state, toks, n_valid, is_first, is_last, *rest):
            if self.on:
                nv, last = np.asarray(n_valid), np.asarray(is_last)
                pairs = []
                for s in np.nonzero(nv)[0]:
                    off = ctx.prefilling[int(s)][1]
                    pairs.append((int(off if last[s] else off - nv[s]),
                                  int(nv[s])))
                self.calls["chunk"].append((pairs, int(last.sum())))
            return real_chunk(params, state, toks, n_valid, is_first,
                              is_last, *rest)

        ctx.step_fn = step
        if real_chunk is not None:  # whole-prompt admission has none
            eng._chunk_step_fn = chunk

    def open(self, t_open: float) -> None:
        self.start_at = t_open + self.lead

    def before_iteration(self) -> None:
        self._step_in_iter = 0
        now = self.sess.clock()
        if self.start_at is None or self.done:
            return
        if not self.on and now >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host annotations only
            jax.profiler.start_trace(str(self.out), profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self._ann.__enter__()
            self.sess.spans.annotate = True
            self.on, self.started = True, now
        elif self.on and now - self.started >= self.stop_after:
            self.close()

    def close(self) -> None:
        if self.on:
            self.sess.spans.annotate = False
            self._ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def layout(mix: dict) -> str:
    """The engine layout a traffic file asks for: ``paged`` (paged KV
    pool, chunked prefill), ``contiguous`` (contiguous cache, chunked
    prefill) or ``whole`` (contiguous cache, whole-prompt admission).

    Whole-prompt admission compiles once per group of equal prompt
    lengths, so no warm-up covers a mix that admits in the window: a
    whole-prompt file is taken only as a fixed batch, a closed loop of
    at most ``slots`` clients whose first wave is admitted in set-up.
    Raises ``ValueError`` for any other file, before weights are made."""
    eng = mix["engine"]
    if eng["paged"]:
        return "paged"
    if eng.get("prefix_sharing") or "pool_pages" in eng:
        raise ValueError("a non-paged traffic file states prefix_sharing "
                         "false and has no pool_pages")
    if eng["prefill_chunk"] > 0:
        return "contiguous"
    if not (mix["loop"] == "closed" and mix.get("first_wave_in_setup")
            and mix["clients"] <= eng["slots"]):
        raise ValueError(
            "whole-prompt admission (prefill_chunk 0) compiles once per "
            "prompt length, so its traffic must be a fixed batch: a closed "
            "loop with first_wave_in_setup and at most `slots` clients")
    return "whole"


def warm(sess: client.Session, eng_conf: dict, vocab: int) -> None:
    """Run every program the window uses once, with one request, then the
    harvest scatter at every count of slots that can retire at once.

    Chunked layouts: a request of three chunk waves whose decode crosses a
    page boundary (chunk step, paged admit, hot-tier snapshot, decode
    step, page-table install, harvest); a contiguous cache has no pages,
    and the chunk stands in for the page in the request's length.
    Whole-prompt: a request whose decode crosses from the hot tier into
    the cold (its own prefill and admit, decode step, harvest); the
    window's batch is admitted in set-up (``layout``)."""
    import jax.numpy as jnp

    eng = sess.engine
    c, hot = eng_conf["prefill_chunk"], eng_conf["hot_cap"]
    if c:
        ps = eng._page_size if eng_conf["paged"] else c
        n = hot + ps - 2
        while n <= 2 * c:
            n += ps
    else:
        n = max(hot - 2, 1)
    rng = np.random.default_rng(0)
    plan = traffic.Planned(index=-1, max_new=8,
                           prompt=rng.integers(0, vocab, n, dtype=np.int32))
    sess.submit(plan, sess.clock())
    while not sess.idle():
        sess.iterate()
    alloc = sess.ctx.state.allocated
    for k in range(1, eng_conf["slots"] + 1):
        idx = jnp.asarray(list(range(k)), jnp.int32)  # as the harvest makes it
        alloc.at[idx].set(False).block_until_ready()
    sess.records.clear()


def cache_size(engine, eng_conf: dict, pool=None) -> str:
    """The KV cache for the logs: a paged engine's pool (its pages in use,
    given the session's ``pool``), else the contiguous cache's tiers."""
    if eng_conf["paged"]:
        if pool is not None:
            return f"pool pages in use {pool.used()}"
        return (f"pool pages {eng_conf['pool_pages']} of "
                f"{engine._page_size} tokens")
    slots, n, hot = eng_conf["slots"], eng_conf["max_len"], eng_conf["hot_cap"]
    return (f"contiguous cache {slots} slots x {n} tokens (hot {hot}, "
            f"cold {n - hot})")


def process_seconds() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        import os

        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / ticks
        return time.clock_gettime(time.CLOCK_BOOTTIME) - start
    except (OSError, ValueError, IndexError, AttributeError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def run_cell(spec: Spec, workload: str, seed: int, seconds: float,
             trace_on: bool, *, require_chip: bool = True,
             control: Optional[str] = None,
             fault: Optional[Callable] = None,
             work_dir: Optional[Path] = None, log=sys.stderr) -> dict:
    """One run of ``workload``; returns the result line as a dict.

    ``control`` switches on one of the program's lower-precision paths
    (``bench.lib.model.CONTROLS``): the check's control.
    ``fault(engine, session)`` breaks the timed path before the window
    (the check's fault tests).
    Neither is used by the benchmark's own runs.
    """
    t_setup0 = time.perf_counter() - process_seconds()
    cell = spec.workload(workload)
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform} device(s)")
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    eng_conf = mix["engine"]
    paged = layout(mix) == "paged"
    model = spec.model(conf)
    sizes = model.sizes(conf)
    cfg = model.model_config(conf, control)
    device = devs[0]
    work_dir = Path(work_dir or spec.root / ".bench_work")

    from repro.serving.engine import Engine

    engine = Engine(
        cfg, model.program_params(seed, conf), hot_cap=eng_conf["hot_cap"],
        max_len=eng_conf["max_len"], slots=eng_conf["slots"],
        sync_every=eng_conf["sync_every"],
        prefill_chunk=eng_conf["prefill_chunk"], paged=paged,
        n_pages=eng_conf["pool_pages"] if paged else None,
        prefix_sharing=eng_conf["prefix_sharing"], seed=0)
    gc.collect()
    print(f"memory after the model: {device.memory_stats()}; "
          f"{cache_size(engine, eng_conf)}", file=log)
    sess = client.Session(engine, eng_conf)
    if eng_conf["prefill_chunk"] and not sess.ctx.chunked:
        raise ValueError(f"{cfg.name} cannot chunk prefill into its cache: "
                         "its traffic file states prefill_chunk 0")
    compiles = CompileCounter()
    warm(sess, eng_conf, sizes["vocab"])
    mix_plan = traffic.Mix(mix, seed, seconds, sizes["vocab"])
    closed = mix["loop"] == "closed"
    if closed and mix.get("first_wave_in_setup"):
        client.fill_first_wave(sess, mix_plan, mix["clients"])
    tracer = None
    if trace_on:
        tdir = work_dir / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        tracer = Tracer(sess, seconds, tdir)
        sess.iteration_hook = tracer.before_iteration
    if fault is not None:
        fault(engine, sess)
    compiles.active = True
    setup_s = time.perf_counter() - t_setup0
    if tracer is not None:
        tracer.open(sess.clock())
    if closed:
        window = client.run_closed(sess, mix_plan, mix["clients"], seconds,
                                   mix.get("first_wave_in_setup", False))
    else:
        window = client.run_open(sess, mix_plan, seconds)
    compiles.active = False
    calls = None
    if tracer is not None:
        tracer.close()
        calls, tracer = tracer.calls, None
    jax.block_until_ready(sess.ctx.state)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    sess.read_in_flight()
    records = [r for r in sess.records.values() if r.due <= window["close"]]
    served = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          engine.params)
    run = Run(cell=workload, records=records, window=window, setup_s=setup_s,
              model=model, sizes=sizes, served=served,
              slots=eng_conf["slots"], chunk=eng_conf["prefill_chunk"],
              kv_itemsize=1 if cfg.bitnet.kv_fp8 else 2, calls=calls)
    print(f"window: {window['close'] - window['open']:.3f} s, requests "
          f"{len(records)}, preemptions {sess.ctx.stats.preemptions}, "
          f"{cache_size(engine, eng_conf, sess.ctx.pool)}, compiles in "
          f"window {compiles.count}, peak bytes {peak}", file=log)
    # the program's state goes before the trace is read and the
    # reference runs: the device's peak has been taken
    del sess, engine
    gc.collect()

    result_device = {"platform": device.platform, "kind": device.device_kind,
                     "count": cell["chips"], "memory_peak_bytes": int(peak)}
    breakdown = None
    if trace_on:
        run.trace = trace.reduce(trace.find(work_dir / "trace"), SPAN_NAMES)
        run.peaks = (peaks(device.device_kind) if device.platform == "tpu"
                     else None)
        result_device["busy_s"] = run.trace.busy_s
        result_device["window_s"] = run.trace.window_s
        breakdown = {"device_ops": run.trace.top_ops(),
                     "idle_gaps": run.trace.top_gaps()}
    metrics, missing = {}, []
    for m in (spec.per_layer(workload) if trace_on
              else spec.end_to_end(workload)):
        value = (setup_s if m["name"] == "setup_s"
                 else spec.reader(m["name"])(run))
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        else:
            missing.append(m["name"])
    if missing:
        print(f"metrics with nothing to read: {missing}", file=log)

    finished = [r for r in records if r.outcome is not None]
    failed = sum(r.outcome != "finished" for r in finished)
    # the requests the window finished, and those it was serving at its close
    picked = check.sample([r for r in run.judged() if r.outcome]
                          + [r for r in records if r.outcome is None], seed)
    limits = spec.limits(workload)
    t_check = time.perf_counter()
    compared = (check.logit_gap(conf, seed, picked)
                if picked else {"logit_gap": float("inf")})
    t_check = time.perf_counter() - t_check
    gap = compared["logit_gap"]
    limit = limits.get("logit_gap")
    correct = bool(picked) and limit is not None and gap <= limit
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {"logit_gap": {"value": gap, "limit": limit}}
    print(f"sampled {compared.get('tokens', 0)} served tokens of "
          f"{compared.get('requests', 0)} requests, checked in "
          f"{t_check:.1f} s", file=log)
    return out
