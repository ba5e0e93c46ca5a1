"""Bring-up check: the serving path on a TPU, at falcon3-1b's published widths.

Builds falcon3-1b as ``launch/serve.py`` does (18 layers, d 2048, GQA 8/4 x
256, FFN 8192, vocab 131072; random bf16 weights from ``--seed``), packs it,
and serves a handful of mixed-length requests through ``Engine.serve`` with
chunked prefill and a paged cold tier. It checks that every request
finished, that no weight was reloaded, that the chunk step compiled once,
and that the compiled steps hold the Pallas kernels. Then it compares one
prompt's logits, after prefill and after one decode step, between the
packed Pallas path and the packed XLA path.

  python chip_smoke.py [--seed N]
  python chip_smoke.py --four-chips [--seed N]

``--four-chips`` runs only the data-parallel phase: four one-chip replicas
behind ``Router`` against one ``Engine`` serving the same requests; greedy
tokens must match per request and the replicas must sit on four devices.

Without a TPU it exits non-zero before serving. The last line of standard
output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import qops  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import Replica, Router  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.scheduler import Request  # noqa: E402

ARCH = "falcon3-1b"
HOT_CAP = 32  # the paper's hot tier
MAX_LEN = 512
SLOTS = 4  # fewer slots than requests: admission into freed slots runs
CHUNK = 128
N_REQUESTS = 8
# Pallas vs XLA logits, as max |diff| / max |XLA logit|. Both paths run
# the same integer matmuls on the same packed weights and differ in where
# bf16 activations round (attention and fusion boundaries), which can move
# an int8 activation code by one step. On the CPU, with the kernels
# interpreted at d 256, this measured 0.013-0.026 in bf16 over 2-18 layers
# and 3e-7 in f32. A broken kernel (wrong trit order, missing RoPE, a
# skipped block) moves logits by about their own size.
LOGIT_TOL = 0.1


def check(ok: bool, what: str) -> None:
    """Fail the run (a non-zero exit) unless ``ok``."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def model(seed: int):
    cfg = get_config(ARCH)
    params = T.init_params(jax.random.PRNGKey(seed), cfg, dtype=jnp.bfloat16)
    return cfg, params


def requests(cfg, seed: int) -> list:
    """Mixed-length prompts (tens to several hundred tokens), a few dozen
    new tokens each."""
    rng = np.random.RandomState(seed)
    lens = rng.randint(20, 420, size=N_REQUESTS)
    news = rng.randint(16, 49, size=N_REQUESTS)
    return [
        Request(rid=i,
                tokens=rng.randint(0, cfg.vocab_size, size=(int(n),)
                                   ).astype(np.int32),
                max_new_tokens=int(m))
        for i, (n, m) in enumerate(zip(lens, news))
    ]


def engine(cfg, params, device=None) -> Engine:
    return Engine(cfg, params, hot_cap=HOT_CAP, max_len=MAX_LEN, slots=SLOTS,
                  prefill_chunk=CHUNK, paged=True, device=device)


def check_finished(fins, reqs) -> None:
    check(sorted(f.rid for f in fins) == [r.rid for r in reqs],
          "one terminal record per request")
    bad = [(f.rid, f.outcome) for f in fins if f.outcome != "finished"]
    check(not bad, f"every outcome finished: {bad}")
    want = {r.rid: r.max_new_tokens for r in reqs}
    check(all(len(f.tokens) == want[f.rid] for f in fins),
          "every request got its max_new_tokens")


def step_kernels(eng: Engine) -> dict:
    """Whether the lowered decode and chunk steps call the Pallas kernels
    (``tpu_custom_call``), traced from abstract arguments."""
    key = eng.key  # _init_state splits the engine key; keep it concrete
    state = jax.eval_shape(lambda: eng._init_state(SLOTS, MAX_LEN))
    eng.key = key
    (decode,) = eng._step_fns.values()
    i32 = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    b = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_)
    chunk_args = (eng.params, state,
                  jax.ShapeDtypeStruct((SLOTS, CHUNK), jnp.int32), i32, b, b,
                  i32, jax.random.PRNGKey(0))
    return {
        "decode": "tpu_custom_call" in decode.lower(eng.params, state).as_text(),
        "chunk": "tpu_custom_call" in eng._chunk_step_fn.lower(
            *chunk_args).as_text(),
    }


def logit_diff(cfg, packed, tokens) -> dict:
    """Relative max |Pallas - XLA| logit difference after prefilling
    ``tokens`` (1, S) and after one decode step on the XLA path's greedy
    token, both impls on the same packed weights."""
    got = {}
    nxt = None
    for impl in ("xla", "pallas"):
        c = dataclasses.replace(
            cfg, bitnet=dataclasses.replace(cfg.bitnet, impl=impl))
        check(qops.resolve_impl(c) == impl, f"resolve_impl honours {impl}")
        logits, cache = jax.jit(functools.partial(
            T.prefill, cfg=c, hot_cap=HOT_CAP, max_len=MAX_LEN))(
                packed, batch={"tokens": tokens})
        if nxt is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        step, _ = jax.jit(functools.partial(T.decode_step, cfg=c))(
            packed, tokens=nxt, cache=cache)
        got[impl] = {"prefill": np.asarray(logits, np.float32),
                     "decode": np.asarray(step, np.float32)}
    diffs = {}
    for name in ("prefill", "decode"):
        p, x = got["pallas"][name], got["xla"][name]
        check(bool(np.isfinite(p).all() and np.isfinite(x).all()),
              f"{name} logits finite")
        diffs[name] = float(np.abs(p - x).max() / np.abs(x).max())
    return diffs


def device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak(dev, tree=None) -> int:
    """The device's peak bytes in use so far, once ``tree`` is computed."""
    jax.block_until_ready(tree)
    return dev.memory_stats()["peak_bytes_in_use"]


def one_chip(seed: int) -> dict:
    t_start = time.perf_counter()
    dev = jax.devices()[0]
    peaks = {}
    cfg, params = model(seed)
    peaks["init"] = peak(dev, params)
    check(qops.resolve_impl(cfg) == "pallas", "auto impl resolves to pallas")
    eng = engine(cfg, params)
    del params  # only the packed form stays on the chip
    peaks["pack"] = peak(dev, eng.params)
    print(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads}x{cfg.resolved_head_dim} "
          f"ffn={cfg.d_ff} vocab={cfg.vocab_size}")
    print(f"cache dtype: {jnp.dtype(eng._cache_dtype()).name}")

    reqs = requests(cfg, seed)
    print("prompt lengths:", [r.prompt_len for r in reqs],
          "new tokens:", [r.max_new_tokens for r in reqs])
    t0 = time.perf_counter()
    fins = eng.serve(reqs)
    serve_s = time.perf_counter() - t0
    peaks["serve"] = peak(dev)
    check_finished(fins, reqs)
    print(f"served {len(fins)} requests through Engine.serve: all finished")
    check(eng.weight_loads == 0, f"weight_loads == 0 ({eng.weight_loads})")
    compiles = eng._chunk_step_fn._cache_size()
    check(compiles == 1, f"chunk step compiled once ({compiles})")
    print(f"weight_loads: {eng.weight_loads}; chunk-step compiles: {compiles}")
    kernels = step_kernels(eng)
    check(all(kernels.values()), f"tpu_custom_call in the steps: {kernels}")
    print(f"tpu_custom_call in lowered steps: {kernels}")

    diffs = logit_diff(cfg, eng.params,
                       jnp.asarray(reqs[0].tokens, jnp.int32)[None])
    print(f"pallas vs xla logits, max|diff|/max|xla|: {diffs} "
          f"(tolerance {LOGIT_TOL})")
    check(all(d <= LOGIT_TOL for d in diffs.values()),
          f"logit difference within {LOGIT_TOL}: {diffs}")
    peaks["compare"] = peak(dev)
    print(f"peak_bytes_in_use after each phase: {peaks}")
    print(f"seconds: serve {serve_s:.1f}, "
          f"total {time.perf_counter() - t_start:.1f} (compiles included)")
    return device_line(jax.devices())


def four_chips(seed: int) -> dict:
    devs = jax.devices()
    check(len(devs) >= 4, f"four devices ({len(devs)})")
    devs = devs[:4]
    cfg, params = model(seed)
    single = engine(cfg, params, device=devs[0])
    replicas = [Replica(f"r{i}", engine(cfg, params, device=d))
                for i, d in enumerate(devs)]
    del params

    t0 = time.perf_counter()
    ref = {f.rid: f.tokens for f in single.serve(requests(cfg, seed))}
    single_s = time.perf_counter() - t0

    state_devices: dict = {}

    def on_tick(router):
        for name, rep in router.replicas.items():
            if rep.ctx is not None:
                for leaf in jax.tree.leaves(rep.ctx.state):
                    state_devices.setdefault(name, set()).update(
                        leaf.devices())

    reqs = requests(cfg, seed)
    router = Router(replicas, seed=seed)
    t0 = time.perf_counter()
    fins = router.serve(reqs, on_tick=on_tick)
    fleet_s = time.perf_counter() - t0
    check_finished(fins, reqs)

    for rep in replicas:
        placed = set().union(*(leaf.devices() for leaf in
                               jax.tree.leaves(rep.engine.params)))
        check(placed == {rep.engine.device},
              f"{rep.name} params on its own device: {placed}")
        check(state_devices.get(rep.name, set()) <= {rep.engine.device},
              f"{rep.name} state on its own device: "
              f"{state_devices.get(rep.name)}")
    distinct = {rep.engine.device for rep in replicas}
    check(len(distinct) == 4, f"four distinct replica devices: {distinct}")
    print("replica devices:",
          {rep.name: str(rep.engine.device) for rep in replicas})
    print("replicas that held live state:", sorted(state_devices))
    same = {f.rid: bool(np.array_equal(f.tokens, ref[f.rid])) for f in fins}
    check(all(same.values()), f"router tokens == single-engine tokens: {same}")
    print(f"greedy tokens identical to the single engine for all "
          f"{len(same)} requests")
    st = router.stats
    print(f"router: ticks={st.ticks} retries={st.retries} "
          f"cold_migrations={st.cold_migrations} "
          f"warm_migrations={st.warm_migrations}")
    print("peak_bytes_in_use per device:",
          {str(d): peak(d) for d in devs})
    print(f"seconds: single engine {single_s:.1f}, four replicas "
          f"{fleet_s:.1f} (compiles included)")
    return device_line(devs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica Router phase")
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {devs[0].platform}")
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    print(f"jax {jax.__version__}")
    print(f"compile cache: {use_compile_cache()}")
    dev = four_chips(args.seed) if args.four_chips else one_chip(args.seed)
    # the dry-run module rewrites XLA_FLAGS when imported
    check("repro.launch.dryrun" not in sys.modules,
          "the chip path does not import launch/dryrun.py")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
