"""Serving launcher CLI — batched weight-reload-free generation.

Single engine:

  PYTHONPATH=src python -m repro.launch.serve --arch falcon3-1b --smoke \
      --batch 4 --prompt-len 16 --max-new 32 [--hot-cap 32] [--kv-fp8]

Fault-tolerant fleet (data-parallel router over N replicas, optionally
under seeded replica-kill chaos — see docs/serving.md, "Multi-replica
serving"):

  PYTHONPATH=src python -m repro.launch.serve --arch falcon3-1b --smoke \
      --replicas 2 --batch 8 --max-new 16 --kill-rate 0.05 --chaos-seed 0
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.serving.engine import Engine


def _serve_fleet(cfg, params, args) -> None:
    from repro.launch.mesh import replica_devices
    from repro.serving import (FleetChaosConfig, FleetChaosInjector,
                               LocalTransport, Replica, Router)
    from repro.serving.scheduler import Request

    max_len = args.prompt_len + args.max_new + 8
    # paged serving needs a non-empty cold tier below the hot window
    hot_cap = min(args.hot_cap, max_len // 2)
    replicas = []
    for i in range(args.replicas):
        dev = replica_devices(i, args.replicas)[0]
        # sync_every=2 keeps router ticks fine-grained: health checks,
        # chaos injection and migration all happen at tick boundaries
        eng = Engine(cfg, params, hot_cap=hot_cap, max_len=max_len,
                     slots=max(2, args.batch // args.replicas),
                     prefill_chunk=8, paged=True, sync_every=2, device=dev)
        replicas.append(Replica(f"r{i}", eng))
        print(f"replica r{i}: device {dev}")
    rng = np.random.RandomState(1)
    reqs = [
        Request(rid=i,
                tokens=rng.randint(0, cfg.vocab_size,
                                   size=(args.prompt_len,)).astype(np.int32),
                max_new_tokens=args.max_new)
        for i in range(args.batch)
    ]
    router = Router(replicas, seed=args.chaos_seed,
                    transport=LocalTransport())
    chaos = None
    if args.kill_rate > 0.0 or args.stall_rate > 0.0:
        chaos = FleetChaosInjector(FleetChaosConfig(
            seed=args.chaos_seed, kill_rate=args.kill_rate,
            stall_rate=args.stall_rate, max_kills=args.replicas - 1))
    t0 = time.perf_counter()
    fin = router.serve(reqs, on_tick=chaos.on_tick if chaos else None)
    dt = time.perf_counter() - t0
    toks = sum(len(f.tokens) for f in fin)
    st = router.stats
    bad = sorted((f.rid, f.outcome) for f in fin if f.outcome != "finished")
    print(f"fleet served {len(fin)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s) across {args.replicas} replicas")
    print(f"outcomes: {bad if bad else 'all finished'}")
    print(f"failover: kills={len(chaos.kills) if chaos else 0} "
          f"cold_migrations={st.cold_migrations} "
          f"warm_migrations={st.warm_migrations} "
          f"handoffs_imported={st.handoffs_imported} "
          f"retries={st.retries} restarts={st.restarts} ticks={st.ticks}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--hot-cap", type=int, default=32)
    ap.add_argument("--kv-fp8", action="store_true")
    ap.add_argument("--codec", default="pack2", choices=["pack2", "pack243"])
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a fault-tolerant router over N "
                         "data-parallel engine replicas")
    ap.add_argument("--kill-rate", type=float, default=0.0,
                    help="fleet chaos: per-tick replica-kill probability "
                         "(needs --replicas >= 2)")
    ap.add_argument("--stall-rate", type=float, default=0.0,
                    help="fleet chaos: per-tick replica-stall probability")
    ap.add_argument("--chaos-seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(
        cfg,
        bitnet=dataclasses.replace(cfg.bitnet, kv_fp8=args.kv_fp8, codec=args.codec),
    )
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if args.replicas > 1:
        _serve_fleet(cfg, params, args)
        return
    eng = Engine(
        cfg, params, hot_cap=args.hot_cap,
        max_len=args.prompt_len + args.max_new + 8,
    )
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    res = eng.generate(prompts, max_new_tokens=args.max_new)
    toks = res.steps * args.batch
    print(f"generated {toks} tokens in {res.wall_s:.2f}s "
          f"({toks/res.wall_s:.1f} tok/s on this host)")
    print(f"external-DRAM reduction {100*res.external_reduction:.1f}% "
          f"(hot_cap={args.hot_cap}); weight reloads: {eng.weight_loads}")


if __name__ == "__main__":
    main()
