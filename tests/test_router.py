"""Fault-tolerant multi-replica serving (ISSUE 9 tentpole).

Layers:

  * **bit-exact failover** — under seeded replica kills, stalls and
    handoff corruption (the three fixed CI seeds), every request reaches
    exactly one terminal outcome and greedy outputs are bit-identical to
    a faultless single-engine run, with the fleet invariant checker
    green after every router tick;
  * **lifecycle-stage kills** — a replica dies while its requests are
    queued, mid-prefill, mid-decode, and mid-migration (double kill);
  * **migration mechanics** — warm drain ships checksummed fp8 KV
    payloads that seed the survivor's prefix cache (prefix reuse > 0);
    corruption is detected (``HandoffError``) and degrades to cold
    recompute, never to wrong tokens;
  * **slot-state serialization** — export → import round-trips
    bit-identically for tiered and paged layouts; the fp8 wire payload
    is 4x smaller than the f32 wire form (and 2x smaller than native
    bf16); checksum mismatch raises the typed error;
  * **control plane** — least-loaded placement, deterministic
    backoff/retry reconciliation, heartbeat health checks on an
    injected clock, restart through ``run_with_recovery`` with injected
    restart failures, the kill+cancel same-tick race, and
    ``PreemptionGuard`` graceful drain (including the signal-handler
    path, triggered manually);
  * the fleet invariant checker itself is **falsifiable** — hand-built
    violations raise.
"""

import signal
from types import SimpleNamespace

import jax
import numpy as np
import pytest

import repro.core.kv_cache as kvc
from repro.configs import get_smoke_config
from repro.core.kv_cache import HandoffError
from repro.distributed.fault import (FaultInjector, InjectedFault,
                                     PreemptionGuard)
from repro.models import transformer as T
from repro.serving import (Engine, FleetChaosConfig, FleetChaosInjector,
                           InvariantViolation, LocalTransport, Replica,
                           ReplicaDead, Request, Router,
                           check_fleet_invariants)

CI_SEEDS = [0, 1, 2]
HOT, ML, PS = 4, 64, 8


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("falcon3-1b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("sync_every", 2)
    return Engine(cfg, params, hot_cap=HOT, max_len=ML, prefill_chunk=4,
                  paged=True, page_size=PS, **kw)


def _prompt(seed, n, vocab):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, vocab), np.int32
    )


def _reqs(cfg, n=5, base_rid=0, budget=10):
    return [
        Request(rid=base_rid + i, tokens=_prompt(i, 6 + i, cfg.vocab_size),
                max_new_tokens=budget)
        for i in range(n)
    ]


def _fleet(cfg, params, n=2, **router_kw):
    reps = [Replica(f"r{i}", _engine(cfg, params)) for i in range(n)]
    return Router(reps, **router_kw), reps


@pytest.fixture(scope="module")
def reference(setup):
    """Faultless single-engine terminal tokens, keyed by rid offset."""
    cfg, params = setup
    fins = _engine(cfg, params).serve(_reqs(cfg))
    return {f.rid: f.tokens for f in fins}


def _assert_bit_exact(fins, reference, base_rid=0):
    assert len(fins) == len(reference)
    for f in fins:
        assert f.outcome == "finished", (f.rid, f.outcome)
        np.testing.assert_array_equal(f.tokens, reference[f.rid - base_rid])


# ---------------------------------------------------------------------------
# bit-exact failover under seeded chaos (the CI smoke: 3 fixed seeds)
# ---------------------------------------------------------------------------


def _tick_clock(step=0.005):
    """Deterministic clock: advances a fixed amount per READ, so backoff
    windows are measured in control-flow events, not wall time — two
    identical runs see identical clocks regardless of jit compilation."""
    state = {"t": 0.0}

    def clock():
        state["t"] += step
        return state["t"]

    return clock


@pytest.mark.parametrize("seed", CI_SEEDS)
def test_kill_and_migrate_bit_exact(setup, reference, seed):
    cfg, params = setup
    rid0 = 100 * (seed + 1)

    def run():
        router, _ = _fleet(cfg, params, seed=seed, clock=_tick_clock(),
                           sleep=lambda s: None, straggler_drain=False)
        chaos = FleetChaosInjector(
            FleetChaosConfig(seed=seed, kill_rate=0.3, max_kills=2))
        fins = router.serve(_reqs(cfg, base_rid=rid0),
                            on_tick=chaos.on_tick)
        return router, chaos, fins

    router, chaos, fins = run()
    _assert_bit_exact(fins, reference, base_rid=rid0)
    assert chaos.kills, "seeded schedule must actually kill"
    assert router.stats.cold_migrations > 0
    assert router.stats.restarts == len(chaos.kills)
    # determinism: same seed → same injection points, same counters,
    # same tokens (the injected clock removes wall-time influence)
    router2, chaos2, fins2 = run()
    assert chaos2.kills == chaos.kills
    assert router2.stats.cold_migrations == router.stats.cold_migrations
    assert router2.stats.ticks == router.stats.ticks
    for a, b in zip(fins, fins2):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_warm_migration_reuses_prefix(setup, reference):
    """A stall flags the replica as a straggler; the router drains it
    with KV handoffs — at least one survivor admission rides the
    imported prefix instead of recomputing from scratch."""
    cfg, params = setup
    router, _ = _fleet(cfg, params, seed=0)
    chaos = FleetChaosInjector(
        FleetChaosConfig(seed=0, stall_rate=0.25, stall_seconds=0.3))
    fins = router.serve(_reqs(cfg, base_rid=300), on_tick=chaos.on_tick)
    _assert_bit_exact(fins, reference, base_rid=300)
    assert chaos.stalls
    assert router.stats.drains >= 1
    assert router.stats.warm_migrations >= 1
    assert router.stats.handoffs_imported >= 1
    assert sum(f.prefix_tokens_reused for f in fins) > 0


def test_corrupt_handoff_detected_falls_back_cold(setup, reference):
    """Every handoff is corrupted in flight: the checksum catches each
    one (typed HandoffError, counted), nothing seeds the receiver, and
    the outputs are STILL bit-exact via cold recompute-from-prefix."""
    cfg, params = setup
    router, _ = _fleet(cfg, params, seed=0)
    chaos = FleetChaosInjector(
        FleetChaosConfig(seed=0, stall_rate=0.25, stall_seconds=0.3,
                         corrupt_rate=1.0))
    fins = router.serve(_reqs(cfg, base_rid=400), on_tick=chaos.on_tick)
    _assert_bit_exact(fins, reference, base_rid=400)
    assert router.stats.warm_migrations >= 1
    assert router.stats.handoff_corruptions == router.stats.warm_migrations
    assert router.stats.handoffs_imported == 0


# ---------------------------------------------------------------------------
# kills at every lifecycle stage
# ---------------------------------------------------------------------------


def _kill_at_tick(router, name, tick):
    def hook(r):
        if r.stats.ticks == tick and not r.replicas[name].dead:
            r.replicas[name].kill()
        check_fleet_invariants(r)
    return hook


@pytest.mark.parametrize("tick", [1, 2, 4])
def test_kill_at_stage(setup, reference, tick):
    """tick 1 kills while victims are queued/mid-prefill (chunked
    admission is still streaming its first chunks), later ticks catch
    mid-decode. All stages recover bit-exactly."""
    cfg, params = setup
    rid0 = 500 + 20 * tick
    router, _ = _fleet(cfg, params, seed=0)
    fins = router.serve(_reqs(cfg, base_rid=rid0),
                        on_tick=_kill_at_tick(router, "r0", tick))
    _assert_bit_exact(fins, reference, base_rid=rid0)
    assert router.stats.replica_failures == 1


def test_kill_mid_migration_double_kill(setup, reference):
    """The target of a migration dies before it finishes the migrated
    work (second kill two ticks after the first): requests migrate
    twice and still finish bit-exactly."""
    cfg, params = setup
    router, _ = _fleet(cfg, params, seed=0, max_restarts=2)

    state = {"killed": 0, "first": None}

    def hook(r):
        t = r.stats.ticks
        if state["killed"] == 0 and t == 1:
            r.replicas["r0"].kill()
            state.update(killed=1, first=t)
        elif state["killed"] == 1 and t == state["first"] + 2:
            r.replicas["r1"].kill()
            state["killed"] = 2
        check_fleet_invariants(r)

    fins = router.serve(_reqs(cfg, base_rid=600), on_tick=hook)
    _assert_bit_exact(fins, reference, base_rid=600)
    assert state["killed"] == 2
    assert router.stats.replica_failures == 2


# ---------------------------------------------------------------------------
# the kill + cancel same-tick race (satellite: migration-boundary cancel)
# ---------------------------------------------------------------------------


def test_kill_and_cancel_same_tick(setup):
    """Cancel lands in the same tick the owning replica dies: the rid
    must get EXACTLY ONE terminal (outcome cancelled) — not resurrect on
    the survivor, not double-terminate — and both replicas' pools must
    reconcile (the fleet checker audits refcounts every tick)."""
    cfg, params = setup
    router, reps = _fleet(cfg, params, seed=0)
    reqs = _reqs(cfg, base_rid=700)
    victim_rid = reqs[0].rid

    fired = {"done": False}

    def hook(r):
        if not fired["done"] and r.stats.ticks == 1:
            owner = r.assigned.get(victim_rid)
            r.cancel(victim_rid)
            if owner is not None:
                r.replicas[owner].kill()
            fired["done"] = True
        check_fleet_invariants(r)

    fins = router.serve(reqs, on_tick=hook)
    assert fired["done"]
    terms = [f for f in fins if f.rid == victim_rid]
    assert len(terms) == 1
    assert terms[0].outcome == "cancelled"
    others = [f for f in fins if f.rid != victim_rid]
    assert all(f.outcome == "finished" for f in others)
    assert len(fins) == len(reqs)
    # pools reconcile to tree-only refs on every live replica
    for rep in reps:
        if rep.ctx is not None and rep.ctx.pool is not None:
            tree = rep.ctx.ptree.tree_pages()
            for p in range(rep.ctx.pool.n_pages):
                held = tree.count(p) if hasattr(tree, "count") else \
                    list(tree).count(p)
                assert int(rep.ctx.pool.refs[p]) == held


def test_cancel_mid_migration_window(setup):
    """Cancel lands while the request sits in the router's pending list
    BETWEEN harvest-from-dead-replica and re-admit-on-survivor: the
    tombstone stops the re-admission."""
    cfg, params = setup
    router, _ = _fleet(cfg, params, seed=0)
    reqs = _reqs(cfg, base_rid=720)
    victim_rid = reqs[1].rid
    state = {"phase": 0}

    def hook(r):
        if state["phase"] == 0 and r.stats.ticks == 1:
            owner = r.assigned.get(victim_rid)
            if owner is not None:
                r.replicas[owner].kill()
                state["phase"] = 1
        elif state["phase"] == 1:
            # the kill was harvested this tick: the rid is back in the
            # router's pending list — cancel it THERE
            assert any(p.req.rid == victim_rid for p in r.pending)
            r.cancel(victim_rid)
            state["phase"] = 2
        check_fleet_invariants(r)

    fins = router.serve(reqs, on_tick=hook)
    assert state["phase"] == 2
    terms = [f for f in fins if f.rid == victim_rid]
    assert len(terms) == 1 and terms[0].outcome == "cancelled"
    assert len(fins) == len(reqs)


def test_fresh_session_forgets_stale_cancels(setup):
    """A cancel mark left behind by a dead session must not shoot down
    an unrelated request in the engine's NEXT session (the rid-reuse
    hazard the start_session clear closes)."""
    cfg, params = setup
    eng = _engine(cfg, params)
    eng.cancel(740)  # stale mark, no such request yet
    fins = eng.serve([Request(rid=740, tokens=_prompt(0, 6, cfg.vocab_size),
                              max_new_tokens=4)])
    assert len(fins) == 1 and fins[0].outcome == "finished"


# ---------------------------------------------------------------------------
# slot-state serialization (satellite: round-trip + size + typed errors)
# ---------------------------------------------------------------------------


def _run_one_slot(cfg, params, **kw):
    """Serve one request partway and return (engine, ctx, slot)."""
    eng = _engine(cfg, params, **kw)
    ctx = eng.start_session(
        [Request(rid=1, tokens=_prompt(3, 14, cfg.vocab_size),
                 max_new_tokens=24)])
    for _ in range(8):
        eng.run_iteration(ctx)
    active = [s for s in ctx.sched.active_slots()
              if s not in ctx.prefilling]
    assert active, "request should be mid-decode"
    return eng, ctx, active[0]


def test_export_import_roundtrip_bit_identical(setup):
    """export → pack → unpack → import on a fresh engine reproduces the
    slot's KV rows bit-for-bit (paged layout, both tiers)."""
    cfg, params = setup
    eng, ctx, s = _run_one_slot(cfg, params)
    states = {k: kvc.export_slot_state(c, s)
              for k, c in ctx.state.cache.items()}
    blob = kvc.pack_slot_state(states, PS)
    back = kvc.unpack_slot_state(blob)
    assert set(back) == set(states)
    for key, st in states.items():
        for name in ("hot_k", "hot_v", "cold_k", "cold_v"):
            np.testing.assert_array_equal(st[name], back[key][name])
        assert back[key]["length"] == st["length"]

    # import into a second engine's fresh session: the written rows
    # must read back identically through its cache stacks
    eng2 = _engine(cfg, params)
    ctx2 = eng2.start_session(
        [Request(rid=2, tokens=_prompt(3, 14, cfg.vocab_size),
                 max_new_tokens=24)])
    for _ in range(8):
        eng2.run_iteration(ctx2)
    s2 = [t for t in ctx2.sched.active_slots() if t not in ctx2.prefilling][0]
    for key in ctx2.state.cache:
        new_cache = kvc.import_slot_state(
            ctx2.state.cache[key], s2, back[key])
        got = kvc.export_slot_state(new_cache, s2)
        for name in ("hot_k", "hot_v", "cold_k", "cold_v"):
            np.testing.assert_array_equal(got[name], states[key][name])


def test_roundtrip_tiered_unpaged_layout(setup):
    """The same serialization works on the contiguous tiered layout
    (no page table): non-paged engines can still export/import."""
    cfg, params = setup
    eng = Engine(cfg, params, hot_cap=HOT, max_len=ML, prefill_chunk=4,
                 slots=2, sync_every=2, paged=False)
    ctx = eng.start_session(
        [Request(rid=1, tokens=_prompt(4, 14, cfg.vocab_size),
                 max_new_tokens=24)])
    for _ in range(8):
        eng.run_iteration(ctx)
    s = [t for t in ctx.sched.active_slots() if t not in ctx.prefilling][0]
    states = {k: kvc.export_slot_state(c, s)
              for k, c in ctx.state.cache.items()}
    blob = kvc.pack_slot_state(states, PS)
    back = kvc.unpack_slot_state(blob)
    for key, st in states.items():
        for name in ("hot_k", "hot_v", "cold_k", "cold_v"):
            np.testing.assert_array_equal(st[name], back[key][name])


def test_fp8_payload_4x_smaller_than_f32_wire(setup):
    """The handoff ships rows in the tier STORAGE dtype: with kv_fp8 on
    that is ONE byte per element — 4x smaller than the f32 wire form a
    dtype-naive serializer would send (numpy upcasts fp8 payloads to
    f32 unless told otherwise, and the default engine cache here IS
    f32), and 2x smaller than a native-bf16 wire form."""
    import dataclasses as dc

    import ml_dtypes

    cfg, params = setup
    cfg8 = dc.replace(cfg, name=f"{cfg.name}-fp8wire",
                      bitnet=dc.replace(cfg.bitnet, kv_fp8=True))
    eng, ctx, s = _run_one_slot(cfg8, params)
    states8 = {k: kvc.export_slot_state(c, s)
               for k, c in ctx.state.cache.items()}
    any8 = next(iter(states8.values()))
    assert any8["hot_k"].dtype.itemsize == 1  # fp8 ships as 1 B/elem
    n8 = len(kvc.pack_slot_state(states8, PS))

    def recast(dtype):
        return {
            k: {n: (np.asarray(v).astype(dtype)
                    if isinstance(v, np.ndarray) else v)
                for n, v in st.items()}
            for k, st in states8.items()
        }

    n16 = len(kvc.pack_slot_state(recast(ml_dtypes.bfloat16), PS))
    n32 = len(kvc.pack_slot_state(recast(np.float32), PS))
    # the array BODIES scale exactly with itemsize; framing (magic, key
    # names, dtype strings, shapes, checksums) is a small shared tax
    body8 = sum(int(np.asarray(v).nbytes)
                for st in states8.values()
                for n, v in st.items() if isinstance(v, np.ndarray))
    assert n8 - body8 < 0.15 * n8  # framing is a sliver of the payload
    # the wire stores dtype NAMES, so frames differ by a few bytes per
    # array across dtypes — allow that slack, nothing more
    assert abs((n32 - n8) - 3 * body8) < 128  # f32 wire adds 3 bodies (4x)
    assert abs((n16 - n8) - 1 * body8) < 128  # bf16 wire adds 1 body (2x)
    assert n32 / n8 > 3.5 and n16 / n8 > 1.8
    assert n8 < n16 < n32

    # the default engine really does store f32 tiers (the naive wire
    # form is the honest baseline, not a strawman)
    eng0, ctx0, s0 = _run_one_slot(cfg, params)
    st0 = next(iter(ctx0.state.cache.values()))
    assert kvc.export_slot_state(st0, s0)["hot_k"].dtype.itemsize == 4


def test_checksum_mismatch_raises_typed_error(setup):
    cfg, params = setup
    eng, ctx, s = _run_one_slot(cfg, params)
    states = {k: kvc.export_slot_state(c, s)
              for k, c in ctx.state.cache.items()}
    blob = bytearray(kvc.pack_slot_state(states, PS))
    blob[len(blob) // 2] ^= 0xFF
    with pytest.raises(HandoffError) as ei:
        kvc.unpack_slot_state(bytes(blob))
    assert ei.value.key is not None  # names the corrupted entry
    with pytest.raises(HandoffError, match="torn"):
        kvc.unpack_slot_state(bytes(blob[: len(blob) // 3]))
    with pytest.raises(HandoffError):
        kvc.unpack_slot_state(b"NOPE" + bytes(blob)[4:])


def test_import_refuses_dtype_cast(setup):
    """import_slot_state must never silently cast KV bits."""
    cfg, params = setup
    eng, ctx, s = _run_one_slot(cfg, params)
    key = next(iter(ctx.state.cache))
    st = kvc.export_slot_state(ctx.state.cache[key], s)
    st = dict(st, hot_k=st["hot_k"].astype(np.float16))
    with pytest.raises(HandoffError, match="dtype"):
        kvc.import_slot_state(ctx.state.cache[key], s, st)


# ---------------------------------------------------------------------------
# control plane: placement, backoff, health, restart
# ---------------------------------------------------------------------------


def test_least_loaded_placement_spreads(setup):
    """With both replicas idle and equal, requests spread instead of
    piling on one replica."""
    cfg, params = setup
    router, reps = _fleet(cfg, params, seed=0)
    for rep in reps:
        rep.start()
    for r in _reqs(cfg, n=4, base_rid=800, budget=4):
        router.submit(r)
    router._dispatch()
    homes = set(router.assigned.values())
    assert homes == {"r0", "r1"}
    while router.tick():
        pass
    assert len(router.finished) == 4


def test_backoff_is_deterministic_and_reconciles(setup):
    """Same router seed → same backoff delays; retry counters reconcile
    with per-request dispatch surplus (the fleet checker's rule)."""
    cfg, params = setup

    def run():
        router, _ = _fleet(cfg, params, seed=7)
        delays = [router._backoff(a) for a in (1, 1, 2, 3, 4)]
        return delays

    a, b = run(), run()
    assert a == b
    assert all(x <= router_cap() * (1.5) for x in a)
    # monotone envelope: attempt k's un-jittered base doubles up to cap
    router, _ = _fleet(cfg, params, seed=7, backoff_jitter=0.0)
    bases = [router._backoff(k) for k in (1, 2, 3, 4, 5, 6)]
    assert bases == sorted(bases)
    assert bases[-1] == router.backoff_cap


def router_cap():
    return 0.5


def test_retry_budget_exhaustion_fails_terminally(setup):
    """A replica that dies every time it touches the work makes the
    request fail AFTER retry_limit dispatches — outcome 'failed',
    exactly one terminal, counters reconcile."""
    cfg, params = setup
    reps = [Replica("r0", _engine(cfg, params))]
    router = Router(reps, seed=0, retry_limit=2, max_restarts=3,
                    sleep=lambda s: None)

    def hook(r):
        # kill the lone replica whenever it holds live work
        rep = r.replicas["r0"]
        if not rep.dead and rep.busy():
            rep.kill()
        check_fleet_invariants(r)

    fins = router.serve(_reqs(cfg, n=1, base_rid=820), on_tick=hook)
    assert len(fins) == 1
    assert fins[0].outcome == "failed"
    assert router.attempts[820] == 2
    assert router.stats.failed == 1


def test_heartbeat_timeout_drains(setup):
    """A replica whose heartbeat goes stale (injected clock) is drained
    even with straggler detection off."""
    cfg, params = setup
    now = {"t": 0.0}
    clock = lambda: now["t"]  # noqa: E731
    reps = [Replica(f"r{i}", _engine(cfg, params), clock=clock)
            for i in range(2)]
    router = Router(reps, seed=0, straggler_drain=False,
                    heartbeat_timeout=5.0, clock=clock,
                    sleep=lambda s: None)
    fired = {"done": False}

    def hook(r):
        now["t"] += 0.1
        if not fired["done"] and r.stats.ticks == 2:
            # r0's heartbeat goes stale relative to the fake clock
            r.replicas["r0"].heartbeat = now["t"] - 10.0
            fired["done"] = True
        check_fleet_invariants(r)

    fins = router.serve(_reqs(cfg, base_rid=840), on_tick=hook)
    assert len(fins) == 5
    assert router.stats.drains >= 1


def test_restart_retries_through_run_with_recovery(setup):
    """A deterministically failing restart (FaultInjector on the
    replica) is retried by run_with_recovery and the replica rejoins."""
    cfg, params = setup
    router, reps = _fleet(cfg, params, seed=0, max_restarts=2)
    reps[0].restart_faults = FaultInjector(fail_at_steps=(1,))

    def hook(r):
        if r.stats.ticks == 1 and not r.replicas["r0"].dead:
            r.replicas["r0"].kill()
        check_fleet_invariants(r)

    fins = router.serve(_reqs(cfg, base_rid=860), on_tick=hook)
    assert len(fins) == 5
    assert all(f.outcome == "finished" for f in fins)
    assert reps[0].restart_faults.fired  # the injected failure happened
    assert not reps[0].dead  # ...and recovery retried past it
    assert router.stats.restarts == 1


def test_restart_budget_exhausted_replica_stays_dead(setup):
    """Every restart attempt fails: the replica is retired and the
    fleet finishes on the survivor."""
    cfg, params = setup
    router, reps = _fleet(cfg, params, seed=0, max_restarts=1)
    reps[0].restart_faults = FaultInjector(fail_at_steps=(1, 2, 3, 4, 5))

    def hook(r):
        if r.stats.ticks == 1 and not r.replicas["r0"].dead:
            r.replicas["r0"].kill()
        check_fleet_invariants(r)

    fins = router.serve(_reqs(cfg, base_rid=880), on_tick=hook)
    assert len(fins) == 5
    assert all(f.outcome == "finished" for f in fins)
    assert reps[0].dead
    assert "r0" in router._retired


# ---------------------------------------------------------------------------
# PreemptionGuard graceful drain (satellite)
# ---------------------------------------------------------------------------


def test_preemption_guard_graceful_drain(setup, reference):
    """guard.request() mid-serve: the engine finishes its iteration,
    folds the active slots, and returns early with the evacuated
    requests in last_drained; resubmitting them (fresh engine) yields
    the same tokens bit-exactly."""
    cfg, params = setup
    guard = PreemptionGuard()
    eng = _engine(cfg, params, guard=guard)
    reqs = _reqs(cfg, base_rid=900)

    def hook(ctx):
        if ctx.iteration == 2:
            guard.request()

    fins = eng.serve(reqs, on_iteration=hook)
    assert eng.last_drained, "drain must evacuate in-flight work"
    assert not guard.requested  # consumed by the drain
    drained_rids = {r.rid for r in eng.last_drained}
    assert drained_rids.isdisjoint({f.rid for f in fins})
    # resume elsewhere: a second engine completes the drained requests
    fins2 = _engine(cfg, params).serve(eng.last_drained)
    combined = {f.rid: f for f in list(fins) + list(fins2)}
    assert len(combined) == len(reqs)
    for f in combined.values():
        assert f.outcome == "finished"
        np.testing.assert_array_equal(f.tokens, reference[f.rid - 900])


def test_preemption_guard_signal_handler_path(setup):
    """The signal-handler body (pragma: no cover) flips the flag — call
    it directly, the way a real SIGTERM delivery would."""
    guard = PreemptionGuard(install_handlers=False)
    assert not guard.requested
    guard._handler(signal.SIGTERM, None)
    assert guard.requested


def test_router_uses_drain_for_warm_migration(setup):
    """Replica.drain (the guard's evacuation path) is what the router's
    health sweep calls: after a manual drain the work migrates and
    finishes on the fleet."""
    cfg, params = setup
    router, reps = _fleet(cfg, params, seed=0)
    state = {"drained": False}

    def hook(r):
        if not state["drained"] and r.stats.ticks == 2:
            rep = r.replicas["r0"]
            if rep.busy():
                r._drain_replica(rep, "manual")
                state["drained"] = True
        check_fleet_invariants(r)

    fins = router.serve(_reqs(cfg, base_rid=920), on_tick=hook)
    assert len(fins) == 5
    assert all(f.outcome == "finished" for f in fins)
    assert state["drained"] and router.stats.drains >= 1


# ---------------------------------------------------------------------------
# straggler stats wiring (satellite)
# ---------------------------------------------------------------------------


def test_serve_stats_iteration_times(setup):
    """Every serve() records per-iteration wall time: p50/max populated,
    and an injected slow iteration shows up in straggler_flags."""
    cfg, params = setup
    eng = _engine(cfg, params)
    import time as _time

    def hook(ctx):
        if ctx.iteration == 6:
            _time.sleep(0.3)

    eng.serve(_reqs(cfg, base_rid=940), on_iteration=hook)
    st = eng.last_stats
    assert st.iter_p50 > 0.0
    assert st.iter_max >= 0.3
    assert st.straggler_flags >= 1
    assert st.iter_max >= st.iter_p50


def test_replica_exposes_straggler_flags(setup):
    cfg, params = setup
    rep = Replica("r0", _engine(cfg, params))
    rep.start()
    assert rep.straggler_flags() == 0
    for r in _reqs(cfg, n=2, base_rid=960, budget=12):
        rep.submit(r)
    steps = 0
    while rep.busy():
        if steps == 5:  # after the monitor has its >=5 baseline samples
            rep.stall(0.3)
        rep.step()
        steps += 1
    assert steps >= 6  # the stalled iteration had its >=5-sample baseline
    assert rep.straggler_flags() >= 1


# ---------------------------------------------------------------------------
# the fleet checker is falsifiable
# ---------------------------------------------------------------------------


def _fake_router(**kw):
    base = dict(finished=[], pending=[], replicas={}, assigned={},
                attempts={}, accepted={},
                stats=SimpleNamespace(retries=0, failed=0))
    base.update(kw)
    return SimpleNamespace(**base)


def test_fleet_checker_catches_lost_request():
    r = _fake_router(accepted={1: object()})
    with pytest.raises(InvariantViolation, match="NOWHERE"):
        check_fleet_invariants(r)


def test_fleet_checker_catches_double_terminal():
    fin = SimpleNamespace(rid=1, outcome="finished")
    r = _fake_router(accepted={1: object()}, finished=[fin, fin])
    with pytest.raises(InvariantViolation, match="2 places"):
        check_fleet_invariants(r)


def test_fleet_checker_catches_rid_on_two_replicas():
    req = SimpleNamespace(rid=1)
    rep = lambda name: SimpleNamespace(  # noqa: E731
        name=name, dead=False,
        ctx=SimpleNamespace(sched=SimpleNamespace(queue=[req],
                                                  slot_req=[None]),
                            pool=None))
    r = _fake_router(accepted={1: req},
                     replicas={"a": rep("a"), "b": rep("b")})
    with pytest.raises(InvariantViolation, match="2 places"):
        check_fleet_invariants(r)


def test_fleet_checker_catches_shared_pool(setup):
    cfg, params = setup
    from repro.serving import PagePool
    pool = PagePool(4)
    mk = lambda name: SimpleNamespace(  # noqa: E731
        name=name, dead=False,
        ctx=SimpleNamespace(sched=SimpleNamespace(queue=[], slot_req=[]),
                            pool=pool, ptree=None, slot_pages=[],
                            host_table=None, spec=False))
    r = _fake_router(replicas={"a": mk("a"), "b": mk("b")})
    with pytest.raises(InvariantViolation, match="share one PagePool"):
        check_fleet_invariants(r)


def test_fleet_checker_catches_retry_mismatch():
    r = _fake_router(attempts={1: 3},
                     accepted={},
                     stats=SimpleNamespace(retries=0, failed=0))
    with pytest.raises(InvariantViolation, match="retries"):
        check_fleet_invariants(r)


# ---------------------------------------------------------------------------
# replica guard rails
# ---------------------------------------------------------------------------


def test_dead_replica_refuses_work(setup):
    cfg, params = setup
    rep = Replica("r0", _engine(cfg, params))
    rep.start()
    rep.kill()
    with pytest.raises(ReplicaDead):
        rep.submit(Request(rid=1, tokens=np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=2))
    with pytest.raises(ReplicaDead):
        rep.step()
    with pytest.raises(ReplicaDead):
        rep.drain()


def test_local_transport_corruption_is_one_shot():
    t = LocalTransport()
    payload = bytes(range(64))
    t.corrupt_next()
    assert t.send(payload) != payload
    assert t.send(payload) == payload
    t.truncate_next()
    assert len(t.send(payload)) < len(payload)
    assert t.sent == 3 and t.corrupted == 2


def test_replica_devices_partitions_evenly():
    from repro.launch.mesh import replica_devices

    devs = list(range(8))  # partitioning is pure — any sequence works
    assert replica_devices(0, 2, devs) == (0, 1, 2, 3)
    assert replica_devices(1, 2, devs) == (4, 5, 6, 7)
    got = [replica_devices(i, 3, devs) for i in range(3)]
    assert all(len(g) == 2 for g in got)
    assert len({d for g in got for d in g}) == 6  # pairwise disjoint
    # fewer devices than replicas (CPU dev box): round-robin, never empty
    assert replica_devices(2, 4, [0, 1]) == (0,)
    assert replica_devices(3, 4, [0, 1]) == (1,)
    with pytest.raises(ValueError):
        replica_devices(2, 2, devs)


def test_engine_device_commits_params_and_state(setup, reference):
    """``Engine(device=)`` commits params and the serving state to that
    device, and the placed engine serves the same greedy tokens."""
    cfg, params = setup
    dev = jax.devices()[-1]
    eng = _engine(cfg, params, device=dev)
    seen = set()

    def hook(ctx):
        for leaf in jax.tree.leaves(ctx.state):
            assert leaf.committed
            seen.update(leaf.devices())

    fins = eng.serve(_reqs(cfg), on_iteration=hook)
    _assert_bit_exact(fins, reference)
    for leaf in jax.tree.leaves(eng.params):
        assert leaf.committed and leaf.devices() == {dev}
    assert seen == {dev}
