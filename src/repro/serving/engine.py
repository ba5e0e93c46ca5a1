"""Continuous-batching serving engine: packed-ternary weights + per-slot
DR-tiered KV caches, with a fully-jitted decode hot loop.

The paper's deployment (§V-B): weights fused on-die (here: packed ternary,
device-resident across the whole session — ZERO weight reload), a DR
eDRAM hot tier for the first ``hot_cap`` tokens of each sequence, external
memory for the rest. Because the weights never move, the serving problem
reduces to keeping the decode path saturated — which is what the slot
model below does.

Architecture
------------
Device state (``DecodeState``) is a fixed-shape pytree over ``n_slots``
batch rows: the stacked tiered KV cache (per-slot ``lengths``), the last
sampled token, a ``done`` mask, per-slot output buffer and the vectorized
DR-traffic ledger. One decode step is ONE jitted dispatch:

  * embedding -> L-layer scan -> logits for every slot,
  * KV appends and recurrent-state updates gated by the on-device
    ``active = allocated & ~done`` mask,
  * sampling (greedy or temperature) on-device,
  * stop-token detection folds into ``done`` ON DEVICE — no
    ``bool(jnp.all(...))`` host pull, so the Python loop never blocks.

The host only syncs at *chunk boundaries* (every ``sync_every`` steps): it
reads the small ``done``/``allocated`` masks, retires finished slots,
harvests their outputs and per-slot ledgers, and admits queued prompts
into the freed slots (``serving/scheduler.py`` decides who goes where) —
either as whole same-length groups (prefill dispatch + cache scatter) or,
with ``prefill_chunk`` set, as fixed-size chunk dispatches streamed
straight into the live cache at per-slot offsets (flash-prefill
continuation: ONE prefill compilation for any prompt-length mix). Slots at different
sequence lengths decode side by side; per-slot lengths keep each
sequence's attention exact — on TPU via the flash-decode Pallas kernel
(``kernels/flash_decode.py``: hot and cold tier merged in one streaming
launch, S-blocks predicated per slot so a sequence streams only its own
prefix — the compute-side counterpart of the DR-traffic ledger below),
elsewhere via the masked validity paths in ``core/kv_cache.py``.

Traffic accounting
------------------
The ledger is vectorized per slot in *token* units
(``kv_cache.step_traffic_tokens``) and accumulated inside the jitted step;
the analytic prompt-phase ledger (``prompt_traffic_tokens``) is added at
admission. Per sequence, the total reconciles exactly with
``dr_edram.closed_form_reduction(seq_len, hot_cap)`` — including in
mixed-length batches, which is asserted in tests.

Paged serving
-------------
With ``paged=True`` the cold tier is page-table indirected
(``core/kv_cache.PagedKVCache``): cold KV rows live in a shared pool and
each slot's page-table row maps its logical cold pages onto pool pages.
A host-side refcounted radix tree (``serving/paging.py``) matches each
new prompt against previously served prefixes; matched cold pages are
adopted by reference (one physical copy across N slots), the boundary
page is adopted copy-on-write, the hot tier is restored from a pooled
snapshot, and chunked prefill streams only the novel suffix. The whole
per-slot (re)initialisation is ONE fused jitted dispatch
(``kv_cache.paged_admit`` vmapped over the layer stacks). Skipped
prefill work is reported per request as
``FinishedRequest.prefix_tokens_reused`` and the prompt-phase ledger
switches to ``prompt_traffic_tokens_resumed`` so the DR accounting
reconciles with the external reads that actually happened.

Graceful degradation (docs/serving.md, "Degradation modes")
------------------------------------------------------------
The page pool is the paper's fixed on-die KV budget: overload must
degrade against it, never crash against it. Pages are allocated
*lazily* — admission funds only the prompt, decode growth is funded
chunk-by-chunk — and when the pool cannot fund a claim the engine
reclaims in order: LRU tree eviction first, then **preemption** of
strictly weaker slots (``SlotScheduler.preempt_victims``: never a
stronger claim, fewest-emitted/newest first among the eligible). A
preempted request's emitted tokens fold into its prompt and it requeues;
re-admission rides the prefix-cache match + chunked prefill, so only
work past the shared prefix is recomputed and greedy outputs stay
bit-identical to an unconstrained run (asserted in tests). Requests
carry ``deadline``/``priority``, ``Engine.cancel(rid)`` propagates to
slot retirement and page decref mid-flight, and a bounded queue sheds
overflow explicitly; every terminal path surfaces as
``FinishedRequest.outcome``. ``serving/chaos.py`` fault-injects this
plane (pool exhaustion, stragglers, mid-prefill cancellation) and
re-checks the refcount/page-table invariants after every loop iteration
under test, via serve()'s ``on_iteration`` hook.

Speculative decoding (docs/serving.md, "Speculative decoding")
--------------------------------------------------------------
``Engine(draft_cfg=..., draft_params=..., spec_k=K)`` replaces the
one-token decode dispatch with a draft-verify round: K greedy draft
steps against a per-slot draft KV cache propose a K-token chunk, ONE
``transformer.spec_verify_chunk`` dispatch scores it against the target
cache without appending, and the vectorized acceptance rule
(``serving/speculative.longest_accepted_prefix``) keeps the longest
prefix the target itself would have emitted. Linear cache layouts
commit the full chunk and roll the rejected suffix back with
``kv_cache.truncate`` (the paged form then decrefs the stranded trailing
pages at the iteration boundary); ring (SWA) layouts commit only the
accepted rows — a wrapped ring append is destructive, so there is
nothing safe to roll back. Greedy outputs are bit-identical to the
non-speculative loop for every accept/reject mix (every emitted token is
a target argmax; the draft only sets the pace), which
tests/test_speculative.py asserts end-to-end.

docs/serving.md walks the full request lifecycle (slots, admission
groups, ``sync_every`` semantics, the paging lifecycle, the
reconciliation contract); docs/kernels.md covers the packed fast path
the decode loop runs on.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Set)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import dr_edram, kv_cache
from repro.core.kv_cache import HandoffError
from repro.distributed.fault import PreemptionGuard, StragglerMonitor
from repro.models import pack as pack_lib
from repro.models import transformer as T
from repro.serving import sdc as sdc_lib
from repro.serving import speculative as spec_lib
from repro.serving.paging import (PagePool, PagePoolError, PrefixCache,
                                  PrefixMatch, pages_needed)
from repro.serving.scheduler import (FinishedRequest, Request, SlotScheduler,
                                     terminal_record)

TRAFFIC_KEYS = kv_cache.TRAFFIC_KEYS

# consecutive no-progress serve-loop iterations tolerated before the
# engine declares the pool unreclaimable. Transient holds (chaos
# injection pinning pages for a few iterations) ride through; a pool
# that genuinely cannot fund the strongest queued claim — unreachable
# under the default sizing + the serve() feasibility check — still
# surfaces as a typed PagePoolError instead of a silent spin.
_STALL_LIMIT = 32

# `generate` pads rows that stopped early with this sentinel. The stop
# token itself is a real emitted token (it appears in `tokens` when
# sampled), so padding with it would make genuine stops
# indistinguishable from padding; -1 is outside every vocabulary.
PAD_TOKEN = -1


class DecodeState(NamedTuple):
    """Fixed-shape device state for the jitted decode loop (one row = slot)."""

    cache: Any  # stacked tiered KV / SSM state pytree, per-slot lengths
    tok: jax.Array  # (slots,) int32 — last sampled token per slot
    key: jax.Array  # PRNG key threaded through on-device sampling
    allocated: jax.Array  # (slots,) bool — slot holds a live request
    done: jax.Array  # (slots,) bool — request finished (stop / budget)
    seq_len: jax.Array  # (slots,) int32 — cache length incl. prompt
    n_gen: jax.Array  # (slots,) int32 — tokens emitted so far
    max_new: jax.Array  # (slots,) int32 — per-slot generation budget
    out: jax.Array  # (slots, out_cap) int32 — emitted tokens
    ledger: Dict[str, jax.Array]  # 4 × (slots,) int32 decode token counts
    # speculative decoding (None / zeros on non-speculative engines):
    draft_cache: Any = None  # draft model's per-slot tiered KV cache
    drafted: Any = None  # (slots,) int32 — draft proposals scored so far
    accepted: Any = None  # (slots,) int32 — proposals the target accepted
    # SDC sentinel: latches (slots,) True when a step's logits go
    # non-finite for an active slot — folded ON DEVICE every dispatch,
    # read only at scrub sync points (serving/sdc.py)
    numerics_bad: Any = None


@dataclasses.dataclass
class GenerationResult:
    tokens: jax.Array  # (b, max_new) int32, PAD_TOKEN past each row's end
    steps: int  # max over rows (the batch's wall-clock step count)
    traffic: dict  # accumulated on-die vs external bytes
    wall_s: float
    # tokens actually emitted per row — rows that hit the stop token
    # early are shorter than `steps`; `tokens[i, steps_per_row[i]:]` is
    # all PAD_TOKEN.
    steps_per_row: Optional[List[int]] = None

    @property
    def external_reduction(self) -> float:
        return kv_cache.external_reduction(self.traffic)


@dataclasses.dataclass
class ServeStats:
    """Control-plane counters for one ``serve()`` call (``Engine.
    last_stats``): how much degradation the workload forced. ``
    recompute_tokens`` counts prompt tokens a re-admission actually
    prefilled again (attempt prompt minus the prefix-cache match) — the
    price of preemption, to weigh against the prefix-sharing savings in
    ``FinishedRequest.prefix_tokens_reused``."""

    preemptions: int = 0
    rejected: int = 0
    cancelled: int = 0
    expired: int = 0
    recompute_tokens: int = 0
    grown_pages: int = 0
    iterations: int = 0
    # per-iteration wall time (seconds), fed live into the session's
    # StragglerMonitor (distributed/fault.py): p50/max over the whole
    # call plus how many iterations the monitor flagged as stragglers
    # (> factor x window median). The router's health checks consume the
    # same monitor through Replica.straggler_flags().
    iter_p50: float = 0.0
    iter_max: float = 0.0
    straggler_flags: int = 0
    # speculative decoding ledger (0 on non-speculative engines): draft
    # proposals scored by the target vs proposals accepted. Per request
    # the identity `emitted == accepted + rounds` holds (each verify
    # round always emits its pending token on top of the accepted run).
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    # SDC ladder counters (0 unless Engine(integrity=...) is set): faults
    # the scrub detected, full KV pages crc-verified, packed leaves
    # reloaded from their golden copy, and slots contained for
    # non-finite logits (outcome "numerics")
    sdc_detected: int = 0
    pages_scrubbed: int = 0
    weight_reloads: int = 0
    slots_quarantined: int = 0
    # observability (docs/serving.md, "Observability"): host seconds per
    # run_iteration phase (the spans ``engine.<phase>``), and the work
    # dispatched, counted from the host mirrors with no device read —
    # decode dispatches, decoding slots summed over them, each decoding
    # slot's valid prefix (new token included) summed over them, chunk
    # dispatches and the prompt tokens they carried; a speculative
    # engine counts its rounds. ``traces`` counts each trace of a jitted
    # program by name, so a retrace in steady state shows.
    host_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    decode_dispatches: int = 0
    decode_slot_steps: int = 0
    kv_tokens_attended: int = 0
    chunk_dispatches: int = 0
    prefill_tokens: int = 0
    traces: Dict[str, int] = dataclasses.field(default_factory=dict)

    def record_spec(self, fin: FinishedRequest) -> None:
        self.drafted_tokens += fin.drafted_tokens
        self.accepted_tokens += fin.accepted_tokens


@dataclasses.dataclass
class _ServeCtx:
    """Mutable state of one ``serve()`` call, threaded through the
    admission / growth / preemption / harvest helpers and handed to the
    ``on_iteration`` hook after every loop iteration (the chaos harness
    and invariant checker in ``serving/chaos.py`` read ``pool`` /
    ``ptree`` / ``host_table`` / ``slot_pages`` / ``sched`` through it;
    mutating anything but the pool's free pages or issuing
    ``Engine.cancel`` from the hook is undefined)."""

    state: DecodeState
    sched: SlotScheduler
    finished: List[FinishedRequest]
    stats: ServeStats
    token_bytes: int
    chunked: bool
    remaining: List[int]  # per-slot budget mirror (host-side, no sync)
    seq_mirror: List[int]  # per-slot upper bound on cache length
    prefix_used: List[int]  # matched-prefix tokens per live slot
    prefilling: Dict[int, list]  # slot -> [req, offset], mid-prefill
    slot_pages: List[List[int]]
    pool: Optional[PagePool] = None
    ptree: Optional[PrefixCache] = None
    host_table: Optional[np.ndarray] = None
    iteration: int = 0
    # speculative decoding: slot -> [req, offset] for the draft model's
    # own chunked prefill (runs alongside the target's; a slot decodes
    # only once BOTH caches hold the full prompt), plus the geometry the
    # invariant checker needs to audit post-rollback page occupancy
    draft_prefilling: Dict[int, list] = dataclasses.field(default_factory=dict)
    spec: bool = False
    hot_cap: int = 0
    page_size: int = 0
    # session plumbing (start_session/run_iteration): the jitted step for
    # this session's (out_cap, stop_token), the sync chunk width, the
    # per-iteration hook, the wall-time straggler monitor, the stall-
    # guard counter, and — after drain_session — the folded requests
    # that were evacuated instead of finished
    step_fn: Any = None
    chunk: int = 8
    on_iteration: Optional[Callable[["_ServeCtx"], None]] = None
    monitor: Optional[StragglerMonitor] = None
    stall: int = 0
    drained: Optional[List[Request]] = None
    # SDC scrub state (Engine(integrity=...)): crc stamps over FULL cold
    # pages keyed page -> (born, crc32) — `born` names the page's
    # current life (PagePool.born), so stale stamps can never follow a
    # reallocated id; per-slot count of tokens verified at the last
    # clean scrub (the rollback target for detected corruption); and
    # the iteration of the last scrub (cadence bookkeeping)
    page_crc: Dict[int, tuple] = dataclasses.field(default_factory=dict)
    verified_len: Optional[List[int]] = None
    last_scrub: int = -1


@contextlib.contextmanager
def _phase(stats: ServeStats, name: str, **args):
    """One phase of ``Engine.run_iteration``: a profiler span
    ``engine.<name>`` on the device trace's clock (free while no trace
    runs), and its host seconds added to ``stats.host_s``."""
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"engine.{name}", **args):
        yield
    stats.host_s[name] = stats.host_s.get(name, 0.0) + time.perf_counter() - t


class Engine:
    """Weight-reload-free continuous-batching inference engine.

    ``serve(requests)`` is the native API: a list of :class:`Request` with
    arbitrary prompt lengths and budgets, served through ``slots``
    concurrent slots with mid-decode admission. ``generate(prompts, ...)``
    is the aligned-batch convenience wrapper (one slot per row) kept for
    the launchers, examples and benchmarks.

    The engine is immutable after construction: sampling mode,
    temperature, hot_cap and max_len are baked into the cached jitted
    step/prefill/admit functions at first trace, so mutating those
    attributes later is silently ignored — build a new Engine instead
    (the packed params can be shared across engines).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        hot_cap: int = 32,
        max_len: int = 256,
        pack: bool = True,
        sample: str = "greedy",
        temperature: float = 1.0,
        seed: int = 0,
        slots: int = 8,
        sync_every: int = 8,
        prefill_chunk: int = 0,
        paged: bool = False,
        page_size: Optional[int] = None,
        n_pages: Optional[int] = None,
        prefix_sharing: bool = True,
        max_queue: Optional[int] = None,
        clock: Optional[Callable[[], float]] = None,
        draft_cfg: Optional[ModelConfig] = None,
        draft_params=None,
        spec_k: int = 0,
        spec_force: Optional[str] = None,
        guard: Optional[PreemptionGuard] = None,
        integrity: Optional[sdc_lib.IntegrityConfig] = None,
        device: Optional[jax.Device] = None,
    ):
        self.cfg = cfg
        # the chip that holds this engine's params and state (default:
        # JAX's default device). Committed arrays pin every jitted step to
        # it, so data-parallel replicas in one process each use their own.
        self.device = device
        # Freeze to ROM form once (packed trits + fused wqkv/wgu/w_dqkv/w_gu
        # projection groups, models/pack.py); never reloaded afterwards. The
        # decode hot loop then runs the packed fast path (core/bitlinear.
        # packed_matmul: act-quant-prologue + epilogue-fused Pallas kernel on
        # TPU via BitNetConfig.impl="auto" — raw bf16 in, scaled float out,
        # no int8/int32 HBM intermediates; E-loop expert kernel for MoE) and
        # the flash-decode attention kernel (kernels/flash_decode.py) over
        # the tiered KV cache, dispatched by the same impl="auto" rule.
        self.params = pack_lib.pack_params(params, cfg) if pack else params
        self.mode = "packed" if pack else "qat"
        self.hot_cap = hot_cap
        self.max_len = max_len
        self.sample = sample
        self.temperature = temperature
        self.key = self._place(jax.random.PRNGKey(seed))
        self.slots = slots
        self.sync_every = sync_every
        # chunked-prefill admission (docs/serving.md): 0 keeps the legacy
        # same-length-group whole-prompt admission; C > 0 streams prompts
        # into freed slots as fixed-size C-token chunk dispatches against
        # the live cache — ONE prefill compilation total for any prompt-
        # length mix. Supported for attention-cache families without a
        # frontend; other archs fall back to grouped admission.
        self.prefill_chunk = prefill_chunk
        # paged cold tier + refcounted prefix sharing (module docstring /
        # serving/paging.py). One page = one flash S-block, so the decode
        # kernel's cold gather indexes whole pages — page_size defaults to
        # the block the kernel would pick anyway.
        self.paged = paged
        self.prefix_sharing = bool(prefix_sharing) and paged
        if paged:
            if not (prefill_chunk > 0 and self._chunked_capable()
                    and cfg.attn_type == "full"):
                raise ValueError(
                    "paged serving needs chunked prefill (prefill_chunk > 0)"
                    " on a full-attention cache family — grouped whole-"
                    "prompt admission bypasses the page table"
                )
            if max_len <= hot_cap:
                raise ValueError(
                    f"paged serving needs a non-empty cold tier (max_len "
                    f"{max_len} <= hot_cap {hot_cap})"
                )
            from repro.kernels import ops as kops

            rep = max(cfg.n_heads // max(cfg.n_kv_heads, 1), 1)
            self._page_size = int(
                page_size
                or kops.default_page_size(rep, cfg.resolved_head_dim, max_len)
            )
            self._pps = -(-(max_len - hot_cap) // self._page_size)
            self._n_hot_pages = (
                -(-hot_cap // self._page_size) if hot_cap else 0
            )
            self._n_pages_cfg = n_pages
        # speculative decoding (module docstring, "Speculative decoding"):
        # a draft model + chunk width K turn the decode dispatch into a
        # draft-verify round. Greedy-only — temperature speculation needs
        # rejection sampling (serving/speculative.rejection_sample, a
        # stub) — and it rides the chunked-prefill machinery, so archs
        # that cannot chunk fall back to plain decode with a warning
        # rather than fail (the conformance suite asserts the warning).
        self.draft_cfg = draft_cfg
        self.spec_k = int(spec_k)
        if spec_force not in (None, "reject"):
            raise ValueError(f"spec_force must be None or 'reject': {spec_force!r}")
        self.spec_force = spec_force
        spec = draft_params is not None and self.spec_k > 0
        if spec:
            if draft_cfg is None:
                raise ValueError("draft_params requires draft_cfg")
            if sample != "greedy":
                spec_lib.rejection_sample()
            if draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}: draft proposals are token ids "
                    "scored by the target — the vocabularies must match"
                )
            if not (prefill_chunk > 0 and self._chunked_capable()):
                warnings.warn(
                    f"speculative decoding needs chunked prefill on an "
                    f"attention-cache family without a frontend; "
                    f"{cfg.name} (family={cfg.family}, attn={cfg.attn_type}"
                    f", frontend={cfg.frontend}, prefill_chunk="
                    f"{prefill_chunk}) falls back to non-speculative "
                    "decode", RuntimeWarning, stacklevel=2,
                )
                spec = False
        self.spec = spec
        self.draft_params = self._place(
            pack_lib.pack_params(draft_params, draft_cfg) if (spec and pack)
            else (draft_params if spec else None)
        )
        # backpressure bound on the admission queue (None = unbounded);
        # overflow at submit time is shed as outcome "rejected", never
        # silently queued. serve(max_queue=...) overrides per call.
        self.max_queue = max_queue
        # injectable clock for Request.deadline (tests/chaos use a fake
        # clock so expiry is deterministic); deadlines are absolute times
        # on THIS clock
        self._clock = clock or time.monotonic
        # cooperative preemption (distributed/fault.py): when the guard's
        # flag is raised mid-serve (SIGTERM or an external drain request),
        # the loop finishes the current chunk, folds every active slot's
        # emitted tokens into its request (the PR 7 preemption trick) and
        # returns — the evacuated requests land in `last_drained`, ready
        # to resubmit here or on another replica with bit-exact greedy
        # continuation.
        self.guard = guard
        # SDC integrity plane (serving/sdc.py; docs/serving.md "Fault
        # model & SDC ladder"): stamp every packed leaf with ABFT wsum +
        # crc32, verify the stamps at load (a corrupt ROM image refuses
        # to come up), and keep a HOST-side golden copy of the packed
        # words — the repair ladder's reload source. The serve loop then
        # scrubs on the cadence in `integrity` (engine._scrub).
        self.integrity = integrity
        self._golden: Optional[Dict[str, np.ndarray]] = None
        self.weight_fault_strikes = 0  # distinct scrubs that found faults
        self.unhealthy = False  # strikes >= max_weight_strikes
        if integrity is not None:
            self.params = pack_lib.add_integrity(self.params)
            bad = pack_lib.verify_packed(self.params)
            if bad:
                raise sdc_lib.WeightFaultError(
                    f"packed weights failed crc32 at load: {bad}")
            self._golden = {
                path: np.asarray(pw.packed).copy()
                for path, pw in pack_lib.iter_packed_leaves(self.params)
            }
        self.params = self._place(self.params)
        self.last_drained: Optional[List[Request]] = None
        self._cancel_requested: Set[int] = set()
        self.last_stats: Optional[ServeStats] = None  # of the last serve()
        self.weight_loads = 0  # host->device weight transfers after init
        self._step_fns: dict = {}  # (out_cap, stop_token) -> jitted step
        self._batch_axes = None  # lazy: cache-leaf batch-axis pytree
        self._admit_fn = None  # jitted admission (compiles per group size)
        self._chunk_step_fn = None  # jitted chunked-prefill dispatch
        self._paged_admit_fn = None  # jitted fused paged (re)admission
        self._save_hot_fn = None  # jitted hot-tier snapshot dispatch
        self._set_table_fn = None  # jitted page-table install (growth)
        self._spec_step_fns: dict = {}  # (out_cap, stop) -> jitted round
        self._draft_chunk_fn = None  # jitted draft-cache prefill chunk
        # the stats that trace-time counts go to: those of the session
        # whose iteration ran last (run_iteration sets it)
        self._iter_stats: Optional[ServeStats] = None

        def prefill(p, batch):
            self._traced("prefill")
            return T.prefill(p, self.cfg, batch, hot_cap=self.hot_cap,
                             max_len=self.max_len, mode=self.mode)

        # jitted prefill (one compile per admitted (group, prompt) shape)
        self._prefill = jax.jit(prefill)

    def _place(self, tree):
        """Commit ``tree`` to this engine's device (no-op without one)."""
        return tree if self.device is None else jax.device_put(tree, self.device)

    def _traced(self, name: str) -> None:
        """Called at the top of each jitted program's body, so it runs only
        while JAX traces it: counts the trace into ``ServeStats.traces``."""
        if self._iter_stats is not None:
            traces = self._iter_stats.traces
            traces[name] = traces.get(name, 0) + 1

    def _chunked_capable(self) -> bool:
        """Chunked prefill needs a pure attention-token path: per-slot
        tiered KV caches (no recurrent SSM state to stream) and no
        frontend features spliced ahead of the text tokens."""
        return (
            self.cfg.family in ("dense", "moe")
            and self.cfg.attn_type in ("full", "swa")
            and self.cfg.frontend == "none"
        )

    # ------------------------------------------------------------------
    # sizing helpers
    # ------------------------------------------------------------------

    def _kv_token_bytes(self) -> int:
        cfg = self.cfg
        if cfg.attn_type == "mla":
            per_layer = cfg.mla.kv_cache_dim * 2
        elif cfg.attn_type == "none":
            per_layer = 0
        else:
            per_layer = 2 * cfg.n_kv_heads * cfg.resolved_head_dim * 2
        from repro.analysis.roofline import _n_attn_layers

        return per_layer * _n_attn_layers(cfg)

    # ------------------------------------------------------------------
    # device state init / admission scatter
    # ------------------------------------------------------------------

    def _cache_dtype(self):
        # same rule prefill uses, so admission scatters are cast-free
        return self.params["final_ln"].dtype

    def _pool_pages(self, n_slots: int) -> int:
        """Pool size for a serve() call: a full private page set per slot,
        plus headroom for the transient unevictable pages one admission
        round can pin (per fill: the matched hot snapshot + the COW
        source, protected until the fused admit dispatch lands) and one
        spare page set so insertion can snapshot a hot node."""
        if self._n_pages_cfg is not None:
            return self._n_pages_cfg
        return (
            n_slots * self._pps
            + self._pps
            + n_slots * (self._n_hot_pages + 1)
            + self._n_hot_pages
        )

    def _init_state(self, n_slots: int, out_cap: int) -> DecodeState:
        paged_kw = (
            dict(paged=True, page_size=self._page_size,
                 n_pages=self._pool_pages(n_slots))
            if self.paged else {}
        )
        cache = T.init_decode_cache(
            self.cfg, n_slots, self.max_len, self.hot_cap,
            dtype=self._cache_dtype(), **paged_kw
        )
        # the draft's cache is always a plain contiguous tiered cache —
        # it is private scratch (never prefix-shared, never paged) whose
        # lengths track the target's accepted lengths via truncate
        draft_cache = (
            T.init_decode_cache(
                self.draft_cfg, n_slots, self.max_len, self.hot_cap,
                dtype=self.draft_params["final_ln"].dtype,
            )
            if self.spec else None
        )
        self.key, sub = jax.random.split(self.key)

        def z():
            # distinct buffers: the jitted step/admit donate the state, and
            # XLA rejects donating one buffer through several arguments
            return jnp.zeros((n_slots,), jnp.int32)

        return self._place(DecodeState(
            cache=cache,
            tok=z(),
            key=sub,
            allocated=jnp.zeros((n_slots,), bool),
            done=jnp.zeros((n_slots,), bool),
            seq_len=z(),
            n_gen=z(),
            max_new=z(),
            out=jnp.zeros((n_slots, out_cap), jnp.int32),
            ledger={k: z() for k in TRAFFIC_KEYS},
            draft_cache=draft_cache,
            drafted=z(),
            accepted=z(),
            numerics_bad=jnp.zeros((n_slots,), bool),
        ))

    def _cache_batch_axes(self):
        """Pytree (matching the cache) of each leaf's batch axis, found by
        diffing the abstract shapes of two init sizes — robust across the
        dense/moe/ssm/hybrid cache layouts without per-family code."""
        if self._batch_axes is not None:
            return self._batch_axes
        sa = jax.eval_shape(
            lambda: T.init_decode_cache(self.cfg, 2, self.max_len, self.hot_cap)
        )
        sb = jax.eval_shape(
            lambda: T.init_decode_cache(self.cfg, 3, self.max_len, self.hot_cap)
        )

        def axis(a, b):
            diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape)) if x != y]
            assert len(diffs) == 1, (a.shape, b.shape)
            return diffs[0]

        self._batch_axes = jax.tree.map(axis, sa, sb)
        return self._batch_axes

    def _scatter_cache(self, live, fresh, slots_idx: jax.Array):
        """Write each fresh cache row (batch n) into the live cache at
        ``slots_idx`` along every leaf's batch axis."""
        axes = self._cache_batch_axes()

        def scatter(lv, fr, ax):
            lv_m = jnp.moveaxis(lv, ax, 0)
            fr_m = jnp.moveaxis(fr, ax, 0)
            return jnp.moveaxis(lv_m.at[slots_idx].set(fr_m.astype(lv_m.dtype)), 0, ax)

        return jax.tree.map(scatter, live, fresh, axes)

    def _sample_fn(self, logits: jax.Array, key: jax.Array) -> jax.Array:
        with jax.named_scope("sample"):
            if self.sample == "greedy":
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jax.random.categorical(
                key, logits / self.temperature, axis=-1
            ).astype(jnp.int32)

    # ------------------------------------------------------------------
    # the fully-jitted decode step
    # ------------------------------------------------------------------

    def _get_step(self, out_cap: int, stop_token: Optional[int]):
        """One decode dispatch: emit -> decode/append -> account -> sample
        -> fold stop into ``done``. Entirely on device; no host syncs."""
        key = (out_cap, stop_token)
        if key in self._step_fns:
            return self._step_fns[key]
        cfg, mode, hot_cap = self.cfg, self.mode, self.hot_cap

        def step(params, state: DecodeState) -> DecodeState:
            self._traced("step")
            with jax.named_scope("bookkeeping"):
                active = state.allocated & ~state.done
                act32 = active.astype(jnp.int32)
                # emit the pending token (sampled last step / at admission)
                emit = (
                    jnp.arange(out_cap, dtype=jnp.int32)[None]
                    == state.n_gen[:, None]
                ) & active[:, None]
                out = jnp.where(emit, state.tok[:, None], state.out)
                n_gen = state.n_gen + act32
                key_next, sub = jax.random.split(state.key)
            # decode: append the pending token's KV, get next logits
            logits, cache = T.decode_step(
                params, cfg, state.tok, state.cache, mode=mode, active=active
            )
            # on-device sampling
            sampled = self._sample_fn(logits, sub)
            with jax.named_scope("bookkeeping"):
                # vectorized per-slot DR ledger at the pre-append length
                tr = kv_cache.step_traffic_tokens(state.seq_len, hot_cap)
                ledger = {
                    k: state.ledger[k] + tr[k] * act32 for k in TRAFFIC_KEYS
                }
                seq_len = state.seq_len + act32
                tok = jnp.where(active, sampled, state.tok)
                # on-device stop handling: retire via mask, never break
                done = state.done | (active & (n_gen >= state.max_new))
                if stop_token is not None:
                    done = done | (active & (tok == stop_token))
                # SDC sentinel: latch non-finite logits per active slot,
                # on device — the scrub reads it at the next sync point
                numerics_bad = state.numerics_bad | (
                    active & ~jnp.isfinite(logits).all(axis=-1))
            return DecodeState(
                cache=cache, tok=tok, key=key_next, allocated=state.allocated,
                done=done, seq_len=seq_len, n_gen=n_gen,
                max_new=state.max_new, out=out, ledger=ledger,
                draft_cache=state.draft_cache, drafted=state.drafted,
                accepted=state.accepted, numerics_bad=numerics_bad,
            )

        fn = jax.jit(step, donate_argnums=(1,))
        self._step_fns[key] = fn
        return fn

    # ------------------------------------------------------------------
    # admission: prefill queued prompts into freed slots
    # ------------------------------------------------------------------

    def _get_admit(self):
        """Jitted admission: scatter fresh cache rows + sample first tokens
        + reset per-slot bookkeeping, all in ONE dispatch. Compiles once
        per admitted group size (shapes of idx/logits), not per prompt
        length — the fresh cache shape only depends on the group size."""
        if self._admit_fn is not None:
            return self._admit_fn

        def admit(state, fresh, logits, idx, p_len, max_new, key):
            self._traced("admit")
            first = self._sample_fn(logits, key)
            cache = self._scatter_cache(state.cache, fresh, idx)
            n = idx.shape[0]
            z = jnp.zeros((n,), jnp.int32)
            return DecodeState(
                cache=cache,
                tok=state.tok.at[idx].set(first),
                key=state.key,
                allocated=state.allocated.at[idx].set(True),
                done=state.done.at[idx].set(max_new <= 0),
                seq_len=state.seq_len.at[idx].set(p_len),
                n_gen=state.n_gen.at[idx].set(0),
                max_new=state.max_new.at[idx].set(max_new),
                out=state.out.at[idx].set(0),
                ledger={k: state.ledger[k].at[idx].set(z) for k in TRAFFIC_KEYS},
                draft_cache=state.draft_cache,
                drafted=state.drafted.at[idx].set(0),
                accepted=state.accepted.at[idx].set(0),
                numerics_bad=state.numerics_bad.at[idx].set(False),
            )

        self._admit_fn = jax.jit(admit, donate_argnums=(0,))
        return self._admit_fn

    # ------------------------------------------------------------------
    # chunked prefill: stream fixed-size prompt chunks into the live state
    # ------------------------------------------------------------------

    def _get_chunk_step(self):
        """Jitted chunked-prefill dispatch. Every shape is fixed by
        (slots, prefill_chunk) — per-slot offsets (``cache.lengths``),
        valid counts and first/last flags are data — so this compiles
        exactly ONCE per engine regardless of the prompt-length mix
        (asserted in tests/test_scheduler.py via ``_cache_size``).

        One dispatch per chunk wave: run ``transformer.prefill_chunk_step``
        over all slots (idle slots ride along with ``n_valid = 0`` and
        touch nothing), reset per-slot bookkeeping where ``is_first``,
        and sample the first token where ``is_last`` — the slot then
        enters the decode loop exactly as a group-admitted one would.
        """
        if self._chunk_step_fn is not None:
            return self._chunk_step_fn
        cfg, mode = self.cfg, self.mode

        def chunk_step(params, state: DecodeState, tokens, n_valid,
                       is_first, is_last, max_new, key) -> DecodeState:
            self._traced("chunk_step")
            with jax.named_scope("bookkeeping"):
                # a slot's first chunk starts from a clean cache row
                cache = {
                    k: c._replace(
                        lengths=jnp.where(is_first[None, :], 0, c.lengths)
                    )
                    for k, c in state.cache.items()
                }
            logits, cache = T.prefill_chunk_step(
                params, cfg, tokens, cache, n_valid, mode=mode
            )
            first_tok = self._sample_fn(logits, key)
            with jax.named_scope("bookkeeping"):
                z32 = jnp.zeros_like(state.n_gen)
                done = jnp.where(is_first, False, state.done)
                ledger = {
                    k: jnp.where(is_first, z32, state.ledger[k])
                    for k in TRAFFIC_KEYS
                }
                return DecodeState(
                    cache=cache,
                    tok=jnp.where(is_last, first_tok, state.tok),
                    key=state.key,
                    allocated=state.allocated | is_last,
                    done=jnp.where(is_last, max_new <= 0, done),
                    seq_len=jnp.where(is_first, 0, state.seq_len) + n_valid,
                    n_gen=jnp.where(is_first, 0, state.n_gen),
                    max_new=jnp.where(is_last, max_new, state.max_new),
                    out=jnp.where(is_first[:, None], 0, state.out),
                    ledger=ledger,
                    draft_cache=state.draft_cache,
                    drafted=jnp.where(is_first, 0, state.drafted),
                    accepted=jnp.where(is_first, 0, state.accepted),
                    numerics_bad=jnp.where(is_first, False,
                                           state.numerics_bad),
                )

        self._chunk_step_fn = jax.jit(chunk_step, donate_argnums=(1,))
        return self._chunk_step_fn

    # ------------------------------------------------------------------
    # speculative decoding: draft prefill + the jitted draft-verify round
    # ------------------------------------------------------------------

    def _get_draft_chunk(self):
        """Jitted chunked prefill of the DRAFT cache: same wave protocol
        as ``_get_chunk_step`` (idle slots ride along with ``n_valid=0``)
        but only the cache matters — the draft's prompt logits are
        discarded, the target samples every emitted token. Compiles once
        per engine."""
        if self._draft_chunk_fn is not None:
            return self._draft_chunk_fn
        dcfg, mode = self.draft_cfg, self.mode

        def dchunk(dparams, state: DecodeState, tokens, n_valid,
                   is_first) -> DecodeState:
            self._traced("draft_chunk")
            dcache = {
                k: c._replace(
                    lengths=jnp.where(is_first[None, :], 0, c.lengths)
                )
                for k, c in state.draft_cache.items()
            }
            _, dcache = T.prefill_chunk_step(
                dparams, dcfg, tokens, dcache, n_valid, mode=mode
            )
            return state._replace(draft_cache=dcache)

        self._draft_chunk_fn = jax.jit(dchunk, donate_argnums=(1,))
        return self._draft_chunk_fn

    def _get_spec_step(self, out_cap: int, stop_token: Optional[int]):
        """One speculative draft-verify round, fully on device (the
        spec-mode replacement for ``_get_step``; same compile-key
        discipline). K draft ``decode_step``s propose a chunk, ONE
        ``transformer.spec_verify_chunk`` scores it without appending,
        the acceptance rule picks ``n_emit``, and the commit path writes
        exactly the surviving rows (ring) or writes-then-truncates
        (linear — the paged trailing pages are decrefed host-side at the
        iteration boundary). Every emitted token is the target's argmax,
        so greedy outputs match the sequential loop bit-for-bit."""
        key = (out_cap, stop_token)
        if key in self._spec_step_fns:
            return self._spec_step_fns[key]
        cfg, dcfg, mode = self.cfg, self.draft_cfg, self.mode
        hot_cap, k_spec = self.hot_cap, self.spec_k
        ring = cfg.attn_type == "swa"
        force_reject = self.spec_force == "reject"

        def spec_step(params, dparams, state: DecodeState) -> DecodeState:
            self._traced("spec_step")
            active = state.allocated & ~state.done
            act32 = active.astype(jnp.int32)
            seq0 = state.seq_len
            remaining = jnp.maximum(state.max_new - state.n_gen, 0)
            chunk_valid = jnp.where(
                active, jnp.minimum(k_spec, remaining), 0
            )
            # -- draft: K cheap greedy steps against the draft cache.
            # chunk[:, 0] is the pending token; step i appends row i's
            # KV (gated by chunk_valid, so draft lengths advance by
            # exactly chunk_valid) and its argmax proposes row i+1.
            dcache = dict(state.draft_cache)
            cols = [state.tok]
            tok_i = state.tok
            for i in range(k_spec):
                gate = active & (i < chunk_valid)
                dlogits, dcache = T.decode_step(
                    dparams, dcfg, tok_i, dcache, mode=mode, active=gate
                )
                prop = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                tok_i = jnp.where(gate, prop, tok_i)
                if i + 1 < k_spec:
                    cols.append(tok_i)
            chunk = jnp.stack(cols, axis=1)  # (slots, K)
            # -- verify: one fixed-shape chunk dispatch, no append
            logits, kvs = T.spec_verify_chunk(
                params, cfg, chunk, state.cache, chunk_valid, mode=mode
            )
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            n_emit = spec_lib.longest_accepted_prefix(
                chunk, greedy, chunk_valid, stop_token,
                force_reject=force_reject,
            )
            # -- commit: a wrapped ring append is destructive, so ring
            # layouts commit only the accepted rows; linear layouts
            # commit the whole chunk and roll back via truncate (the
            # path the paged page-table machinery audits)
            commit_n = n_emit if ring else chunk_valid
            cache = T.spec_commit_chunk(cfg, state.cache, kvs, commit_n)
            if not ring:
                cache = {
                    kk: kv_cache.truncate(c, seq0 + n_emit)
                    for kk, c in cache.items()
                }
            # draft rollback keeps draft lengths == target lengths at
            # every round boundary (the draft re-proposes the rejected
            # suffix next round, now conditioned on the corrected token)
            dcache = {
                kk: kv_cache.truncate(c, seq0 + n_emit)
                for kk, c in dcache.items()
            }
            # -- emit the accepted run into the output buffer
            pos = (
                jnp.arange(out_cap, dtype=jnp.int32)[None]
                - state.n_gen[:, None]
            )
            emit = (pos >= 0) & (pos < n_emit[:, None])
            vals = jnp.take_along_axis(
                chunk, jnp.clip(pos, 0, k_spec - 1), axis=1
            )
            out = jnp.where(emit, vals, state.out)
            n_gen = state.n_gen + n_emit
            seq_len = seq0 + n_emit
            # pending token for the next round: the target's own
            # continuation after the last emitted token — exactly what
            # the sequential loop would have sampled there
            new_tok = jnp.take_along_axis(
                greedy, jnp.clip(n_emit - 1, 0, k_spec - 1)[:, None], axis=1
            )[:, 0]
            tok = jnp.where(active, new_tok, state.tok)
            done = state.done | (active & (n_gen >= state.max_new))
            if stop_token is not None:
                done = done | (active & (tok == stop_token))
            tr = kv_cache.spec_traffic_tokens(
                seq0, chunk_valid, commit_n, hot_cap
            )
            ledger = {
                kk: state.ledger[kk] + tr[kk] * act32 for kk in TRAFFIC_KEYS
            }
            # SDC sentinel over the verify logits (slots, K, vocab)
            numerics_bad = state.numerics_bad | (
                active & ~jnp.isfinite(logits).all(axis=(-2, -1)))
            return DecodeState(
                cache=cache, tok=tok, key=state.key,
                allocated=state.allocated, done=done, seq_len=seq_len,
                n_gen=n_gen, max_new=state.max_new, out=out, ledger=ledger,
                draft_cache=dcache,
                drafted=state.drafted + jnp.maximum(chunk_valid - 1, 0),
                accepted=state.accepted + jnp.maximum(n_emit - 1, 0),
                numerics_bad=numerics_bad,
            )

        fn = jax.jit(spec_step, donate_argnums=(2,))
        self._spec_step_fns[key] = fn
        return fn

    # ------------------------------------------------------------------
    # paged admission: page-table install + hot restore + COW, one dispatch
    # ------------------------------------------------------------------

    def _get_paged_admit(self):
        """Jitted fused paged (re)admission: vmap ``kv_cache.paged_admit``
        over every attention stack's layer axis and reset the per-slot
        decode bookkeeping where ``reset``. Every shape is fixed by the
        slot count, so this compiles exactly ONCE per engine regardless
        of which slots a round (re)admits or what their prompts matched."""
        if self._paged_admit_fn is not None:
            return self._paged_admit_fn

        def admit(state: DecodeState, reset, new_len, new_table,
                  hot_src, cow_src, cow_dst) -> DecodeState:
            self._traced("paged_admit")
            vm = jax.vmap(
                kv_cache.paged_admit,
                in_axes=(0, None, None, None, None, None, None),
            )
            cache = {
                k: vm(c, reset, new_len, new_table, hot_src, cow_src, cow_dst)
                for k, c in state.cache.items()
            }
            z32 = jnp.zeros_like(state.n_gen)
            return DecodeState(
                cache=cache,
                tok=jnp.where(reset, 0, state.tok),
                key=state.key,
                # the slot decodes only after its last prompt chunk
                # (chunk_step folds `is_last` into `allocated`)
                allocated=state.allocated & ~reset,
                done=state.done & ~reset,
                seq_len=jnp.where(reset, new_len, state.seq_len),
                n_gen=jnp.where(reset, 0, state.n_gen),
                max_new=state.max_new,
                out=jnp.where(reset[:, None], 0, state.out),
                ledger={k: jnp.where(reset, z32, state.ledger[k])
                        for k in TRAFFIC_KEYS},
                draft_cache=state.draft_cache,
                drafted=jnp.where(reset, 0, state.drafted),
                accepted=jnp.where(reset, 0, state.accepted),
                numerics_bad=jnp.where(reset, False, state.numerics_bad),
            )

        self._paged_admit_fn = jax.jit(admit, donate_argnums=(0,))
        return self._paged_admit_fn

    def _get_save_hot(self):
        """Jitted hot-tier snapshot (``kv_cache.save_hot`` vmapped over
        the layer stacks): copies one slot's hot tier into pool pages so
        the prefix tree can later restore it into another slot."""
        if self._save_hot_fn is not None:
            return self._save_hot_fn

        def sh(state: DecodeState, slot, page_ids) -> DecodeState:
            self._traced("save_hot")
            vm = jax.vmap(kv_cache.save_hot, in_axes=(0, None, None))
            cache = {k: vm(c, slot, page_ids) for k, c in state.cache.items()}
            return state._replace(cache=cache)

        self._save_hot_fn = jax.jit(sh, donate_argnums=(0,))
        return self._save_hot_fn

    def _get_set_table(self):
        """Jitted page-table install for mid-decode growth: overwrite
        every attention stack's page table with the host mirror (the
        mirror is exact — admission and growth keep it in lock-step with
        the device copy). Fixed shape (slots, pages_per_slot): one
        compile per engine."""
        if self._set_table_fn is not None:
            return self._set_table_fn

        def st(state: DecodeState, table) -> DecodeState:
            self._traced("set_table")
            cache = {
                k: c._replace(
                    page_table=jnp.broadcast_to(
                        table.astype(c.page_table.dtype), c.page_table.shape
                    )
                )
                for k, c in state.cache.items()
            }
            return state._replace(cache=cache)

        self._set_table_fn = jax.jit(st, donate_argnums=(0,))
        return self._set_table_fn

    # ------------------------------------------------------------------
    # page-pressure control plane: reclaim, preemption, release
    # ------------------------------------------------------------------

    def _release_slot_state(self, state: DecodeState, s: int,
                            truncate: bool = True) -> DecodeState:
        """Release slot ``s``'s device row mid-flight (preemption or
        cancellation): clear the allocated/done masks and truncate the
        cache row to length 0 (``kv_cache.release_slots``) so the slot is
        inert until re-admitted. Grouped-admission archs (SSM state, no
        per-slot lengths) skip the truncation — their admission scatters
        a complete fresh row anyway."""
        n = int(state.allocated.shape[0])
        mask = np.zeros((n,), bool)
        mask[s] = True
        mj = jnp.asarray(mask)
        kw = {}
        if truncate:
            kw["cache"] = {
                k: kv_cache.release_slots(c, mj)
                for k, c in state.cache.items()
            }
            if self.spec and state.draft_cache is not None:
                kw["draft_cache"] = {
                    k: kv_cache.release_slots(c, mj)
                    for k, c in state.draft_cache.items()
                }
        if state.numerics_bad is not None:
            kw["numerics_bad"] = state.numerics_bad & ~mj
        return state._replace(
            allocated=state.allocated & ~mj, done=state.done & ~mj, **kw
        )

    def _preempt_slot(self, ctx: _ServeCtx, s: int,
                      n_fold: Optional[int] = None) -> None:
        """Evict slot ``s`` mid-flight to reclaim its pages: fold the
        tokens it already emitted into the request's prompt, release its
        pages and device row, and requeue the request (its arrival stamp
        — its claim — survives). Recompute-from-prefix is bit-exact for
        greedy decoding: at preemption the pending token t_k is sampled
        but neither emitted nor cached, so re-prefilling
        prompt ‖ t_0..t_{k-1} deterministically re-samples t_k from the
        same last-position logits — and the prefix cache means only the
        suffix past the longest shared prefix is actually recomputed.

        ``n_fold`` caps how many emitted tokens fold into the prompt —
        the SDC repair ladder passes the slot's last scrub-verified
        count, so tokens emitted after a detected corruption are
        DISCARDED and regenerated from the clean prefix instead of
        poisoning the re-admission (the traffic ledger still charges
        the full attempt: the device really did that work)."""
        req = ctx.sched.slot_req[s]
        tb = ctx.token_bytes
        carry = (dict(req.carry_traffic) if req.carry_traffic
                 else {k: 0 for k in TRAFFIC_KEYS})
        if s in ctx.prefilling:
            off = ctx.prefilling.pop(s)[1]
            if off:  # charge the partial prefill the device already did
                prompt = kv_cache.prompt_traffic_tokens_resumed(
                    off, min(ctx.prefix_used[s], off), self.hot_cap)
                for k in TRAFFIC_KEYS:
                    carry[k] += prompt[k] * tb
        else:
            st = ctx.state
            p_attempt = req.prompt_len
            n_gen = int(np.asarray(st.n_gen[s]))
            if n_fold is not None:
                n_gen = min(n_fold, n_gen)
            if n_gen:
                out_row = np.asarray(st.out[s, :n_gen], np.int32)
                if req.orig_prompt_len is None:
                    req.orig_prompt_len = req.prompt_len
                req.tokens = np.concatenate(
                    [np.asarray(req.tokens, np.int32), out_row])
                req.max_new_tokens -= n_gen
            prompt = kv_cache.prompt_traffic_tokens_resumed(
                p_attempt, ctx.prefix_used[s], self.hot_cap)
            for k in TRAFFIC_KEYS:
                carry[k] += (prompt[k] + int(np.asarray(st.ledger[k][s]))) * tb
            if self.spec:
                # speculation accounting survives preemption the same way
                # traffic does: fold this attempt's counters into the
                # request, the re-admission resets the device rows
                req.carry_drafted += int(np.asarray(st.drafted[s]))
                req.carry_accepted += int(np.asarray(st.accepted[s]))
        ctx.draft_prefilling.pop(s, None)
        req.carry_traffic = carry
        req.carry_reused += ctx.prefix_used[s]
        req.n_preemptions += 1
        ctx.stats.preemptions += 1
        if ctx.slot_pages[s]:
            ctx.pool.decref(ctx.slot_pages[s])
            ctx.slot_pages[s] = []
        ctx.prefix_used[s] = 0
        ctx.remaining[s] = 0
        ctx.seq_mirror[s] = 0
        if ctx.verified_len is not None:
            ctx.verified_len[s] = 0
        ctx.sched.requeue(s)
        ctx.state = self._release_slot_state(
            ctx.state, s, truncate=ctx.chunked)

    def _paged_alloc(self, ctx: _ServeCtx, n: int, beneficiary: Request,
                     exclude: Sequence[int] = ()) -> Optional[List[int]]:
        """Allocate ``n`` pages for ``beneficiary``, reclaiming under
        pressure: LRU tree eviction first (cached prefixes are cheaper to
        lose than live work), then preemption of strictly weaker slots,
        one victim at a time (``SlotScheduler.preempt_victims`` policy) —
        a victim's pages may be tree-shared, so each preemption can also
        unlock further eviction. None when the claim cannot be funded:
        the caller requeues (admission) or self-preempts (growth), and
        the request retries at a later sync point."""
        ctx.ptree.evict_for(n)
        pages = ctx.pool.alloc(n)
        while pages is None:
            emitted = {
                s: ctx.sched.slot_req[s].max_new_tokens - ctx.remaining[s]
                for s in ctx.sched.active_slots()
                if s not in ctx.prefilling
            }
            victims = [
                v for v in ctx.sched.preempt_victims(
                    beneficiary, emitted, exclude)
                if ctx.slot_pages[v]  # pageless victims fund nothing
            ]
            if not victims:
                return None
            self._preempt_slot(ctx, victims[0])
            ctx.ptree.evict_for(n)
            pages = ctx.pool.alloc(n)
        return pages

    def _ensure_pages(self, ctx: _ServeCtx, chunk: int) -> None:
        """Fund mid-decode cold-page growth before a decode chunk: extend
        every decoding slot's page row to cover the furthest position the
        chunk can append (the host budget mirror bounds it — no device
        sync). Strongest claims fund first, so when the pool is tight the
        weak get preempted by ``_paged_alloc`` before they themselves ask;
        a slot whose own growth cannot be funded self-preempts (requeues)
        rather than stall the batch."""
        hc, ps = self.hot_cap, self._page_size
        decoding = [
            s for s in ctx.sched.active_slots() if s not in ctx.prefilling
        ]
        dirty = False
        for s in sorted(decoding,
                        key=lambda i: ctx.sched.slot_req[i].claim):
            req = ctx.sched.slot_req[s]
            if req is None:  # preempted by a stronger claim this round
                continue
            target = min(
                ctx.seq_mirror[s] + min(chunk, ctx.remaining[s]),
                self.max_len,
            )
            need = pages_needed(target, hc, ps) - len(ctx.slot_pages[s])
            if need <= 0:
                continue
            pages = self._paged_alloc(ctx, need, req, exclude=(s,))
            if pages is None:
                self._preempt_slot(ctx, s)
                continue
            k0 = len(ctx.slot_pages[s])
            ctx.slot_pages[s].extend(pages)
            ctx.host_table[s, k0 : k0 + len(pages)] = pages
            ctx.stats.grown_pages += len(pages)
            dirty = True
        if dirty:
            ctx.state = self._get_set_table()(
                ctx.state, jnp.asarray(ctx.host_table))

    def _admit_paged(self, ctx: _ServeCtx, fills) -> bool:
        """Host-side page bookkeeping for every slot paired this round,
        then ONE fused device dispatch. Matched pages are transiently
        increfed so the eviction/preemption that funds the fresh
        allocations can never free them before the dispatch reads them.

        Pages are allocated lazily — enough to cover the PROMPT only;
        decode growth is funded chunk-by-chunk by ``_ensure_pages`` — so
        admission pressure reflects real occupancy, not worst-case
        budgets. A fill the pool cannot fund (even after evicting the
        tree and preempting every weaker slot) unwinds its own increfs
        and requeues; it retries at the next sync point once pages free
        up. Returns True when at least one fill was admitted."""
        n_slots = ctx.host_table.shape[0]
        ps, hc, pps = self._page_size, self.hot_cap, self._pps
        reset = np.zeros((n_slots,), bool)
        new_len = np.zeros((n_slots,), np.int32)
        new_table = ctx.host_table.copy()
        hot_src = np.full((n_slots, max(self._n_hot_pages, 1)), -1, np.int32)
        cow_src = np.full((n_slots,), -1, np.int32)
        cow_dst = np.full((n_slots,), -1, np.int32)
        transient: List[int] = []
        # same-round fills are never preemption victims: an already-
        # processed fill has bookkeeping in flight for the fused dispatch
        # (reverting it would corrupt the host mirror), a pending one has
        # no pages to reclaim anyway
        fill_slots = [s for s, _ in fills]
        admitted = False
        for s, req in fills:
            m = (ctx.ptree.match(req.tokens)
                 if self.prefix_sharing else PrefixMatch())
            mine: List[int] = []  # this fill's transient increfs
            if m.length:
                ctx.pool.incref(m.hot_pages)
                mine.extend(m.hot_pages)
                if m.cow_src >= 0:
                    ctx.pool.incref([m.cow_src])
                    mine.append(m.cow_src)
                # the slot's own (retained) reader refs on adopted pages
                ctx.pool.incref(m.shared_pages)
            n_cold = min(pages_needed(req.prompt_len, hc, ps), pps)
            shared = list(m.shared_pages)
            n_fresh = n_cold - len(shared)
            fresh = self._paged_alloc(ctx, n_fresh, req, exclude=fill_slots)
            if fresh is None:
                # unwind THIS fill's bookkeeping before requeueing — the
                # transient and shared increfs must not outlive the
                # failed admission (they would leak the pages for good)
                if mine:
                    ctx.pool.decref(mine)
                if m.length:
                    ctx.pool.decref(list(m.shared_pages))
                ctx.sched.requeue(s)
                ctx.remaining[s] = 0
                ctx.seq_mirror[s] = 0
                continue
            transient.extend(mine)
            row = shared + fresh
            if m.cow_src >= 0 and fresh:
                cow_src[s] = m.cow_src
                cow_dst[s] = fresh[0]  # boundary page = first non-shared
            reset[s] = True
            admitted = True
            new_len[s] = m.length
            if m.hot_pages:
                hot_src[s, : len(m.hot_pages)] = m.hot_pages
            new_table[s] = row + [0] * (pps - len(row))
            ctx.slot_pages[s] = row
            ctx.prefix_used[s] = m.length
            ctx.seq_mirror[s] = req.prompt_len
            if req.orig_prompt_len is not None:
                # a re-admission prefills again what an earlier attempt
                # already computed, minus what the prefix cache kept
                ctx.stats.recompute_tokens += req.prompt_len - m.length
            # chunk streaming resumes at the matched offset: the prefix's
            # KV is already in the cache, only the suffix is prefilled
            ctx.prefilling[s] = [req, m.length]
        if admitted:
            ctx.state = self._get_paged_admit()(
                ctx.state, jnp.asarray(reset), jnp.asarray(new_len),
                jnp.asarray(new_table), jnp.asarray(hot_src),
                jnp.asarray(cow_src), jnp.asarray(cow_dst),
            )
            ctx.host_table[:] = new_table
        if transient:
            ctx.pool.decref(transient)
        return admitted

    # ------------------------------------------------------------------
    # outcomes: finish / cancel / expire / reject
    # ------------------------------------------------------------------

    def cancel(self, rid: int) -> None:
        """Request cancellation of ``rid`` mid-flight. Processed at the
        next sync point of the running ``serve()``: an active slot
        retires immediately (tokens emitted so far surface with outcome
        ``"cancelled"``), its pages decref and its device row is
        released; a queued request is shed without running. Unknown or
        already-finished rids are no-ops."""
        self._cancel_requested.add(rid)

    def _terminal_outcome(self, req: Request, now: float) -> Optional[str]:
        if req.rid in self._cancel_requested:
            self._cancel_requested.discard(req.rid)
            return "cancelled"
        if req.deadline is not None and now >= req.deadline:
            return "expired"
        return None

    def _attempt_prompt_len(self, req: Request) -> int:
        return req.prompt_len + (
            self.cfg.n_patches if req.patches is not None else 0)

    def _build_finished(self, req: Request, out_row: np.ndarray,
                        seq_len: int, decode_ledger: Dict[str, int],
                        prefilled_len: int, prefix_used: int,
                        outcome: str, token_bytes: int,
                        drafted: int = 0, accepted: int = 0) -> FinishedRequest:
        """Assemble a FinishedRequest from one slot's harvest. For a
        request that was preempted along the way, the prompt that the
        final attempt decoded from contains earlier attempts' emitted
        tokens — stitch them back onto the output and report the
        ORIGINAL prompt length, so callers see one uninterrupted
        generation; the traffic ledger sums every attempt's real work
        (``carry_traffic``) on top of this attempt's."""
        traffic = {
            k: int(decode_ledger[k]) * token_bytes for k in TRAFFIC_KEYS
        }
        if prefilled_len:
            prompt = kv_cache.prompt_traffic_tokens_resumed(
                prefilled_len, min(prefix_used, prefilled_len), self.hot_cap)
            for k in TRAFFIC_KEYS:
                traffic[k] += prompt[k] * token_bytes
        if req.carry_traffic:
            for k in TRAFFIC_KEYS:
                traffic[k] += req.carry_traffic[k]
        if req.orig_prompt_len is not None:
            prior = np.asarray(req.tokens, np.int32)[req.orig_prompt_len:]
            tokens = np.concatenate([prior, out_row])
            prompt_len = req.orig_prompt_len
        else:
            tokens = out_row
            prompt_len = req.prompt_len
        return FinishedRequest(
            rid=req.rid,
            prompt_len=prompt_len,
            tokens=tokens,
            seq_len=seq_len,
            steps=len(tokens),
            traffic=traffic,
            prefix_tokens_reused=prefix_used + req.carry_reused,
            outcome=outcome,
            n_preemptions=req.n_preemptions,
            drafted_tokens=drafted + req.carry_drafted,
            accepted_tokens=accepted + req.carry_accepted,
            t_admit=req.t_admit,
            t_first=req.t_first,
            t_finish=self._clock(),
        )

    def _finish_queued(self, req: Request, outcome: str) -> FinishedRequest:
        """Terminal record for a request that never held a slot at the
        end (rejected / cancelled / expired while queued) — shared with
        the router via ``scheduler.terminal_record``."""
        fin = terminal_record(req, outcome)
        fin.t_finish = self._clock()
        return fin

    def _cancel_slot(self, ctx: _ServeCtx, s: int, outcome: str) -> None:
        """Terminate an active slot mid-flight (cancel / deadline):
        harvest whatever it emitted, retire it, decref its pages and
        release its device row."""
        req = ctx.sched.retire(s)
        st = ctx.state
        ctx.draft_prefilling.pop(s, None)
        if s in ctx.prefilling:
            off = ctx.prefilling.pop(s)[1]
            fin = self._build_finished(
                req, np.zeros((0,), np.int32), seq_len=off,
                decode_ledger={k: 0 for k in TRAFFIC_KEYS},
                prefilled_len=off, prefix_used=ctx.prefix_used[s],
                outcome=outcome, token_bytes=ctx.token_bytes,
            )
        else:
            n_gen = int(np.asarray(st.n_gen[s]))
            out_row = (np.asarray(st.out[s, :n_gen], np.int32)
                       if n_gen else np.zeros((0,), np.int32))
            spec_kw = (
                dict(drafted=int(np.asarray(st.drafted[s])),
                     accepted=int(np.asarray(st.accepted[s])))
                if self.spec else {}
            )
            fin = self._build_finished(
                req, out_row, seq_len=int(np.asarray(st.seq_len[s])),
                decode_ledger={k: int(np.asarray(st.ledger[k][s]))
                               for k in TRAFFIC_KEYS},
                prefilled_len=self._attempt_prompt_len(req),
                prefix_used=ctx.prefix_used[s],
                outcome=outcome, token_bytes=ctx.token_bytes, **spec_kw,
            )
        ctx.finished.append(fin)
        ctx.stats.record_spec(fin)
        if ctx.slot_pages[s]:
            ctx.pool.decref(ctx.slot_pages[s])
            ctx.slot_pages[s] = []
        ctx.prefix_used[s] = 0
        ctx.remaining[s] = 0
        ctx.seq_mirror[s] = 0
        if ctx.verified_len is not None:
            ctx.verified_len[s] = 0
        ctx.state = self._release_slot_state(
            ctx.state, s, truncate=ctx.chunked)

    def _sweep_cancel_expire(self, ctx: _ServeCtx) -> int:
        """Apply cancellations and deadline expiry at a sync point, to
        queued and active requests alike. Returns the number of requests
        terminated (progress, for the stall guard)."""
        now = self._clock()
        events = 0
        for req in list(ctx.sched.queue):
            outcome = self._terminal_outcome(req, now)
            if outcome:
                ctx.sched.drop(req)
                fin = self._finish_queued(req, outcome)
                ctx.finished.append(fin)
                ctx.stats.record_spec(fin)
                setattr(ctx.stats, outcome,
                        getattr(ctx.stats, outcome) + 1)
                events += 1
        for s, req in enumerate(ctx.sched.slot_req):
            if req is None:
                continue
            outcome = self._terminal_outcome(req, now)
            if outcome:
                self._cancel_slot(ctx, s, outcome)
                setattr(ctx.stats, outcome,
                        getattr(ctx.stats, outcome) + 1)
                events += 1
        return events

    # ------------------------------------------------------------------
    # SDC scrub: the detect -> contain -> repair ladder
    # (serving/sdc.py; docs/serving.md "Fault model & SDC ladder")
    # ------------------------------------------------------------------

    def _scrub(self, ctx: _ServeCtx) -> None:
        """One scrub pass, run inside ``run_iteration`` BEFORE harvest:

          1. weights — re-crc every packed leaf (exact) and optionally
             ABFT-probe it; a mismatch reloads the leaf from its golden
             host copy, flushes the prefix tree, rolls every live slot
             back to its verified frontier and counts a strike
             (``max_weight_strikes`` strikes -> ``unhealthy``, the
             Router's retirement signal);
          2. KV pages — crc-stamp newly FULL cold pages and re-verify
             existing stamps; a mismatch quarantines the page for good,
             evicts the damaged subtree from the prefix tree and rolls
             the owning slots back to their verified frontier;
          3. numerics — read the device ``numerics_bad`` sentinel;
             a latched slot is contained (terminal outcome
             ``"numerics"``) or raised as :class:`sdc.NumericsError`,
             per ``IntegrityConfig.on_numerics``.

        Runs every ``scrub_every`` iterations AND whenever a decoding
        slot is ripe for harvest — harvest gating: no request retires
        with an unverified tail, which is what makes the ladder's
        recompute-from-prefix produce bit-identical greedy outputs.
        Slots that come through clean advance ``ctx.verified_len`` to
        their current emitted count — the rollback target is therefore
        always from a scrub that PRECEDES any later-detected fault."""
        ic = self.integrity
        done = np.asarray(ctx.state.done)
        ripe = any(
            done[s] for s in ctx.sched.active_slots()
            if s not in ctx.prefilling
        )
        if not (ripe or ctx.iteration - ctx.last_scrub >= ic.scrub_every):
            return
        ctx.last_scrub = ctx.iteration
        weight_hit = ic.scrub_weights and self._scrub_weights(ctx)
        if not weight_hit and ic.scrub_pages and self.paged:
            self._scrub_pages(ctx)
        self._check_numerics(ctx)
        # surviving decoding slots advance their verified frontier
        n_gen = np.asarray(ctx.state.n_gen)
        for s in ctx.sched.active_slots():
            if s in ctx.prefilling or s in ctx.draft_prefilling:
                continue
            ctx.verified_len[s] = int(n_gen[s])

    def _scrub_weights(self, ctx: _ServeCtx) -> bool:
        """Detect + repair packed-weight corruption. Returns True when a
        fault was found (the caller then skips the page scrub: every
        page crc stamp was just invalidated anyway)."""
        bad = set(pack_lib.verify_packed(self.params))
        if self.integrity.abft_probe:
            bad |= set(sdc_lib.abft_verify_tree(self.params))
        if not bad:
            return False
        ctx.stats.sdc_detected += len(bad)
        for path in sorted(bad):
            gold = (self._golden or {}).get(path)
            if gold is None:
                continue  # unrepairable leaf: strike below still counts
            leaf = sdc_lib.get_leaf(self.params, path)
            self.params = sdc_lib.set_leaf(
                self.params, path,
                dataclasses.replace(
                    leaf, packed=self._place(jnp.asarray(gold))))
            self.weight_loads += 1
            ctx.stats.weight_reloads += 1
        self.weight_fault_strikes += 1
        if self.weight_fault_strikes >= self.integrity.max_weight_strikes:
            # repeated faults = a genuinely bad ROM bank, not a cosmic
            # ray; the Router health sweep drains + retires the replica
            self.unhealthy = True
        # containment: everything computed since the fault window opened
        # is suspect — cached prefixes, page stamps, unverified tails
        if ctx.ptree is not None:
            ctx.ptree.flush()
        ctx.page_crc.clear()
        for s in list(ctx.sched.active_slots()):
            self._preempt_slot(ctx, s, n_fold=ctx.verified_len[s])
        return True

    def _scrub_pages(self, ctx: _ServeCtx) -> None:
        """Detect + contain KV-page corruption: stamp newly full pages,
        re-verify stamped ones, quarantine mismatches and roll their
        readers back. Only FULL cold pages behind each slot's frontier
        (plus all tree-held pages) are covered — full pages are
        append-frozen, so their bytes are content-addressable; the hot
        tier and the partial frontier page mutate legitimately and are
        covered by the numerics sentinel only (docs/serving.md)."""
        pool, ptree = ctx.pool, ctx.ptree
        hc, ps = self.hot_cap, self._page_size
        seq_dev = np.asarray(ctx.state.seq_len)
        want = set(ptree.tree_pages()) if ptree is not None else set()
        for s in ctx.sched.active_slots():
            nf = max(0, int(seq_dev[s]) - hc) // ps
            want.update(ctx.slot_pages[s][:nf])
        # retire stamps whose page left the stamped set or was re-
        # allocated to a new life (born advanced) since stamping
        for p in list(ctx.page_crc):
            if p not in want or ctx.page_crc[p][0] != int(pool.born[p]):
                del ctx.page_crc[p]
        check = sorted(ctx.page_crc)
        fresh = sorted(want - set(check))
        crcs = kv_cache.pool_page_crcs(ctx.state.cache, check + fresh)
        bad = [p for p in check if crcs[p] != ctx.page_crc[p][1]]
        for p in fresh:
            ctx.page_crc[p] = (int(pool.born[p]), crcs[p])
        ctx.stats.pages_scrubbed += len(check)
        if not bad:
            return
        ctx.stats.sdc_detected += len(bad)
        # quarantine FIRST so the eviction/preemption decrefs park the
        # damaged pages instead of returning them to the free list
        for p in bad:
            pool.quarantine(p)
            del ctx.page_crc[p]
        if ptree is not None:
            ptree.evict_pages(bad)
        bad_set = set(bad)
        for s in list(ctx.sched.active_slots()):
            if bad_set & set(ctx.slot_pages[s]):
                self._preempt_slot(ctx, s, n_fold=ctx.verified_len[s])

    def _check_numerics(self, ctx: _ServeCtx) -> None:
        """Read the latched non-finite-logits sentinel and contain (or
        raise on) every flagged slot. Containment surfaces the request
        with terminal outcome ``"numerics"`` — its partial output is
        suspect by construction and must not be silently retried."""
        if ctx.state.numerics_bad is None:
            return
        flagged = np.asarray(ctx.state.numerics_bad)
        for s in list(ctx.sched.active_slots()):
            if not flagged[s]:
                continue
            ctx.stats.sdc_detected += 1
            if self.integrity.on_numerics == "raise":
                req = ctx.sched.slot_req[s]
                raise sdc_lib.NumericsError(
                    f"non-finite logits in slot {s} "
                    f"(rid={getattr(req, 'rid', None)})", slot=s)
            # repair the transient plane before the slot is re-tenanted:
            # the poison bytes outlive the cancelled request otherwise
            sdc_lib.clear_hot_slot(ctx, s)
            self._cancel_slot(ctx, s, "numerics")
            ctx.stats.slots_quarantined += 1

    def _record_prefix(self, state: DecodeState, s: int, req: Request,
                       ptree: PrefixCache,
                       host_table: np.ndarray) -> DecodeState:
        """Insert a freshly prefilled prompt into the prefix tree. The
        ``save_hot`` callback fires only when the tree needs a new hot
        node (one jitted snapshot dispatch); cold pages are adopted from
        the slot's page table by reference."""
        box = [state]

        def save(ids):
            arr = np.full((max(ptree.n_hot_pages, 1),), -1, np.int32)
            arr[: len(ids)] = ids
            box[0] = self._get_save_hot()(
                box[0], jnp.int32(s), jnp.asarray(arr)
            )

        ptree.insert(np.asarray(req.tokens, np.int32), host_table[s], save)
        return box[0]

    def _stream_chunks(self, state: DecodeState, n_slots: int,
                       prefilling: Dict[int, list], stats: ServeStats,
                       max_waves: Optional[int] = None,
                       on_last=None,
                       draft_prefilling: Optional[Dict[int, list]] = None,
                       ) -> DecodeState:
        """Stream pending prompt chunks: one dispatch per wave, one
        C-token chunk per prefilling slot per wave. With ``max_waves``
        set the drain stops early and ``prefilling`` carries the
        remaining offsets into the next serving-loop iteration, so a
        long prompt interleaves with decode chunks instead of stalling
        every active slot until the whole queue's prompts are cached.
        ``on_last(state, slot, req)`` runs after the wave that completes
        a slot's prompt (paged serving records the prefix there).

        Speculative engines stream the DRAFT cache's prefill alongside
        (``draft_prefilling``, one extra dispatch per wave). The draft
        always starts at offset 0 — prefix sharing is a target-cache
        concept — so it can lag a target that resumed mid-prompt; the
        target's FINAL chunk is withheld until the draft catches up,
        because the slot enters the speculative decode rounds the moment
        its target prefill completes (``allocated`` is device state) and
        a round against a partial draft cache would propose garbage."""
        step = self._get_chunk_step()
        c = self.prefill_chunk
        dp = draft_prefilling if draft_prefilling is not None else {}
        waves = 0
        while ((prefilling or dp)
               and (max_waves is None or waves < max_waves)):
            toks = np.zeros((n_slots, c), np.int32)
            n_valid = np.zeros((n_slots,), np.int32)
            is_first = np.zeros((n_slots,), bool)
            is_last = np.zeros((n_slots,), bool)
            max_new = np.zeros((n_slots,), np.int32)
            finished_slots = []
            any_target = False
            for s, (req, off) in prefilling.items():
                part = np.asarray(req.tokens, np.int32)[off : off + c]
                if (s in dp and off + len(part) >= req.prompt_len
                        and dp[s][1] + c < req.prompt_len):
                    continue  # withhold the last chunk; draft still lags
                any_target = True
                toks[s, : len(part)] = part
                n_valid[s] = len(part)
                # paged slots were fully reset by the fused admit dispatch
                # (and may resume mid-prompt at a matched offset), so the
                # chunk step must not re-zero their state
                is_first[s] = off == 0 and not self.paged
                max_new[s] = req.max_new_tokens
                if off + len(part) >= req.prompt_len:
                    is_last[s] = True
                    finished_slots.append(s)
                else:
                    prefilling[s] = [req, off + len(part)]
            if dp:
                dtoks = np.zeros((n_slots, c), np.int32)
                dn_valid = np.zeros((n_slots,), np.int32)
                d_first = np.zeros((n_slots,), bool)
                d_done = []
                for s, (req, doff) in dp.items():
                    part = np.asarray(req.tokens, np.int32)[doff : doff + c]
                    dtoks[s, : len(part)] = part
                    dn_valid[s] = len(part)
                    d_first[s] = doff == 0
                    if doff + len(part) >= req.prompt_len:
                        d_done.append(s)
                    else:
                        dp[s] = [req, doff + len(part)]
                state = self._get_draft_chunk()(
                    self.draft_params, state, jnp.asarray(dtoks),
                    jnp.asarray(dn_valid), jnp.asarray(d_first),
                )
                for s in d_done:
                    dp.pop(s)
            if any_target:
                stats.chunk_dispatches += 1
                stats.prefill_tokens += int(n_valid.sum())
                self.key, sub = jax.random.split(self.key)
                state = step(
                    self.params, state, jnp.asarray(toks),
                    jnp.asarray(n_valid), jnp.asarray(is_first),
                    jnp.asarray(is_last), jnp.asarray(max_new), sub,
                )
            waves += 1
            for s in finished_slots:
                req, _ = prefilling.pop(s)
                if on_last is not None:
                    state = on_last(state, s, req)
        return state

    def _admit(
        self, state: DecodeState, slots_idx: List[int], group: List[Request]
    ) -> DecodeState:
        """Prefill ``group`` (equal prompt lengths) and scatter the fresh
        cache rows + first sampled tokens into ``slots_idx``."""
        toks = jnp.asarray(
            np.stack([np.asarray(r.tokens, np.int32) for r in group]), jnp.int32
        )
        batch = {"tokens": toks}
        if group[0].patches is not None:
            batch["patches"] = jnp.asarray(
                np.stack([np.asarray(r.patches) for r in group])
            )
        logits, fresh = self._prefill(self.params, batch)
        idx = jnp.asarray(slots_idx, jnp.int32)
        p_len = toks.shape[1] + (self.cfg.n_patches if "patches" in batch else 0)
        max_new = jnp.asarray([r.max_new_tokens for r in group], jnp.int32)
        self.key, sub = jax.random.split(self.key)
        return self._get_admit()(
            state, fresh, logits, idx, jnp.int32(p_len), max_new, sub
        )

    # ------------------------------------------------------------------
    # the serving loop — a resumable session: start_session() builds the
    # context, run_iteration() advances it by exactly one loop iteration,
    # finish_session() seals the stats. serve() composes the three; the
    # data-parallel router (serving/router.py) drives them directly so N
    # replica sessions interleave in one process.
    # ------------------------------------------------------------------

    def _validate_request(self, r: Request, n_slots: int) -> None:
        need = r.prompt_len + (
            self.cfg.n_patches if r.patches is not None else 0)
        if need == 0:
            # an empty prompt has no last-token logits to sample the
            # first generated token from — under chunked admission it
            # would silently sample from a zero-valid chunk's garbage
            # logits row
            raise ValueError(
                f"request {r.rid}: empty prompt (at least one prompt "
                "token is required to sample the first output token)"
            )
        if need + r.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {r.rid}: prompt {need} + max_new "
                f"{r.max_new_tokens} exceeds max_len {self.max_len}"
            )
        if self.paged:
            # feasibility, not headroom: with lazy growth plus
            # preemption, any request whose PEAK page set fits the
            # pool will eventually complete (the strongest claim can
            # reclaim every other page); one that cannot fit alone
            # can never be served and must be refused up front
            peak = pages_needed(
                min(need + r.max_new_tokens, self.max_len),
                self.hot_cap, self._page_size)
            if peak > self._pool_pages(n_slots):
                raise ValueError(
                    f"request {r.rid}: needs {peak} cold pages at its "
                    f"peak but the pool holds "
                    f"{self._pool_pages(n_slots)} — unservable even "
                    "with every other slot preempted; raise n_pages"
                )

    def start_session(
        self,
        requests: Sequence[Request],
        slots: Optional[int] = None,
        stop_token: Optional[int] = None,
        sync_every: Optional[int] = None,
        max_queue: Optional[int] = None,
        on_iteration: Optional[Callable[[_ServeCtx], None]] = None,
    ) -> _ServeCtx:
        """Validate ``requests`` and build a live serving session — the
        :class:`_ServeCtx` that ``run_iteration`` advances. ``serve()``
        is ``start_session`` + a ``run_iteration`` loop +
        ``finish_session``; the router holds one open session per
        replica and feeds it via ``submit_to_session``."""
        n_slots = slots or self.slots
        chunk = sync_every or self.sync_every
        chunked = self.prefill_chunk > 0 and self._chunked_capable()
        if max_queue is None:
            max_queue = self.max_queue
        for r in requests:
            self._validate_request(r, n_slots)
        # a fresh session owes nothing to rids of earlier sessions: a
        # stale cancel mark must not shoot down an unrelated request that
        # happens to reuse the rid (replica restarts reuse the engine)
        self._cancel_requested.clear()
        # output buffer sized by max_len (which already bounds any budget),
        # NOT by this batch's max budget — the buffer shape is baked into
        # the jitted step, and a varying out_cap would recompile the whole
        # decode graph per distinct value
        out_cap = self.max_len
        sched = SlotScheduler(n_slots, max_queue=max_queue)
        stats = ServeStats()
        finished: List[FinishedRequest] = []
        for r in requests:
            if not sched.submit(r):
                # backpressure: shed explicitly instead of queueing
                # without bound — the caller sees outcome "rejected"
                stats.rejected += 1
                finished.append(self._finish_queued(r, "rejected"))

        state = self._init_state(n_slots, out_cap)
        step = (self._get_spec_step(out_cap, stop_token) if self.spec
                else self._get_step(out_cap, stop_token))
        ctx = _ServeCtx(
            state=state,
            sched=sched,
            finished=finished,
            stats=stats,
            token_bytes=self._kv_token_bytes(),
            chunked=chunked,
            # host mirror of each slot's remaining budget: generation
            # progress is deterministic (one token per active step), so
            # the host can bound the next chunk without reading device
            # state — only stop tokens finish a slot earlier than this
            # mirror predicts. seq_mirror likewise upper-bounds the cache
            # length for page-growth sizing.
            remaining=[0] * n_slots,
            seq_mirror=[0] * n_slots,
            prefix_used=[0] * n_slots,
            # slots mid-prefill, carried ACROSS loop iterations: each
            # iteration streams at most `chunk` waves, then decodes, so
            # long prompts no longer stall every active slot until fully
            # cached
            prefilling={},
            slot_pages=[[] for _ in range(n_slots)],
            verified_len=[0] * n_slots,
            spec=self.spec,
            hot_cap=self.hot_cap,
            step_fn=step,
            chunk=chunk,
            on_iteration=on_iteration,
            # per-iteration wall time feeds the same StragglerMonitor
            # vocabulary the training plane uses; ServeStats summarizes
            # it at finish_session and the router polls `flagged` live
            monitor=StragglerMonitor(window=16, factor=4.0),
        )
        if self.paged:
            ctx.page_size = self._page_size
            ctx.pool = PagePool(self._pool_pages(n_slots))
            ctx.ptree = PrefixCache(ctx.pool, self.hot_cap, self._page_size)
            ctx.host_table = np.zeros((n_slots, self._pps), np.int32)
            # introspection handles for tests and benches: the refcount
            # ledger and prefix tree of the most recent serve() call
            self._last_pool, self._last_ptree = ctx.pool, ctx.ptree
        self._last_ctx = ctx
        return ctx

    def submit_to_session(self, ctx: _ServeCtx, req: Request) -> bool:
        """Dynamic admission into a live session (the router's entry
        point): same validation as ``start_session``, same backpressure
        contract — False means the bounded queue shed the request and
        the CALLER owns its terminal outcome."""
        self._validate_request(req, len(ctx.sched.slot_req))
        return ctx.sched.submit(req)

    def _admit_round(self, ctx: _ServeCtx, chunk: int) -> bool:
        """Admission phase of ``run_iteration``: fill every free slot we
        can — chunked (stream at most ``chunk`` prompt-chunk waves) or as
        whole same-length groups. Returns True when anything was
        admitted or prefilled."""
        sched = ctx.sched
        progress = False
        if ctx.chunked:
            fills = sched.next_fills()
            for s, req in fills:
                ctx.remaining[s] = req.max_new_tokens
            if self.paged and fills:
                progress |= self._admit_paged(ctx, fills)
            elif fills:
                for s, req in fills:
                    ctx.prefilling[s] = [req, 0]
                    ctx.seq_mirror[s] = req.prompt_len
                progress = True
            on_last = None
            if self.prefix_sharing:
                on_last = lambda st, s, r: self._record_prefix(  # noqa: E731
                    st, s, r, ctx.ptree, ctx.host_table
                )
            if self.spec:
                # every freshly admitted slot also prefills the draft
                # cache, always from offset 0 (the draft never shares
                # prefixes — it is private per-slot scratch)
                for s, (req, _off) in ctx.prefilling.items():
                    if s not in ctx.draft_prefilling:
                        ctx.draft_prefilling[s] = [req, 0]
            progress |= bool(ctx.prefilling) or bool(ctx.draft_prefilling)
            ctx.state = self._stream_chunks(
                ctx.state, len(sched.slot_req), ctx.prefilling, ctx.stats,
                max_waves=chunk, on_last=on_last,
                draft_prefilling=(ctx.draft_prefilling
                                  if self.spec else None),
            )
        else:
            while True:
                slots_idx, group = sched.next_group()
                if not group:
                    break
                ctx.state = self._admit(ctx.state, slots_idx, group)
                for s, req in zip(slots_idx, group):
                    ctx.remaining[s] = req.max_new_tokens
                    ctx.seq_mirror[s] = self._attempt_prompt_len(req)
                progress = True
        return progress

    def _advance_mirrors(self, ctx: _ServeCtx, decoding: List[int],
                         n_steps: int) -> None:
        """Advance the host budget/length mirrors of the ``decoding``
        slots past ``n_steps`` decode dispatches."""
        if not (self.spec and n_steps):
            for s in decoding:
                ctx.remaining[s] = max(ctx.remaining[s] - n_steps, 0)
                ctx.seq_mirror[s] = min(
                    ctx.seq_mirror[s] + n_steps, self.max_len)
            return
        # a speculative round emits a data-dependent 1..K tokens, so the
        # deterministic host mirrors no longer hold — refresh them from
        # the device at the sync point (the harvest reads `done` anyway),
        # then return the pages the rollback stranded past each slot's
        # real length so pool occupancy tracks acceptance, not the
        # funded worst case
        n_gen_dev = np.asarray(ctx.state.n_gen)
        seq_dev = np.asarray(ctx.state.seq_len)
        for s in decoding:
            req = ctx.sched.slot_req[s]
            if req is None:
                continue
            ctx.remaining[s] = max(
                int(req.max_new_tokens) - int(n_gen_dev[s]), 0)
            ctx.seq_mirror[s] = int(seq_dev[s])
            if not self.paged or not ctx.slot_pages[s]:
                continue
            keep = pages_needed(
                ctx.seq_mirror[s], self.hot_cap, self._page_size)
            extra = ctx.slot_pages[s][keep:]
            if extra:
                ctx.pool.decref(extra)
                del ctx.slot_pages[s][keep:]
                # unused table entries must hold a VALID page index
                # (PagedKVCache convention); the device copy may keep
                # stale entries — safe, because any row a future round
                # writes there is re-funded and re-installed by
                # _ensure_pages first
                ctx.host_table[s, keep:] = 0

    def _harvest(self, ctx: _ServeCtx, ripe: List[int]) -> None:
        """Retire the ``ripe`` (done) slots: read their outputs and
        ledgers, record their terminal records, free their pages."""
        n_gen = np.asarray(ctx.state.n_gen)
        seq_len = np.asarray(ctx.state.seq_len)
        out = np.asarray(ctx.state.out)
        ledger = {k: np.asarray(ctx.state.ledger[k]) for k in TRAFFIC_KEYS}
        drafted_dev = np.asarray(ctx.state.drafted) if self.spec else None
        accepted_dev = np.asarray(ctx.state.accepted) if self.spec else None
        for s in ripe:
            req = ctx.sched.retire(s)
            spec_kw = (
                dict(drafted=int(drafted_dev[s]),
                     accepted=int(accepted_dev[s]))
                if self.spec else {}
            )
            fin = self._build_finished(
                req, out[s, : n_gen[s]].copy(), int(seq_len[s]),
                {k: ledger[k][s] for k in TRAFFIC_KEYS},
                self._attempt_prompt_len(req), ctx.prefix_used[s],
                "finished", ctx.token_bytes, **spec_kw,
            )
            ctx.finished.append(fin)
            ctx.stats.record_spec(fin)
            self._cancel_requested.discard(req.rid)
            ctx.prefix_used[s] = 0
            ctx.remaining[s] = 0
            ctx.seq_mirror[s] = 0
            if self.paged:
                # pages free exactly when their last reader leaves
                ctx.pool.decref(ctx.slot_pages[s])
                ctx.slot_pages[s] = []
        idx = jnp.asarray(ripe, jnp.int32)
        ctx.state = ctx.state._replace(
            allocated=ctx.state.allocated.at[idx].set(False)
        )

    def run_iteration(self, ctx: _ServeCtx) -> bool:
        """One serving-loop iteration: sweep cancellations/expiries,
        admit into free slots, fund page growth, run one decode chunk,
        harvest finished slots, fire the hook, count the stall guard.
        Each phase is a span ``engine.<phase>`` and adds its host seconds
        to ``ctx.stats.host_s`` (``_phase``). Returns True when the
        iteration made progress. Call only while ``not ctx.sched.idle()``."""
        t0 = time.perf_counter()
        sched, chunk, step = ctx.sched, ctx.chunk, ctx.step_fn
        stats = ctx.stats
        self._iter_stats = stats
        with _phase(stats, "sweep"):
            progress = self._sweep_cancel_expire(ctx) > 0
        # -- admission: fill every free slot we can ----------------
        with _phase(stats, "admit"):
            progress |= self._admit_round(ctx, chunk)
            now = self._clock()
            for req in sched.slot_req:
                if req is not None and req.t_admit is None:
                    req.t_admit = now
        # -- fund mid-decode cold growth (may preempt) -------------
        if self.paged:
            # a speculative round transiently appends up to K rows
            # before rollback, so fund the worst-case advance —
            # _advance_mirrors returns what rollback strands
            with _phase(stats, "grow"):
                self._ensure_pages(
                    ctx, chunk * self.spec_k if self.spec else chunk)
        # -- decode chunk: no host syncs inside --------------------
        # clip the chunk so no dispatch runs past the earliest
        # budget-exhaustion among decoding slots (those steps would be
        # pure waste: the finished slot idles until the next sync);
        # slots still mid-prefill neither bound the chunk nor burn
        # budget — they ride through the decode dispatches inactive.
        # if every decoding slot has exhausted its budget mirror (e.g.
        # max_new_tokens=0 admissions) skip straight to harvest
        decoding = [
            s for s in sched.active_slots()
            if s not in ctx.prefilling and s not in ctx.draft_prefilling
        ]
        budgets = [ctx.remaining[s] for s in decoding
                   if ctx.remaining[s] > 0]
        n_steps = min([chunk] + budgets) if budgets else 0
        stats.decode_dispatches += n_steps
        for s in decoding:
            # slot s decodes in the first m dispatches, attending to
            # seq_mirror[s] + 1 .. seq_mirror[s] + m tokens
            m = min(ctx.remaining[s], n_steps)
            stats.decode_slot_steps += m
            stats.kv_tokens_attended += m * ctx.seq_mirror[s] + m * (m + 1) // 2
        with _phase(stats, "dispatch", n_steps=n_steps):
            for _ in range(n_steps):
                ctx.state = (step(self.params, self.draft_params, ctx.state)
                             if self.spec else step(self.params, ctx.state))
            self._advance_mirrors(ctx, decoding, n_steps)
        progress |= n_steps > 0
        # -- SDC scrub: detect -> contain -> repair, BEFORE harvest —
        # a ripe slot forces a scrub, so no request ever retires with
        # an unverified tail (engine._scrub, "harvest gating")
        if self.integrity is not None:
            with _phase(stats, "scrub"):
                self._scrub(ctx)
        # -- sync point: harvest finished slots --------------------
        # (the slot table mirrors `allocated`, so only the small
        # `done` mask crosses the device boundary here)
        with _phase(stats, "sync"):
            done = np.asarray(ctx.state.done)
        with _phase(stats, "harvest"):
            # every slot past its prefill has its first token by now
            now = self._clock()
            for s in sched.active_slots():
                req = sched.slot_req[s]
                if (req.t_first is None and s not in ctx.prefilling
                        and s not in ctx.draft_prefilling):
                    req.t_first = now
            ripe = [s for s in decoding if done[s]]
            if ripe:
                progress = True
                self._harvest(ctx, ripe)
        # the hook sees the 0-based index of the iteration that just
        # completed (chaos schedules / tests key off it)
        if ctx.on_iteration is not None:
            ctx.on_iteration(ctx)
        ctx.stats.iterations += 1
        ctx.iteration += 1
        # chaos sleeps injected through the hook count into the iteration
        # time on purpose — that IS the straggler signal
        ctx.monitor.record(ctx.iteration - 1, time.perf_counter() - t0)
        # -- stall guard -------------------------------------------
        # nothing prefilled, decoded, admitted, harvested or swept
        # for many consecutive iterations: the queue head cannot be
        # funded even with the pool fully reclaimed (with the
        # feasibility check above this is unreachable unless an
        # external actor — e.g. a chaos hold — pins pages for good;
        # a bounded hold just rides through the tolerance window)
        ctx.stall = 0 if progress else ctx.stall + 1
        if ctx.stall >= _STALL_LIMIT and not sched.idle():
            head = (min(sched.queue, key=lambda r: r.claim)
                    if sched.queue else None)
            raise PagePoolError(
                "page pool exhausted and unreclaimable: "
                f"{len(sched.queue)} queued "
                f"(head rid={getattr(head, 'rid', None)}), "
                f"{ctx.pool.available() if ctx.pool else 0} pages "
                f"free of {ctx.pool.n_pages if ctx.pool else 0} — "
                "raise n_pages"
            )
        return progress

    def finish_session(self, ctx: _ServeCtx) -> List[FinishedRequest]:
        """Seal a session: summarize the iteration-time distribution into
        its :class:`ServeStats` and publish them as ``last_stats``.
        Returns the session's terminal records."""
        if ctx.monitor is not None and ctx.monitor.times:
            ctx.stats.iter_p50 = float(np.median(ctx.monitor.times))
            ctx.stats.iter_max = float(max(ctx.monitor.times))
            ctx.stats.straggler_flags = len(ctx.monitor.flagged)
        self.last_stats = ctx.stats
        return ctx.finished

    def serve(
        self,
        requests: Sequence[Request],
        slots: Optional[int] = None,
        stop_token: Optional[int] = None,
        sync_every: Optional[int] = None,
        max_queue: Optional[int] = None,
        on_iteration: Optional[Callable[[_ServeCtx], None]] = None,
    ) -> List[FinishedRequest]:
        """Serve ``requests`` through continuous batching; returns one
        terminal :class:`FinishedRequest` PER submitted request, in
        completion order (sort by ``rid`` if you need submission order).
        ``FinishedRequest.outcome`` distinguishes normal completion from
        cancellation, deadline expiry and backpressure shedding.

        The decode hot loop issues exactly one jitted dispatch per token
        and never reads device memory; host synchronization happens only
        every ``sync_every`` steps, to retire finished slots and admit
        queued prompts into the freed rows. With ``prefill_chunk`` set
        (and a capable arch), admission streams fixed-size prompt chunks
        into the freed slots instead of whole same-length groups — one
        prefill compilation total, mixed lengths admit immediately.

        Under paged serving, page-pool pressure degrades instead of
        failing: admission and mid-decode growth reclaim pages by LRU
        tree eviction, then by preempting strictly weaker slots
        (recompute-from-prefix; see the module docstring). ``max_queue``
        bounds the admission queue (overflow is shed as ``rejected``);
        ``on_iteration(ctx)`` runs after every loop iteration — the
        fault-injection/invariant hook (``serving/chaos.py``).

        With a :class:`PreemptionGuard` attached (``Engine(guard=...)``),
        a raised flag drains gracefully: the loop finishes its current
        iteration, folds every active slot's emitted tokens into its
        request (bit-exact recompute-from-prefix on re-submission) and
        returns early; the evacuated requests are in ``last_drained``
        and do NOT get terminal records from this call."""
        ctx = self.start_session(
            requests, slots=slots, stop_token=stop_token,
            sync_every=sync_every, max_queue=max_queue,
            on_iteration=on_iteration,
        )
        self.last_drained = None
        while not ctx.sched.idle():
            self.run_iteration(ctx)
            if self.guard is not None and self.guard.requested:
                self.last_drained, _ = self.drain_session(ctx)
                self.guard.requested = False  # consumed: drained once
                break
        return self.finish_session(ctx)

    # ------------------------------------------------------------------
    # session evacuation: drain (cooperative) / abandon (after a crash)
    # — the migration primitives serving/replica.py + router.py build on
    # ------------------------------------------------------------------

    def drain_session(
        self, ctx: _ServeCtx, with_handoffs: bool = False,
    ) -> "tuple[List[Request], Dict[int, bytes]]":
        """Evacuate a LIVE session: every active slot is preempted
        through the PR 7 fold-in path (emitted tokens fold into the
        prompt, ``orig_prompt_len`` marks the seam, pages decref), then
        the queue is emptied. Returns the evacuated requests in claim
        order — resubmitting them (here or on another replica) continues
        generation bit-exactly for greedy sampling.

        With ``with_handoffs=True`` on a paged engine, each decoding
        slot's KV rows are additionally serialized
        (``kv_cache.pack_slot_state``, storage dtype + checksums) BEFORE
        the fold, keyed by rid — the warm-migration payload a receiving
        replica can seed its prefix cache from (``import_handoff``) so
        only the post-prefix suffix recomputes. Mid-prefill slots carry
        no handoff (they migrate cold; they lose at most one chunk)."""
        handoffs: Dict[int, bytes] = {}
        for s in list(ctx.sched.active_slots()):
            req = ctx.sched.slot_req[s]
            if (with_handoffs and self.paged and s not in ctx.prefilling
                    and s not in ctx.draft_prefilling):
                handoffs[req.rid] = self.export_slot(ctx, s)
            self._preempt_slot(ctx, s)
        drained = sorted(ctx.sched.queue, key=lambda r: r.claim)
        ctx.sched.queue.clear()
        ctx.drained = drained
        return drained, handoffs

    def abandon_session(self, ctx: _ServeCtx) -> List[Request]:
        """Host-side teardown of a DEAD session (the device state is
        lost — a killed replica): release every slot's page claims and
        the queue, returning the orphaned requests in claim order. No
        device dispatch and no token folding happens — emitted tokens
        must come from the router's journal (Replica.journal), not from
        a dead device. After this the session's pool reconciles to
        tree-only references and ``ctx.sched`` is idle."""
        orphans: List[Request] = []
        for s in list(ctx.sched.active_slots()):
            req = ctx.sched.retire(s)
            ctx.prefilling.pop(s, None)
            ctx.draft_prefilling.pop(s, None)
            if ctx.slot_pages[s]:
                ctx.pool.decref(ctx.slot_pages[s])
                ctx.slot_pages[s] = []
            ctx.prefix_used[s] = 0
            ctx.remaining[s] = 0
            ctx.seq_mirror[s] = 0
            orphans.append(req)
        orphans.sort(key=lambda r: r.claim)
        orphans += sorted(ctx.sched.queue, key=lambda r: r.claim)
        ctx.sched.queue.clear()
        return orphans

    def export_slot(self, ctx: _ServeCtx, s: int) -> bytes:
        """Serialize slot ``s``'s KV rows across every cache stack into
        one checksummed payload (``kv_cache.pack_slot_state``) — the
        warm-migration wire format. Rows ship in the tier storage dtype:
        with ``kv_fp8`` on, one byte per element."""
        states = {
            k: kv_cache.export_slot_state(c, s)
            for k, c in ctx.state.cache.items()
        }
        return kv_cache.pack_slot_state(states, self._page_size)

    def import_handoff(self, ctx: _ServeCtx, tokens, blob: bytes) -> int:
        """Receiver side of warm migration: verify + unpack a serialized
        slot state and seed this session's prefix cache with it, so the
        follow-up ``submit_to_session`` of the folded request prefix-
        matches instead of recomputing. Returns the number of prompt
        tokens seeded (0 = nothing usable — caller proceeds cold, which
        is always correct, just slower).

        The full hot tier plus every FULL cold page of the payload is
        adopted: cold rows are written into freshly allocated pool pages
        and the tree's ``insert`` adopts them by id; the hot rows are
        written through the same ``save_hot`` page layout a local
        snapshot would use. The partial trailing page (if any) is NOT
        seeded — the prefix match is capped at ``len(tokens) - 1``
        anyway, and chunked prefill recomputes the tail bit-exactly.
        Raises :class:`HandoffError` when the payload fails verification
        (corrupted/torn transfer) — the caller falls back to cold."""
        if not (self.paged and self.prefix_sharing and ctx.ptree):
            return 0
        states = kv_cache.unpack_slot_state(blob)
        if set(states) != set(ctx.state.cache):
            raise HandoffError(
                f"handoff cache keys {sorted(states)} do not match this "
                f"engine's {sorted(ctx.state.cache)}")
        toks = np.asarray(tokens, np.int32)
        hc, ps = self.hot_cap, self._page_size
        length = min(st["length"] for st in states.values())
        if length < len(toks):
            raise HandoffError(
                f"handoff covers {length} tokens but the folded request "
                f"carries {len(toks)} — torn capture")
        if len(toks) <= hc:
            return 0  # nothing past the hot tier: cold re-prefill is cheap
        kf = (len(toks) - hc) // ps  # full cold pages only
        ctx.ptree.evict_for(kf)
        pages = ctx.pool.alloc(kf) if kf else []
        if pages is None:
            return 0  # pool too tight to host the handoff: go cold
        new_cache = {}
        for key, st in states.items():
            cache = ctx.state.cache[key]
            if kf:
                ck, cv = st["cold_k"], st["cold_v"]
                tail = ck.shape[2:]
                kp = ck[:, : kf * ps].reshape(
                    (ck.shape[0], kf, ps) + tail)
                vp = cv[:, : kf * ps].reshape(
                    (cv.shape[0], kf, ps) + tail)
                cache = kv_cache.write_pool_pages(cache, pages, kp, vp)
            new_cache[key] = cache
        ctx.state = ctx.state._replace(cache=new_cache)

        def save(ids):
            # hot payload lands in the tree's snapshot pages using the
            # exact save_hot layout: hot row i -> page ids[i // ps],
            # row i % ps — so a later admission restores it the same
            # way it restores a locally saved snapshot
            arr = np.full((max(ctx.ptree.n_hot_pages, 1),), -1, np.int32)
            arr[: len(ids)] = ids
            cache2 = {}
            for key, st in states.items():
                hk, hv = st["hot_k"], st["hot_v"]
                tail = hk.shape[2:]
                nhp = len(ids)
                pad = nhp * ps - hk.shape[1]
                if pad:
                    z = np.zeros((hk.shape[0], pad) + tail, hk.dtype)
                    hk = np.concatenate([hk, z], axis=1)
                    hv = np.concatenate([hv, z], axis=1)
                kp = hk.reshape((hk.shape[0], nhp, ps) + tail)
                vp = hv.reshape((hv.shape[0], nhp, ps) + tail)
                cache2[key] = kv_cache.write_pool_pages(
                    ctx.state.cache[key], np.asarray(ids, np.int32), kp, vp)
            ctx.state = ctx.state._replace(cache=cache2)

        ok = ctx.ptree.insert(toks, np.asarray(pages, np.int32), save)
        # the tree holds its own refs on whatever it adopted; our
        # allocation refs retire either way (failed/duplicate inserts
        # free the pages right here)
        if pages:
            ctx.pool.decref(pages)
        return hc + kf * ps if ok else 0

    # ------------------------------------------------------------------
    # aligned-batch convenience API (launchers / examples / benchmarks)
    # ------------------------------------------------------------------

    def generate(
        self,
        prompts: jax.Array,  # (b, prompt_len) int32
        max_new_tokens: int = 32,
        patches: Optional[jax.Array] = None,
        stop_token: Optional[int] = None,
    ) -> GenerationResult:
        """Aligned-batch generation: one slot per prompt row, all admitted
        in a single prefill. Semantics match the seed lock-step engine —
        same tokens for greedy sampling — but stop handling is per-slot
        (a finished row retires instead of gating the whole batch)."""
        t0 = time.time()
        b = prompts.shape[0]
        prompts_np = np.asarray(prompts, np.int32)
        patches_np = None if patches is None else np.asarray(patches)
        reqs = [
            Request(
                rid=i, tokens=prompts_np[i], max_new_tokens=max_new_tokens,
                patches=None if patches_np is None else patches_np[i],
            )
            for i in range(b)
        ]
        finished = self.serve(reqs, slots=b, stop_token=stop_token)
        finished.sort(key=lambda f: f.rid)
        rows = [
            np.concatenate(
                [
                    f.tokens,
                    np.full(
                        (max_new_tokens - len(f.tokens),), PAD_TOKEN, np.int32
                    ),
                ]
            )
            for f in finished
        ]
        traffic = {k: 0 for k in TRAFFIC_KEYS}
        for f in finished:
            for k in TRAFFIC_KEYS:
                traffic[k] += f.traffic[k]
        return GenerationResult(
            tokens=jnp.asarray(np.stack(rows), jnp.int32),
            steps=max((f.steps for f in finished), default=0),
            traffic=traffic,
            wall_s=time.time() - t0,
            steps_per_row=[f.steps for f in finished],
        )

    def expected_reduction(self, seq_len: int) -> float:
        """Closed-form DR-eDRAM prediction for a full generation to seq_len."""
        return dr_edram.closed_form_reduction(seq_len, self.hot_cap)
