"""Two-tier (DR eDRAM-style) KV cache with per-slot lengths (paper §IV).

BitROM buffers the first ``hot_cap`` tokens of a sequence on-die (DR eDRAM)
and leaves the tail in external DRAM. The TPU adaptation keeps the same
*structure* — a small pinned "hot" buffer for early tokens plus a large
"cold" buffer — because the structure is what produces the access-traffic
win (early tokens are read at every decode step; see ``dr_edram.py``).

The cache is a pytree of fixed-shape arrays (jit/scan friendly):

  hot_k/hot_v   : (batch, hot_cap, ...)      early tokens
  cold_k/cold_v : (batch, cold_cap, ...)     the rest
  lengths       : (batch,) int32             tokens written, per slot

``...`` is whatever a layer caches per token, stored flat: (n_kv_heads *
head_dim,) for GQA/MQA — the flash kernels' (rows, g·hd) operand layout,
so the decode step hands the stored tiers to the kernel with no relayout
— and (d_latent,) for MLA latents. Appends take new rows in either form
((…, g, hd) or (…, g·hd): row-major bytes are the same) and route on
position; attention runs per-tier and combines with a numerically-stable
streaming softmax, so no concat of the two tiers is ever materialized.

The model keeps one cache per layer stack, every leaf with a leading
layer axis. The decode step writes layer ``l``'s new row in place
(``append_decode(..., layer=l)``) and the flash-decode kernel reads
layer ``l`` of the stack in place, so no per-layer copy of a tier is
ever made.

Continuous batching (serving/scheduler.py) treats each batch row as a
*slot*: sequences of different lengths decode side by side, so every
operation is vectorized over ``lengths``, and the decode-path appends
(``append_decode`` / ``append_decode_ring``) take an optional
``active: (batch,) bool`` mask — inactive slots (retired / not yet
admitted) neither write their tier buffers nor advance their length.
Bulk ``append`` takes ``valid`` (per-slot count of real rows — chunked
prefill masks its final partial chunk with it) and ``ring`` (sliding
-window cold layout); both default to the legacy whole-chunk append.

``PagedKVCache`` is the paged variant of the cold tier: instead of one
contiguous (batch, cold_cap, ...) row per slot, cold tokens live in a
shared physical *page pool* (n_pages, page_size, ...) and each slot owns
an int32 ``page_table`` row mapping its logical cold pages to pool pages.
Slot j's cold position c lives at pool page ``page_table[j, c // ps]``,
row ``c % ps``. The hot tier stays contiguous/pinned per slot (the
DR-eDRAM buffer of the paper). Pages let the serving layer share one
physical copy of a common prompt prefix across slots (refcounted radix
tree, serving/paging.py) — ``as_tiered`` gathers the paged cold tier
back into the contiguous layout, which is how every XLA reference path
here supports paging with bit-exact parity to the contiguous cache.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class TieredKVCache(NamedTuple):
    hot_k: jax.Array
    hot_v: jax.Array
    cold_k: jax.Array
    cold_v: jax.Array
    lengths: jax.Array  # (batch,) int32: tokens currently cached per slot

    @property
    def hot_cap(self) -> int:
        return self.hot_k.shape[1]

    @property
    def cold_cap(self) -> int:
        return self.cold_k.shape[1]

    @property
    def capacity(self) -> int:
        return self.hot_cap + self.cold_cap


def init_cache(
    batch: int,
    hot_cap: int,
    cold_cap: int,
    kv_shape: Sequence[int],
    dtype=jnp.bfloat16,
) -> TieredKVCache:
    shape_hot = (batch, hot_cap) + tuple(kv_shape)
    shape_cold = (batch, cold_cap) + tuple(kv_shape)
    return TieredKVCache(
        hot_k=jnp.zeros(shape_hot, dtype),
        hot_v=jnp.zeros(shape_hot, dtype),
        cold_k=jnp.zeros(shape_cold, dtype),
        cold_v=jnp.zeros(shape_cold, dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


class PagedKVCache(NamedTuple):
    """Tiered cache with a paged cold tier (see module docstring).

    ``hot_k/hot_v`` are identical to ``TieredKVCache`` (contiguous,
    per-slot). The cold tier is a shared pool: ``pool_k/pool_v`` hold
    ``n_pages`` pages of ``page_size`` tokens each, and ``page_table``
    (batch, pages_per_slot) int32 maps each slot's logical cold pages to
    pool pages. Unused table entries must hold a *valid* pool index
    (convention: 0) — reads are masked by ``lengths``, never by the
    table, so sentinel values out of range would break the gather.
    Ring/SWA layouts are not supported in paged form.
    """

    hot_k: jax.Array
    hot_v: jax.Array
    pool_k: jax.Array  # (n_pages, page_size, ...)
    pool_v: jax.Array
    page_table: jax.Array  # (batch, pages_per_slot) int32
    lengths: jax.Array  # (batch,) int32

    @property
    def hot_cap(self) -> int:
        return self.hot_k.shape[1]

    @property
    def page_size(self) -> int:
        return self.pool_k.shape[1]

    @property
    def n_pages(self) -> int:
        return self.pool_k.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def cold_cap(self) -> int:
        return self.pages_per_slot * self.page_size

    @property
    def capacity(self) -> int:
        return self.hot_cap + self.cold_cap


def init_paged_cache(
    batch: int,
    hot_cap: int,
    cold_cap: int,
    kv_shape: Sequence[int],
    dtype=jnp.bfloat16,
    page_size: int = 256,
    n_pages: Optional[int] = None,
) -> PagedKVCache:
    """Fresh paged cache. ``cold_cap`` rounds up to whole pages; the pool
    defaults to exactly one private page set per slot and the page table
    to the identity mapping (slot b's page j = pool page b * pps + j), so
    an unshared paged cache is the contiguous cache re-addressed."""
    assert cold_cap > 0, "paged cache needs a non-empty cold tier"
    pps = -(-cold_cap // page_size)
    if n_pages is None:
        n_pages = batch * pps
    assert n_pages >= 1
    table = (jnp.arange(batch, dtype=jnp.int32)[:, None] * pps
             + jnp.arange(pps, dtype=jnp.int32)[None])
    table = jnp.minimum(table, n_pages - 1)
    shape_hot = (batch, hot_cap) + tuple(kv_shape)
    shape_pool = (n_pages, page_size) + tuple(kv_shape)
    return PagedKVCache(
        hot_k=jnp.zeros(shape_hot, dtype),
        hot_v=jnp.zeros(shape_hot, dtype),
        pool_k=jnp.zeros(shape_pool, dtype),
        pool_v=jnp.zeros(shape_pool, dtype),
        page_table=table,
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def cold_view(cache: PagedKVCache) -> tuple:
    """Gather the paged cold tier into contiguous (batch, cold_cap, ...)
    k/v arrays — the indirection the flash kernels do per S-block, done
    at once for the XLA reference paths."""
    b = cache.page_table.shape[0]
    ck = cache.pool_k[cache.page_table]  # (b, pps, ps, ...)
    cv = cache.pool_v[cache.page_table]
    tail = cache.pool_k.shape[2:]
    return (ck.reshape((b, cache.cold_cap) + tail),
            cv.reshape((b, cache.cold_cap) + tail))


def as_tiered(cache: PagedKVCache) -> TieredKVCache:
    """Contiguous view of a paged cache (gathers the cold tier)."""
    ck, cv = cold_view(cache)
    return TieredKVCache(cache.hot_k, cache.hot_v, ck, cv, cache.lengths)


def append(
    cache: TieredKVCache,
    k_new: jax.Array,
    v_new: jax.Array,
    valid: Optional[jax.Array] = None,
    ring: bool = False,
    layer: Optional[jax.Array] = None,
) -> TieredKVCache:
    """Append up to ``t_new`` tokens (batch, t_new, ...). Early positions
    land hot.

    Each slot appends starting at its own ``lengths[b]``, so the same call
    serves aligned prefill (all lengths equal), per-slot refill and the
    chunked-prefill placement (serving/engine.py): ``valid`` (b,) int32
    caps how many of the ``t_new`` rows are real per slot — rows past a
    slot's valid count (chunk padding) are neither written nor counted,
    and lengths advance by ``valid``. Routing is data-independent given
    the traced lengths: every new token goes to the hot tier if its
    absolute position < hot_cap, else cold — or, with ``ring=True``
    (sliding-window archs), to cold slot (pos - hot_cap) % cold_cap. In
    ring mode only each slot's last ``cold_cap`` valid tokens write (the
    earlier ones would be evicted within this very call; keeping a single
    writer per ring slot keeps the one-hot scatter exact).

    With ``layer`` the cache is layer-stacked and only layer ``layer``'s
    rows and lengths change, in place (``_append_at``).
    """
    return _on_layer(_append_at, cache, layer, k_new, v_new, valid, ring)


def as_stack(cache, layer):
    """(layer-stacked cache, layer index) for a cache and the layer the
    caller works on: ``layer`` None means an unstacked cache, lifted to a
    stack of one (a leading unit axis, no copy) at layer 0."""
    if layer is None:
        return jax.tree.map(lambda a: a[None], cache), jnp.zeros((), jnp.int32)
    return cache, layer


def _on_layer(fn, cache, layer, *args):
    """``fn(stack, layer, *args)`` -> stack, unstacked again when the
    cache came unstacked (``as_stack``)."""
    stack, at = as_stack(cache, layer)
    out = fn(stack, at, *args)
    return out if layer is not None else jax.tree.map(lambda a: a[0], out)


def _append_at(stack, layer, k_new, v_new, valid, ring: bool):
    """``append`` into layer ``layer`` of a layer-stacked cache. Paged
    cold rows scatter into the stacked pool at page-table-routed rows
    (``mode="drop"`` skips the rows a slot does not write; the serving
    layer gives each writable page one writer slot, so indices never
    collide). The hot tier and a contiguous cold tier take a one-hot
    update of the layer's tier, written back in place: a pass over one
    layer's tier, never a copy of the pool."""
    k_new = _rows(k_new, 2, stack.hot_k, stacked=True)
    v_new = _rows(v_new, 2, stack.hot_v, stacked=True)
    t_new = k_new.shape[1]
    start = stack.lengths[layer]  # (b,)
    t_idx = jnp.arange(t_new, dtype=jnp.int32)[None]  # (1, t)
    pos = start[:, None] + t_idx  # (b, t)
    if valid is None:
        vmask = jnp.ones(pos.shape, bool)
        n_new = jnp.full_like(start, t_new)
    else:
        n_new = valid.astype(jnp.int32)
        vmask = t_idx < n_new[:, None]

    def onehot_put(tier, new, tier_pos, write):
        # tier_pos: (b, t) position within the tier; write: (b, t) bool
        cap = tier.shape[2]
        if cap == 0:  # zero-size tier (e.g. SWA: hot_cap=0) — nothing to write
            return tier
        idx = jnp.clip(tier_pos, 0, cap - 1)
        onehot = (jax.nn.one_hot(idx, cap, dtype=tier.dtype)
                  * write.astype(tier.dtype)[..., None])  # (b, t, cap)
        upd = jnp.einsum("btc,bt...->bc...", onehot, new.astype(tier.dtype))
        written = jnp.einsum("btc->bc", onehot) > 0
        mask = written.reshape(written.shape + (1,) * (upd.ndim - 2))
        return tier.at[layer].set(jnp.where(mask, upd, tier[layer]))

    hot_cap = stack.hot_k.shape[2]
    in_hot = (pos < hot_cap) & vmask
    kw = dict(hot_k=onehot_put(stack.hot_k, k_new, pos, in_hot),
              hot_v=onehot_put(stack.hot_v, v_new, pos, in_hot))
    in_cold = (pos >= hot_cap) & vmask
    cold_pos = pos - hot_cap
    if isinstance(stack, PagedKVCache):
        assert not ring, "ring layout is not supported for paged caches"
        n_pages, ps = stack.pool_k.shape[1:3]
        pg = jnp.clip(cold_pos // ps, 0, stack.page_table.shape[2] - 1)
        page = jnp.take_along_axis(stack.page_table[layer], pg, axis=1)
        page = jnp.where(in_cold, page, n_pages)

        def put_pool(pool, new):
            return pool.at[layer, page, cold_pos % ps].set(
                new.astype(pool.dtype), mode="drop")

        kw.update(pool_k=put_pool(stack.pool_k, k_new),
                  pool_v=put_pool(stack.pool_v, v_new))
    else:
        cold_cap = stack.cold_k.shape[2]
        if ring and cold_cap:
            cold_pos = cold_pos % cold_cap
            # single writer per ring slot: only the last cold_cap valid rows
            in_cold &= (n_new[:, None] - 1 - t_idx) < cold_cap
        kw.update(cold_k=onehot_put(stack.cold_k, k_new, cold_pos, in_cold),
                  cold_v=onehot_put(stack.cold_v, v_new, cold_pos, in_cold))
    kw["lengths"] = stack.lengths.at[layer].set(start + n_new)
    return stack._replace(**kw)


def _rows(new: jax.Array, n_lead: int, tier: jax.Array, stacked: bool = False):
    """New rows ``(*lead, g, hd)`` or ``(*lead, g·hd)`` in the tier's
    stored per-token shape (a row-major reshape: the bytes are the same)."""
    return new.reshape(new.shape[:n_lead] + tier.shape[3 if stacked else 2:])


def _append_one_at(stack, layer, k_new, v_new, active, ring: bool):
    """Decode append into layer ``layer`` of a layer-stacked cache: one
    row per slot, scattered in place (rows a slot does not write get an
    out-of-range index, which ``mode="drop"`` skips). The cost is the b
    rows written, never the tier; under a donated carry the stack is
    updated where it lives."""
    pos = stack.lengths[layer]  # (b,)
    b = pos.shape[0]
    act = jnp.ones((b,), bool) if active is None else active.astype(bool)
    slot = jnp.arange(b, dtype=jnp.int32)
    hot_cap = stack.hot_k.shape[2]
    in_hot = pos < hot_cap

    def put(tier, new, idx, write):
        cap = tier.shape[2]
        if cap == 0:  # zero-size tier (e.g. SWA: hot_cap=0) — nothing to write
            return tier
        rows = jnp.where(write & act, idx, cap)
        new = _rows(new, 1, tier, stacked=True).astype(tier.dtype)
        return tier.at[layer, slot, rows].set(new, mode="drop")

    hot_idx = jnp.clip(pos, 0, max(hot_cap - 1, 0))
    kw = dict(hot_k=put(stack.hot_k, k_new, hot_idx, in_hot),
              hot_v=put(stack.hot_v, v_new, hot_idx, in_hot))
    cold_pos = pos - hot_cap
    if isinstance(stack, PagedKVCache):
        assert not ring, "ring layout is not supported for paged caches"
        n_pages, ps = stack.pool_k.shape[1:3]
        pg = jnp.clip(cold_pos // ps, 0, stack.page_table.shape[2] - 1)
        page = jnp.take_along_axis(stack.page_table[layer], pg[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(~in_hot & act, page, n_pages)

        def put_pool(pool, new):
            new = _rows(new, 1, pool, stacked=True).astype(pool.dtype)
            return pool.at[layer, page, cold_pos % ps].set(new, mode="drop")

        kw.update(pool_k=put_pool(stack.pool_k, k_new),
                  pool_v=put_pool(stack.pool_v, v_new))
    else:
        cold_cap = stack.cold_k.shape[2]
        cold_idx = (cold_pos % max(cold_cap, 1) if ring
                    else jnp.clip(cold_pos, 0, max(cold_cap - 1, 0)))
        kw.update(cold_k=put(stack.cold_k, k_new, cold_idx, ~in_hot),
                  cold_v=put(stack.cold_v, v_new, cold_idx, ~in_hot))
    kw["lengths"] = stack.lengths.at[layer].set(pos + act.astype(jnp.int32))
    return stack._replace(**kw)


def layer_view(stack, layer):
    """Layer ``layer`` of a layer-stacked cache, as an unstacked cache —
    a copy of the layer's tiers, taken only on paths that cannot read the
    stack in place (the XLA reference, MLA decode, whole-prompt
    prefill)."""
    return jax.tree.map(lambda a: a[layer], stack)


def append_decode(
    cache: TieredKVCache,
    k_new: jax.Array,
    v_new: jax.Array,
    active: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> TieredKVCache:
    """Fast path for decode: append exactly one token (batch, ...) per slot.

    ``active`` (batch,) bool gates the write per slot: inactive slots keep
    their buffers and length untouched (continuous-batching retirement).
    With ``layer`` the cache is layer-stacked and only layer ``layer``'s
    rows and lengths change, in place.
    """
    return _on_layer(_append_one_at, cache, layer, k_new, v_new, active,
                     False)


def append_decode_ring(
    cache: TieredKVCache,
    k_new: jax.Array,
    v_new: jax.Array,
    active: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> TieredKVCache:
    """Decode append with a *ring-buffer* cold tier (sliding-window archs).

    Position p ≥ hot_cap lands at cold slot (p - hot_cap) % cold_cap, so the
    cold tier holds exactly the last ``cold_cap`` tokens (SWA window) and
    early tokens are evicted — DR tiering uses hot_cap=0 here (DESIGN.md §4).
    ``layer`` as in ``append_decode``.
    """
    return _on_layer(_append_one_at, cache, layer, k_new, v_new, active,
                     True)


# ---------------------------------------------------------------------------
# Tiered attention read: per-tier partial attention + streaming-softmax merge
# (never concatenates the tiers — the "hot" tier stays a separate buffer).
# ---------------------------------------------------------------------------


def _upcast(x):
    """fp8 tiers compute in bf16 (avoids materializing a 4x f32 copy of the
    whole cache — observed as multi-GiB temp on the decode dry-run);
    everything else upcasts to f32 for exactness."""
    if x.dtype == jnp.float8_e4m3fn:
        return x.astype(jnp.bfloat16)
    return x.astype(jnp.float32)


def _valid_masks(cache: TieredKVCache):
    """Per-slot validity of each tier position: (b, hot_cap), (b, cold_cap).

    The cold formula clamps at cold_cap, which is correct for both the
    linear layout (lengths never exceed capacity) and the ring layout
    (every slot is valid once the window has wrapped).
    """
    lengths = cache.lengths  # (b,)
    hot_valid = jnp.arange(cache.hot_cap)[None] < lengths[:, None]
    n_cold = jnp.clip(lengths - cache.hot_cap, 0, cache.cold_cap)
    cold_valid = jnp.arange(cache.cold_cap)[None] < n_cold[:, None]
    return hot_valid, cold_valid


def _tier_partial(q, k, v, valid, scale):
    """Partial attention over one tier.

    q: (b, h, d); k/v: (b, s, g·d) as stored, or (b, s, g, d), with g =
    kv heads (h = g * rep);
    valid: (b, s) bool. Returns (numerator (b,h,d), denom (b,h), max (b,h)).
    """
    b, s = k.shape[:2]
    h, d = q.shape[1:]
    g = math.prod(k.shape[2:]) // d
    k = k.reshape((b, s, g, d))  # stored (b, s, g·d) -> (b, s, g, d)
    v = v.reshape((b, s, g, math.prod(v.shape[2:]) // g))
    if s == 0:  # zero-capacity tier: neutral element of the streaming merge
        dv = v.shape[-1]
        return (
            jnp.zeros((b, h, dv), jnp.float32),
            jnp.zeros((b, h), jnp.float32),
            jnp.full((b, h), jnp.finfo(jnp.float32).min),
        )
    rep = h // g
    qg = q.reshape(b, g, rep, d).astype(jnp.float32)
    kf = _upcast(k)
    # invalid rows get p = 0, but 0 * NaN = NaN: a non-finite value in a
    # masked row (stale bytes, an aliased padding page) must contribute
    # nothing, so zero it like the flash kernel's v_safe does
    vf = jnp.where(valid[:, :, None, None], _upcast(v), 0.0)
    logits = jnp.einsum(
        "bgrd,bsgd->bgrs", qg.astype(kf.dtype), kf, preferred_element_type=jnp.float32
    ) * scale
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(valid[:, None, None, :], logits, neg)
    m = jnp.max(logits, axis=-1)  # (b,g,r)
    # guard fully-invalid tiers: exp(neg - neg) would be 1; zero them via mask
    p = jnp.exp(logits - m[..., None]) * valid[:, None, None, :]
    denom = jnp.sum(p, axis=-1)  # (b,g,r)
    num = jnp.einsum("bgrs,bsgd->bgrd", p.astype(vf.dtype), vf,
                     preferred_element_type=jnp.float32)  # (b,g,r,d)
    return num.reshape(b, h, d), denom.reshape(b, h), m.reshape(b, h)


def tiered_decode_attention(
    q: jax.Array,
    cache: TieredKVCache,
    scale: float | None = None,
) -> jax.Array:
    """One-token attention over both tiers. q: (b, h, d) -> (b, h, d).

    Validity is per slot (``cache.lengths``), so mixed-length batches each
    attend to exactly their own prefix. A slot with length 0 (unadmitted)
    returns zeros. Ring-buffer cold tiers (SWA) need no flag: the clamped
    validity formula in ``_valid_masks`` covers the wrapped layout, and
    attention is permutation-invariant over KV positions — call sites
    that want to state their layout use the flash-decode entry points
    (``kernels/flash_decode.py``), for which this function is the XLA
    reference path.
    """
    if isinstance(cache, PagedKVCache):
        cache = as_tiered(cache)
    d = q.shape[-1]
    scale = scale if scale is not None else d**-0.5
    hot_valid, cold_valid = _valid_masks(cache)

    n1, d1, m1 = _tier_partial(q, cache.hot_k, cache.hot_v, hot_valid, scale)
    n2, d2, m2 = _tier_partial(q, cache.cold_k, cache.cold_v, cold_valid, scale)

    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m) * (d1 > 0)
    a2 = jnp.exp(m2 - m) * (d2 > 0)
    num = n1 * a1[..., None] + n2 * a2[..., None]
    den = d1 * a1 + d2 * a2
    return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)


def tiered_decode_attention_latent(
    q: jax.Array,  # (b, h, D) — D = latent + rope dims
    cache: TieredKVCache,
    value_dim: int,
    scale: float,
) -> jax.Array:
    """MLA absorbed-form attention over a tiered *latent* cache.

    The cache k-slot holds (c_kv ‖ k_rope) per token, shape (b, s, D); the
    v-slot is empty (0-dim) — values are the first ``value_dim`` dims of the
    k-slot (the latent), so the latent is stored exactly once. Returns the
    per-head latent context (b, h, value_dim). Validity is per slot.
    """
    if isinstance(cache, PagedKVCache):
        cache = as_tiered(cache)
    b = q.shape[0]
    hot_valid, cold_valid = _valid_masks(cache)

    def partial(kbuf, valid):
        if kbuf.shape[1] == 0:  # zero-capacity tier: neutral merge element
            h = q.shape[1]
            return (
                jnp.zeros((b, h, value_dim), jnp.float32),
                jnp.zeros((b, h), jnp.float32),
                jnp.full((b, h), jnp.finfo(jnp.float32).min),
            )
        kf = kbuf.astype(jnp.float32)  # (b, s, D)
        logits = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32), kf) * scale
        neg = jnp.finfo(jnp.float32).min
        logits = jnp.where(valid[:, None, :], logits, neg)
        m = jnp.max(logits, axis=-1)  # (b, h)
        p = jnp.exp(logits - m[..., None]) * valid[:, None, :]
        denom = jnp.sum(p, axis=-1)
        # p is 0 at invalid rows but 0 * NaN = NaN — zero the latent too
        lat = jnp.where(valid[:, :, None], kf[..., :value_dim], 0.0)
        num = jnp.einsum("bhs,bsv->bhv", p, lat)
        return num, denom, m

    n1, d1, m1 = partial(cache.hot_k, hot_valid)
    n2, d2, m2 = partial(cache.cold_k, cold_valid)
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m) * (d1 > 0)
    a2 = jnp.exp(m2 - m) * (d2 > 0)
    num = n1 * a1[..., None] + n2 * a2[..., None]
    den = d1 * a1 + d2 * a2
    return num / jnp.maximum(den, 1e-30)[..., None]


def fill_fresh(
    cache: TieredKVCache,
    k_new: jax.Array,  # (b, s, ...) — already rotated + tier-dtype-ready
    v_new: jax.Array,
    ring: bool = False,
) -> TieredKVCache:
    """Place an aligned full-prompt k/v (offset 0, every slot s tokens)
    into a *fresh* cache with static slices — no one-hot scatter.

    Content-identical to ``append`` on a zero cache (the flash-prefill
    kernel already emitted k/v in position order and tier dtype, so
    placement degenerates to two slice-assignments), and to the ring
    realign of the legacy SWA fill when ``s > cold_cap``.
    """
    if isinstance(cache, PagedKVCache):
        raise NotImplementedError(
            "fill_fresh targets the contiguous cache (grouped admission); "
            "paged serving always streams prompts via chunked append")
    b, s = k_new.shape[:2]
    k_new = _rows(k_new, 2, cache.hot_k)
    v_new = _rows(v_new, 2, cache.hot_v)
    if ring and s > cache.cold_cap:
        w = cache.cold_cap
        # slot of token p is p % w; realign so slots match positions
        idx = jnp.arange(s - w, s) % w
        order = jnp.argsort(idx)
        return cache._replace(
            cold_k=k_new[:, s - w:][:, order].astype(cache.cold_k.dtype),
            cold_v=v_new[:, s - w:][:, order].astype(cache.cold_v.dtype),
            lengths=jnp.full_like(cache.lengths, s),
        )
    n_h = min(s, cache.hot_cap)
    n_c = min(s - n_h, cache.cold_cap)
    hot_k, hot_v = cache.hot_k, cache.hot_v
    cold_k, cold_v = cache.cold_k, cache.cold_v
    if n_h:
        hot_k = hot_k.at[:, :n_h].set(k_new[:, :n_h].astype(hot_k.dtype))
        hot_v = hot_v.at[:, :n_h].set(v_new[:, :n_h].astype(hot_v.dtype))
    if n_c:
        cold_k = cold_k.at[:, :n_c].set(
            k_new[:, n_h : n_h + n_c].astype(cold_k.dtype))
        cold_v = cold_v.at[:, :n_c].set(
            v_new[:, n_h : n_h + n_c].astype(cold_v.dtype))
    return TieredKVCache(
        hot_k, hot_v, cold_k, cold_v, jnp.full_like(cache.lengths, s)
    )


def ring_slot_positions(offset: jax.Array, cold_cap: int) -> jax.Array:
    """Absolute position held by each ring-buffer cold slot: (b, cold_cap).

    With hot_cap = 0 (SWA layout) position p writes ring slot p % cold_cap,
    so slot j holds the largest p < offset with p ≡ j (mod cold_cap) — or
    nothing yet, reported as a negative value (mask on ``>= 0``). Decode
    reads never need this (a wrapped window is fully valid and softmax is
    permutation-invariant), but *prefill continuation* does: a chunk's
    later q rows slide the window past the oldest ring entries, and only
    the absolute position says which ones fell out.
    """
    j = jnp.arange(cold_cap, dtype=jnp.int32)[None]  # (1, cap)
    off = offset.astype(jnp.int32)[:, None]  # (b, 1)
    return off - 1 - ((off - 1 - j) % cold_cap)


def tiered_chunk_attention(
    q: jax.Array,  # (b, C, h, dk) — RoPE already applied
    k_new: jax.Array,  # (b, C, g, dk) — RoPE already applied
    v_new: jax.Array,  # (b, C, g, dv)
    cache: Optional[TieredKVCache],
    valid: Optional[jax.Array] = None,  # (b,) valid chunk rows (default C)
    scale: float | None = None,
    window: int = 0,
    ring: bool = False,
) -> jax.Array:
    """Causal chunk attention over [tiered cache prefix ‖ own chunk].

    The XLA reference for the flash-prefill kernel's *continuation* form
    (kernels/flash_prefill.py): each chunk row attends to the slot's
    cached prefix (per-slot ``cache.lengths`` tokens, both tiers) plus
    the causally-earlier rows of its own chunk. ``valid`` marks how many
    chunk rows are real per slot (chunk padding rows produce garbage
    output and attend nothing). ``window`` applies SWA masking by
    absolute position — with ``ring=True`` the cold tier is the wrapped
    ring layout and slot positions come from ``ring_slot_positions``.
    Partials over (hot, cold, chunk) merge with the same streaming
    softmax as the decode read; tiers are never concatenated.
    """
    if isinstance(cache, PagedKVCache):
        assert not ring, "ring layout is not supported for paged caches"
        cache = as_tiered(cache)
    b, C, h, dk = q.shape
    g = k_new.shape[2]
    rep = h // g
    dv = v_new.shape[-1]
    scale = scale if scale is not None else dk**-0.5
    offset = (
        cache.lengths.astype(jnp.int32)
        if cache is not None
        else jnp.zeros((b,), jnp.int32)
    )
    n_new = (
        valid.astype(jnp.int32) if valid is not None
        else jnp.full((b,), C, jnp.int32)
    )
    q_pos = offset[:, None] + jnp.arange(C, dtype=jnp.int32)[None]  # (b, C)
    qg = jnp.moveaxis(q.reshape(b, C, g, rep, dk), 1, 3)  # (b, g, rep, C, dk)
    neg = jnp.finfo(jnp.float32).min

    def partial(kbuf, vbuf, kpos, kvalid):
        # kbuf: (b, S, g, dk); vbuf: (b, S, g, dv) (cache tiers arrive
        # stored as (b, S, g·d)); kpos/kvalid: (b, S)
        s = kbuf.shape[1]
        if s == 0:
            return (
                jnp.zeros((b, g, rep, C, dv), jnp.float32),
                jnp.zeros((b, g, rep, C), jnp.float32),
                jnp.full((b, g, rep, C), neg),
            )
        kf = _upcast(kbuf.reshape((b, s, g, dk)))
        # causally-masked rows hold real (finite) tokens, so per-row
        # kvalid zeroing suffices to keep NaN out of 0 * v products
        vf = jnp.where(kvalid[:, :, None, None],
                       _upcast(vbuf.reshape((b, s, g, dv))), 0.0)
        logits = jnp.einsum(
            "bgrcd,bsgd->bgrcs", qg.astype(kf.dtype), kf,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = kvalid[:, None, :] & (q_pos[:, :, None] >= kpos[:, None, :])
        if window:
            mask &= (q_pos[:, :, None] - kpos[:, None, :]) < window
        mask = mask[:, None, None]  # (b, 1, 1, C, S)
        logits = jnp.where(mask, logits, neg)
        m = jnp.max(logits, axis=-1)
        p = jnp.exp(logits - m[..., None]) * mask
        denom = jnp.sum(p, axis=-1)
        num = jnp.einsum(
            "bgrcs,bsgd->bgrcd", p.astype(vf.dtype), vf,
            preferred_element_type=jnp.float32,
        )
        return num.astype(jnp.float32), denom, m

    parts = []
    if cache is not None and cache.hot_cap:
        hpos = jnp.broadcast_to(
            jnp.arange(cache.hot_cap, dtype=jnp.int32)[None], (b, cache.hot_cap)
        )
        hvalid = hpos < jnp.minimum(offset, cache.hot_cap)[:, None]
        parts.append(partial(cache.hot_k, cache.hot_v, hpos, hvalid))
    if cache is not None and cache.cold_cap:
        if ring:
            cpos = ring_slot_positions(offset, cache.cold_cap)
            cvalid = cpos >= 0
        else:
            j = jnp.arange(cache.cold_cap, dtype=jnp.int32)[None]
            cpos = jnp.broadcast_to(cache.hot_cap + j, (b, cache.cold_cap))
            n_cold = jnp.clip(offset - cache.hot_cap, 0, cache.cold_cap)
            cvalid = j < n_cold[:, None]
        parts.append(partial(cache.cold_k, cache.cold_v, cpos, cvalid))
    npos = q_pos  # the chunk's own kv rows share the q positions
    nvalid = jnp.arange(C, dtype=jnp.int32)[None] < n_new[:, None]
    parts.append(partial(k_new, v_new, npos, nvalid))

    num, den, m = parts[0]
    for n2, d2, m2 in parts[1:]:
        m_new = jnp.maximum(m, m2)
        a1 = jnp.exp(m - m_new) * (den > 0)
        a2 = jnp.exp(m2 - m_new) * (d2 > 0)
        num = num * a1[..., None] + n2 * a2[..., None]
        den = den * a1 + d2 * a2
        m = m_new
    out = num / jnp.maximum(den, 1e-30)[..., None]  # (b, g, rep, C, dv)
    return jnp.moveaxis(out, 3, 1).reshape(b, C, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged-serving admission ops (device side of serving/paging.py):
# slot (re)initialisation with prefix restore + copy-on-write, and the
# hot-tier snapshot that makes the slot-private hot tier shareable.
# ---------------------------------------------------------------------------


def _pool_flat(cache: PagedKVCache):
    tail = cache.pool_k.shape[2:]
    n_rows = cache.n_pages * cache.page_size
    return (cache.pool_k.reshape((n_rows,) + tail),
            cache.pool_v.reshape((n_rows,) + tail), n_rows)


def paged_admit(
    cache: PagedKVCache,
    reset: jax.Array,  # (b,) bool — slots (re)admitted this wave
    new_lengths: jax.Array,  # (b,) int32 — matched prefix length M
    new_table: jax.Array,  # (b, pages_per_slot) int32
    hot_src: jax.Array,  # (b, n_hot_pages) int32 snapshot pages, -1 = none
    cow_src: jax.Array,  # (b,) int32 boundary-page copy source, -1 = none
    cow_dst: jax.Array,  # (b,) int32 boundary-page copy target, -1 = none
) -> PagedKVCache:
    """(Re)initialise the ``reset`` slots for a new request in one fused
    dispatch: install the slot's page-table row and prefix length, restore
    the hot tier from a pooled snapshot (rows < min(M, hot_cap)), and
    copy-on-write the partially-matched boundary page so the slot can
    append into a private copy. Non-reset slots are untouched. All shapes
    are fixed (full batch, masked), so serving compiles this exactly once.
    """
    reset = reset.astype(bool)
    new_lengths = new_lengths.astype(jnp.int32)
    table = jnp.where(reset[:, None], new_table.astype(jnp.int32),
                      cache.page_table)
    lengths = jnp.where(reset, new_lengths, cache.lengths)
    ps = cache.page_size
    pk, pv, n_rows = _pool_flat(cache)

    # copy-on-write: dst page <- src page (full page; rows past the match
    # boundary are overwritten by the slot's own appends, rows past the
    # slot's final length are masked at read)
    j = jnp.arange(ps, dtype=jnp.int32)[None]  # (1, ps)
    cow_ok = reset & (cow_src >= 0) & (cow_dst >= 0)
    src_rows = jnp.clip(cow_src[:, None], 0, None) * ps + j
    vals_k = pk[jnp.clip(src_rows, 0, n_rows - 1)]
    vals_v = pv[jnp.clip(src_rows, 0, n_rows - 1)]
    dst_rows = jnp.where(cow_ok[:, None],
                         jnp.clip(cow_dst[:, None], 0, None) * ps + j, n_rows)
    flat = dst_rows.reshape(-1)
    tail = pk.shape[1:]
    pk = pk.at[flat].set(vals_k.reshape((-1,) + tail), mode="drop")
    pv = pv.at[flat].set(vals_v.reshape((-1,) + tail), mode="drop")

    # hot restore: rows i < min(M, hot_cap) from the snapshot pages
    hot_k, hot_v = cache.hot_k, cache.hot_v
    if cache.hot_cap:
        i = jnp.arange(cache.hot_cap, dtype=jnp.int32)[None]  # (1, hc)
        pg = jnp.minimum(i // ps, hot_src.shape[1] - 1)
        src_pages = jnp.take_along_axis(
            hot_src.astype(jnp.int32),
            jnp.broadcast_to(pg, (hot_src.shape[0], pg.shape[1])), axis=1)
        rows = jnp.clip(src_pages, 0, None) * ps + i % ps
        ok = (reset[:, None] & (src_pages >= 0)
              & (i < jnp.minimum(new_lengths, cache.hot_cap)[:, None]))
        vk = pk[jnp.clip(rows, 0, n_rows - 1)]
        vv = pv[jnp.clip(rows, 0, n_rows - 1)]
        m = ok.reshape(ok.shape + (1,) * (hot_k.ndim - 2))
        hot_k = jnp.where(m, vk.astype(hot_k.dtype), hot_k)
        hot_v = jnp.where(m, vv.astype(hot_v.dtype), hot_v)

    return cache._replace(
        hot_k=hot_k, hot_v=hot_v,
        pool_k=pk.reshape(cache.pool_k.shape),
        pool_v=pv.reshape(cache.pool_v.shape),
        page_table=table, lengths=lengths,
    )


def save_hot(cache: PagedKVCache, slot: jax.Array,
             page_ids: jax.Array) -> PagedKVCache:
    """Snapshot slot ``slot``'s hot tier into pool pages ``page_ids``
    ((n_hot_pages,) int32, -1 = skip) so the slot-private hot prefix
    becomes shareable through the prefix tree (serving/paging.py). Row i
    of the hot tier lands at row i % ps of page page_ids[i // ps]."""
    ps = cache.page_size
    pk, pv, n_rows = _pool_flat(cache)
    i = jnp.arange(cache.hot_cap, dtype=jnp.int32)
    pages = page_ids.astype(jnp.int32)[jnp.minimum(i // ps,
                                                   page_ids.shape[0] - 1)]
    rows = jnp.where(pages >= 0, jnp.clip(pages, 0, None) * ps + i % ps,
                     n_rows)
    hk = jnp.take(cache.hot_k, slot.astype(jnp.int32), axis=0)
    hv = jnp.take(cache.hot_v, slot.astype(jnp.int32), axis=0)
    pk = pk.at[rows].set(hk.astype(pk.dtype), mode="drop")
    pv = pv.at[rows].set(hv.astype(pv.dtype), mode="drop")
    return cache._replace(pool_k=pk.reshape(cache.pool_k.shape),
                          pool_v=pv.reshape(cache.pool_v.shape))


def release_slots(cache, released: jax.Array):
    """Truncate the ``released`` slots ((b,) bool) to length 0 — slot
    retirement without reinitialisation, used when serving preempts or
    cancels a request mid-flight. Works on tiered and paged caches alike
    (anything with a per-slot ``lengths`` row, stacked or not: the mask
    broadcasts against the trailing batch axis). KV rows and page-table
    entries are left in place: a zero-length slot reads nothing, appends
    restart from row 0 on re-admission, and under paging the freed pool
    pages are owned by the host-side ``PagePool`` refcounts, not by this
    device-side view."""
    released = released.astype(bool)
    lengths = jnp.where(
        jnp.broadcast_to(released, cache.lengths.shape), 0, cache.lengths
    )
    return cache._replace(lengths=lengths)


def truncate(cache, new_lengths: jax.Array):
    """Roll each slot back to ``min(lengths, new_lengths)`` tokens —
    the speculative-decoding reject path (serving/engine.py): the
    verifier appends a full K-token chunk, the acceptance rule keeps a
    prefix, and this drops the rejected suffix. Works on tiered and
    paged caches alike, stacked or not (``new_lengths`` (b,) broadcasts
    against the stacked (L, b) lengths); slots whose length is already
    at or below the target are untouched, so a full-batch call with
    per-slot targets needs no mask.

    KV rows past the new length are left in place — reads are masked by
    ``lengths`` and the next append overwrites them. Under paging the
    page-table entries likewise stay; the HOST decides which pages the
    rollback strands (``ceil(max(len - hot_cap, 0) / page_size)`` pages
    remain live) and decrefs the rest — device state never owns pages.

    NOT valid for ring (SWA) layouts once the window has wrapped: a ring
    append overwrites the oldest window rows in place, so the pre-append
    state is unrecoverable. Ring callers must append only what they keep
    (the serving engine commits ``n_emit`` rows instead of rolling back).
    """
    new_lengths = new_lengths.astype(cache.lengths.dtype)
    return cache._replace(
        lengths=jnp.minimum(cache.lengths, new_lengths)
    )


# ---------------------------------------------------------------------------
# Traffic accounting hooks (ties the functional cache to hwmodel/dr_edram)
# ---------------------------------------------------------------------------


def step_traffic_bytes(
    length: int, hot_cap: int, token_bytes: int
) -> dict:
    """External vs on-die bytes moved by one decode step at cache length L.

    Host-side scalar form (single sequence). The vectorized per-slot form
    used by the jitted serving loop is ``step_traffic_tokens``.
    """
    hot_tokens = min(length, hot_cap)
    cold_tokens = max(length - hot_cap, 0)
    write_ext = 0 if length < hot_cap else token_bytes
    return {
        "ondie_read": hot_tokens * token_bytes,
        "ext_read": cold_tokens * token_bytes,
        "ondie_write": token_bytes - write_ext,
        "ext_write": write_ext,
    }


TRAFFIC_KEYS = ("ondie_read", "ext_read", "ondie_write", "ext_write")


def external_reduction(traffic: dict) -> float:
    """Fraction of accesses kept on-die, from a 4-key traffic ledger.

    Shared by every result type that carries a ledger (engine
    GenerationResult, scheduler FinishedRequest) so the accounting rule
    lives in exactly one place."""
    ext = traffic["ext_read"] + traffic["ext_write"]
    total = ext + traffic["ondie_read"] + traffic["ondie_write"]
    return 1.0 - ext / total if total else 0.0


def step_traffic_tokens(lengths: jax.Array, hot_cap: int) -> dict:
    """Vectorized per-slot ledger for one decode step, in *token* units.

    ``lengths`` (b,) is each slot's cache length *before* the step's append.
    Returns a dict of (b,) int32 token counts; multiply by the per-token KV
    byte size to get bytes (kept as counts on device so int32 never meets
    byte-scaled magnitudes inside the jitted loop). Summing this over steps
    i = 0..S-1 for one slot reproduces ``dr_edram.simulate`` exactly, so the
    accumulated ledger reconciles with ``dr_edram.closed_form_reduction``
    per sequence even in mixed-length batches.
    """
    lengths = lengths.astype(jnp.int32)
    hot = jnp.minimum(lengths, hot_cap)
    cold = jnp.maximum(lengths - hot_cap, 0)
    ext_w = (lengths >= hot_cap).astype(jnp.int32)
    return {
        "ondie_read": hot,
        "ext_read": cold,
        "ondie_write": 1 - ext_w,
        "ext_write": ext_w,
    }


def spec_traffic_tokens(lengths: jax.Array, chunk_valid: jax.Array,
                        committed: jax.Array, hot_cap: int) -> dict:
    """Vectorized per-slot ledger for one speculative draft-verify round
    (token units, like ``step_traffic_tokens``).

    ``lengths`` is each slot's cache length before the round,
    ``chunk_valid`` the number of chunk rows the verifier processed and
    ``committed`` the rows physically appended (= chunk_valid on linear
    layouts, the accepted count on ring layouts). The ledger is charged
    for what the device does, not per emitted token — which is exactly
    the speculation win: the cached prefix streams ONCE per round
    instead of once per token, while the chunk rows attend to each other
    on-die. A spec run therefore does NOT reconcile with the sequential
    closed form ``dr_edram.closed_form_reduction``; it strictly
    undercuts it when acceptance > 0 (asserted in tests). Draft-model
    traffic is outside this ledger — the ledger tracks the target
    model's KV tiers (the draft's KV is a second, much smaller cache).
    """
    lengths = lengths.astype(jnp.int32)
    m = chunk_valid.astype(jnp.int32)
    w = committed.astype(jnp.int32)
    hot = jnp.minimum(lengths, hot_cap)
    cold = jnp.maximum(lengths - hot_cap, 0)
    # chunk row i additionally reads rows 0..i-1 of the chunk, on-die
    intra = m * (m - 1) // 2
    ondie_w = jnp.clip(hot_cap - lengths, 0, w)
    return {
        "ondie_read": hot + intra,
        "ext_read": cold,
        "ondie_write": ondie_w,
        "ext_write": w - ondie_w,
    }


def prompt_traffic_tokens(prompt_len: int, hot_cap: int) -> dict:
    """Closed-form prompt-phase ledger (token units) for one sequence.

    Paper's accounting (§IV Fig. 5a): the edge pipeline processes prompt
    tokens sequentially, so token i writes once and reads tokens 0..i-1 —
    the same ledger as a decode step at length i. This host-side closed
    form equals sum(step_traffic_tokens(i) for i in range(prompt_len)).
    """
    p, b = prompt_len, hot_cap
    if p <= b:
        ondie_read = p * (p - 1) // 2
        ext_read = 0
    else:
        ondie_read = b * (b - 1) // 2 + (p - b) * b
        ext_read = (p - b - 1) * (p - b) // 2
    return {
        "ondie_read": ondie_read,
        "ext_read": ext_read,
        "ondie_write": min(p, b),
        "ext_write": max(p - b, 0),
    }


def prompt_traffic_tokens_resumed(
    prompt_len: int, prefix_len: int, hot_cap: int
) -> dict:
    """Prompt-phase ledger when the first ``prefix_len`` tokens were
    restored from a shared prefix cache (serving/paging.py) instead of
    being prefilled.

    The skipped phase (steps 0..prefix_len-1 of ``prompt_traffic_tokens``)
    never runs; what remains is the tail steps plus the cost of reloading
    the snapshot of the first min(prefix_len, hot_cap) tokens from the
    (external) shared pool into the on-die hot tier. Shared *cold* pages
    cost nothing to adopt — they stay external and are read by the tail
    steps exactly as if the slot had written them itself.
    """
    full = prompt_traffic_tokens(prompt_len, hot_cap)
    skipped = prompt_traffic_tokens(min(prefix_len, prompt_len), hot_cap)
    out = {k: full[k] - skipped[k] for k in TRAFFIC_KEYS}
    reload_hot = min(prefix_len, hot_cap)
    out["ext_read"] += reload_hot
    out["ondie_write"] += reload_hot
    return out


# ---------------------------------------------------------------------------
# Slot-state serialization (replica KV handoff; serving/replica.py)
# ---------------------------------------------------------------------------
#
# Warm migration between engine replicas ships ONE slot's live KV rows in
# the tier STORAGE dtype — with kv_fp8 on, the wire payload is fp8 (one
# byte per element: 4x smaller than an f32 serialization, 2x smaller than
# bf16). The frame carries a crc32 per `page_size` rows of every array
# plus a whole-payload trailer, so a corrupted or torn handoff is
# *detected* (typed `HandoffError`) and the receiver falls back to cold
# recompute-from-prefix instead of serving wrong tokens.


class HandoffError(RuntimeError):
    """A serialized slot-state payload failed verification: truncated
    ("torn") framing, unknown dtype, or a per-page / whole-payload
    checksum mismatch. Receivers treat this as "no handoff" and recompute
    the migrated request from its (prefix-cached) prompt — never import
    unverified KV rows."""

    def __init__(self, msg: str, key: Optional[str] = None,
                 page: Optional[int] = None):
        super().__init__(msg)
        self.key = key
        self.page = page


_HANDOFF_MAGIC = b"KVH1"
_HANDOFF_ARRAYS = ("hot_k", "hot_v", "cold_k", "cold_v")


def _np_storage_dtype(name: str):
    """Resolve a serialized dtype name back to a numpy dtype — including
    the ml_dtypes extension types (bfloat16, float8_e4m3fn, ...) jax
    stores KV tiers in."""
    import ml_dtypes  # ships with jax

    try:
        return np.dtype(name)
    except TypeError:
        pass
    ext = getattr(ml_dtypes, name, None)
    if ext is None:
        raise HandoffError(f"unknown storage dtype {name!r} in handoff")
    return np.dtype(ext)


def slot_state_length(cache) -> "np.ndarray":
    """Per-slot cached length, collapsed over a stacked layer axis."""
    lengths = np.asarray(cache.lengths)
    return lengths.max(axis=0) if lengths.ndim == 2 else lengths


def export_slot_state(cache, slot: int) -> dict:
    """Host copy of one slot's live KV rows, in the tier storage dtype.

    Works on tiered and paged caches, stacked (leading layer axis, the
    engine's per-layer-stack layout) or not; the returned arrays always
    carry a leading layer axis (size 1 when unstacked). ``hot_k/hot_v``
    hold the first ``min(length, hot_cap)`` rows, ``cold_k/cold_v`` the
    remaining ``length - hot_cap`` rows — for a paged cache they are
    gathered through the slot's page-table row, so the export is
    layout-independent: importing on either layout is bit-identical.
    """
    lengths = np.asarray(cache.lengths)
    stacked = lengths.ndim == 2
    length = int(lengths[:, slot].max()) if stacked else int(lengths[slot])
    hot_k = cache.hot_k if stacked else cache.hot_k[None]
    hot_v = cache.hot_v if stacked else cache.hot_v[None]
    hc = hot_k.shape[2]
    n_hot = min(length, hc)
    n_cold = max(length - hc, 0)
    state = {
        "length": length,
        "stacked": stacked,
        "hot_k": np.asarray(hot_k[:, slot, :n_hot]),
        "hot_v": np.asarray(hot_v[:, slot, :n_hot]),
    }
    if hasattr(cache, "page_table"):
        pool_k = cache.pool_k if stacked else cache.pool_k[None]
        pool_v = cache.pool_v if stacked else cache.pool_v[None]
        ps = pool_k.shape[2]
        table = np.asarray(cache.page_table)
        row = table[0, slot] if stacked else table[slot]
        kp = -(-n_cold // ps) if n_cold else 0
        ids = np.asarray(row[:kp], np.int32)
        ck = np.asarray(pool_k[:, ids])  # (layers, kp, ps, ...)
        cv = np.asarray(pool_v[:, ids])
        tail = ck.shape[3:]
        state["cold_k"] = ck.reshape((ck.shape[0], kp * ps) + tail)[:, :n_cold]
        state["cold_v"] = cv.reshape((cv.shape[0], kp * ps) + tail)[:, :n_cold]
    else:
        cold_k = cache.cold_k if stacked else cache.cold_k[None]
        cold_v = cache.cold_v if stacked else cache.cold_v[None]
        state["cold_k"] = np.asarray(cold_k[:, slot, :n_cold])
        state["cold_v"] = np.asarray(cold_v[:, slot, :n_cold])
    return state


def import_slot_state(cache, slot: int, state: dict):
    """Write an exported slot state into ``slot`` of ``cache`` (the
    inverse of :func:`export_slot_state`; bit-identical round trip when
    the dtypes match — enforced, a silent cast would corrupt fp8 bits).

    For a paged cache the cold rows are scattered through the slot's
    CURRENT page-table row, overwriting whole pages — the caller must
    have pointed the row at exclusively-owned (refcount-1) pool pages
    first, exactly like a fresh admission."""
    lengths = np.asarray(cache.lengths)
    stacked = lengths.ndim == 2
    length = int(state["length"])
    hot_k = cache.hot_k if stacked else cache.hot_k[None]
    hc = hot_k.shape[2]
    n_hot = min(length, hc)
    n_cold = max(length - hc, 0)
    for name in _HANDOFF_ARRAYS:
        want = np.dtype(cache.hot_k.dtype.name)
        got = np.dtype(state[name].dtype)
        if want != got:
            raise HandoffError(
                f"handoff dtype {got} does not match cache storage dtype "
                f"{want} for {name!r} — refusing to cast KV bits", key=name)
    hk = jnp.asarray(state["hot_k"])
    hv = jnp.asarray(state["hot_v"])
    if stacked:
        new_hk = cache.hot_k.at[:, slot, :n_hot].set(hk)
        new_hv = cache.hot_v.at[:, slot, :n_hot].set(hv)
        new_lengths = cache.lengths.at[:, slot].set(length)
    else:
        new_hk = cache.hot_k.at[slot, :n_hot].set(hk[0])
        new_hv = cache.hot_v.at[slot, :n_hot].set(hv[0])
        new_lengths = cache.lengths.at[slot].set(length)
    kw = dict(hot_k=new_hk, hot_v=new_hv, lengths=new_lengths)
    if hasattr(cache, "page_table"):
        pool_k = cache.pool_k if stacked else cache.pool_k[None]
        ps = pool_k.shape[2]
        kp = -(-n_cold // ps) if n_cold else 0
        if kp:
            table = np.asarray(cache.page_table)
            row = (table[0, slot] if stacked else table[slot])[:kp]
            ck, cv = state["cold_k"], state["cold_v"]
            tail = ck.shape[2:]
            pad = kp * ps - n_cold
            if pad:
                z = np.zeros((ck.shape[0], pad) + tail, ck.dtype)
                ck = np.concatenate([ck, z], axis=1)
                cv = np.concatenate([cv, z], axis=1)
            ck = jnp.asarray(ck.reshape((ck.shape[0], kp, ps) + tail))
            cv = jnp.asarray(cv.reshape((cv.shape[0], kp, ps) + tail))
            ids = jnp.asarray(row, jnp.int32)
            if stacked:
                kw["pool_k"] = cache.pool_k.at[:, ids].set(ck)
                kw["pool_v"] = cache.pool_v.at[:, ids].set(cv)
            else:
                kw["pool_k"] = cache.pool_k.at[ids].set(ck[0])
                kw["pool_v"] = cache.pool_v.at[ids].set(cv[0])
    else:
        ck = jnp.asarray(state["cold_k"])
        cv = jnp.asarray(state["cold_v"])
        if stacked:
            kw["cold_k"] = cache.cold_k.at[:, slot, :n_cold].set(ck)
            kw["cold_v"] = cache.cold_v.at[:, slot, :n_cold].set(cv)
        else:
            kw["cold_k"] = cache.cold_k.at[slot, :n_cold].set(ck[0])
            kw["cold_v"] = cache.cold_v.at[slot, :n_cold].set(cv[0])
    return cache._replace(**kw)


def write_pool_pages(cache: PagedKVCache, page_ids,
                     k_pages, v_pages) -> PagedKVCache:
    """Write whole pages into the shared pool: ``k_pages/v_pages`` are
    (layers, n, page_size, ...) rows for pool pages ``page_ids`` ((n,)
    int32). The receiver-side primitive of warm migration — imported
    cold pages land in freshly allocated pool pages, then the prefix
    tree adopts them by id (Engine.import_handoff)."""
    ids = jnp.asarray(page_ids, jnp.int32)
    stacked = np.asarray(cache.lengths).ndim == 2
    kp = jnp.asarray(k_pages)
    vp = jnp.asarray(v_pages)
    if stacked:
        return cache._replace(pool_k=cache.pool_k.at[:, ids].set(kp),
                              pool_v=cache.pool_v.at[:, ids].set(vp))
    return cache._replace(pool_k=cache.pool_k.at[ids].set(kp[0]),
                          pool_v=cache.pool_v.at[ids].set(vp[0]))


def gather_pool_pages(cache: PagedKVCache, page_ids):
    """Read whole pages out of the shared pool: the read mirror of
    :func:`write_pool_pages`. Returns ``(k_pages, v_pages)`` as numpy
    arrays of shape (layers, n, page_size, ...) in the storage dtype —
    layers is 1 for an unstacked cache. One device pull per tensor."""
    ids = np.asarray(page_ids, np.int32)
    stacked = np.asarray(cache.lengths).ndim == 2
    if stacked:
        return (np.asarray(cache.pool_k[:, ids]),
                np.asarray(cache.pool_v[:, ids]))
    return (np.asarray(cache.pool_k[ids])[None],
            np.asarray(cache.pool_v[ids])[None])


def pool_page_crcs(caches: dict, pages) -> dict:
    """crc32 of each pool page's bytes across every cache stack: the
    DR-eDRAM retention stamp the serving scrub verifies. ``caches`` is
    the engine's ``{key: PagedKVCache}`` dict; the per-page digest
    chains K then V bytes of every stack in sorted-key order, so any
    single bit flip anywhere in the page's storage changes it. Returns
    ``{page_id: crc}``; two device pulls per stack regardless of page
    count."""
    import zlib

    ids = sorted(int(p) for p in pages)
    if not ids:
        return {}
    crcs = {p: 0 for p in ids}
    for key in sorted(caches):
        cache = caches[key]
        if not hasattr(cache, "page_table"):
            continue
        kp, vp = gather_pool_pages(cache, ids)
        for i, p in enumerate(ids):
            crcs[p] = zlib.crc32(
                np.ascontiguousarray(kp[:, i]).tobytes(), crcs[p])
            crcs[p] = zlib.crc32(
                np.ascontiguousarray(vp[:, i]).tobytes(), crcs[p])
    return {p: c & 0xFFFFFFFF for p, c in crcs.items()}


def pack_slot_state(states: dict, page_size: int) -> bytes:
    """Serialize ``{cache_key: export_slot_state(...)}`` into one framed
    byte payload. Arrays ship in their storage dtype (fp8 stays one byte
    per element on the wire) with a crc32 per ``page_size`` rows and a
    whole-payload crc32 trailer; :func:`unpack_slot_state` verifies both
    and raises :class:`HandoffError` on any mismatch."""
    import struct
    import zlib

    ps = max(int(page_size), 1)
    out = [_HANDOFF_MAGIC, struct.pack("<II", len(states), ps)]
    for key in sorted(states):
        st = states[key]
        kb = key.encode()
        out.append(struct.pack("<H", len(kb)))
        out.append(kb)
        out.append(struct.pack("<IB", int(st["length"]),
                               1 if st["stacked"] else 0))
        for name in _HANDOFF_ARRAYS:
            arr = np.ascontiguousarray(st[name])
            dt = arr.dtype.name.encode()
            rows = arr.shape[1]
            n_pages = -(-rows // ps) if rows else 0
            out.append(struct.pack("<H", len(dt)))
            out.append(dt)
            out.append(struct.pack("<B", arr.ndim))
            out.append(struct.pack(f"<{arr.ndim}I", *arr.shape))
            out.append(struct.pack("<I", n_pages))
            for p in range(n_pages):
                chunk = np.ascontiguousarray(
                    arr[:, p * ps:(p + 1) * ps]).tobytes()
                out.append(struct.pack("<II", len(chunk),
                                       zlib.crc32(chunk) & 0xFFFFFFFF))
                out.append(chunk)
    body = b"".join(out)
    return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)


def unpack_slot_state(buf: bytes) -> dict:
    """Parse + verify a :func:`pack_slot_state` payload. Raises
    :class:`HandoffError` on torn framing, unknown dtypes, a per-page
    crc mismatch (``.key``/``.page`` name the damage) or a payload-crc
    mismatch (header corruption) — never returns unverified rows."""
    import struct
    import zlib

    try:
        pos = [0]

        def take(n):
            a, b = pos[0], pos[0] + n
            if b > len(buf):
                raise HandoffError("torn handoff payload: truncated frame")
            pos[0] = b
            return buf[a:b]

        if take(4) != _HANDOFF_MAGIC:
            raise HandoffError("not a slot-state handoff payload (bad magic)")
        n_entries, ps = struct.unpack("<II", take(8))
        states = {}
        for _ in range(n_entries):
            klen, = struct.unpack("<H", take(2))
            key = take(klen).decode()
            length, stacked = struct.unpack("<IB", take(5))
            st = {"length": int(length), "stacked": bool(stacked)}
            for name in _HANDOFF_ARRAYS:
                dlen, = struct.unpack("<H", take(2))
                dtype = _np_storage_dtype(take(dlen).decode())
                ndim, = struct.unpack("<B", take(1))
                shape = struct.unpack(f"<{ndim}I", take(4 * ndim))
                n_pages, = struct.unpack("<I", take(4))
                arr = np.zeros(shape, dtype)
                rows = shape[1] if ndim > 1 else 0
                for p in range(n_pages):
                    nbytes, crc = struct.unpack("<II", take(8))
                    chunk = take(nbytes)
                    if (zlib.crc32(chunk) & 0xFFFFFFFF) != crc:
                        raise HandoffError(
                            f"handoff page checksum mismatch: {key}.{name} "
                            f"page {p}", key=key, page=p)
                    a, b = p * ps, min((p + 1) * ps, rows)
                    arr[:, a:b] = np.frombuffer(chunk, dtype).reshape(
                        (shape[0], b - a) + tuple(shape[2:]))
                st[name] = arr
            states[key] = st
        trailer, = struct.unpack("<I", take(4))
        if (zlib.crc32(buf[:pos[0] - 4]) & 0xFFFFFFFF) != trailer:
            raise HandoffError("handoff payload checksum mismatch "
                               "(corrupted framing)")
        if pos[0] != len(buf):
            raise HandoffError("torn handoff payload: trailing bytes")
        return states
    except HandoffError:
        raise
    except Exception as e:  # struct.error, reshape/frombuffer mismatches
        raise HandoffError(f"torn handoff payload: {e}") from None
