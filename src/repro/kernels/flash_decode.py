"""Pallas flash-decode attention over the tiered DR KV cache (paper §IV).

The decode-side twin of the packed-ternary matmul fast path: with the
projections fused end to end, decode attention was the last XLA-shaped hot
path in the continuous-batching engine. The XLA reference
(``core/kv_cache.tiered_decode_attention``) materializes full
``(b, g, rep, capacity)`` logits over the *padded* hot+cold capacity every
step, upcasts entire fp8 tiers, and masks instead of skipping — a slot at
length 37 pays for the whole cache. This kernel streams instead:

  * **grid (batch, kv_group, s_blocks)** — the S dimension walks the hot
    tier's blocks first, then the cold tier's, carrying the online-softmax
    state (running max / denominator / numerator) in VMEM scratch, so both
    tiers merge *in one launch* with no two-pass HBM merge and no
    concatenated copy of the tiers (the DR structure stays intact);
  * **per-slot length predication** — ``cache.lengths`` rides in as a
    scalar-prefetch operand: fully-invalid S-blocks are skipped in the
    body (``pl.when``) and their BlockSpec indices *park* on the last
    valid block (the actq-prologue trick — consecutive steps that map to
    the same block elide the HBM→VMEM copy), so a slot streams only the
    KV bytes its own prefix occupies;
  * **per-block fp8 dequant** — fp8(e4m3) tiers are upcast tile-by-tile
    in VMEM; the bf16 copy of the whole tier that the XLA path
    materializes never exists;
  * **GQA folded into the q block** — the ``rep`` query heads of a kv
    group form the (rep, d) q tile of one grid row, so grouped heads
    share each streamed KV tile.

Three entry points, mirroring the attention variants:

  * ``flash_decode_attention``        — GQA/MQA over (k, v) tiers;
  * ``flash_decode_attention_latent`` — MLA absorbed form: the cache
    k-slot holds (c_kv ‖ k_rope); values are the latent *prefix* of the
    k-slot (first ``value_dim`` dims), sliced per block in VMEM;
  * ``flash_decode_attention_ring``   — ring/SWA cold tier. The math is
    identical (the clamped validity formula covers the wrapped layout:
    attention is permutation-invariant over KV positions, and once the
    window wraps every ring slot is valid); the entry point exists so
    call sites state their layout.

All dispatch through ``impl`` ("auto" → Pallas on TPU, XLA elsewhere —
the same rule as ``qops.resolve_impl``); the XLA fallbacks are the
existing ``kv_cache`` paths, bit-*tolerant* (fp32-reference parity to
tight tolerance — the merge order differs, so exact bit equality is not
the contract here, unlike the integer matmul kernels). S-block sizes come
from the kind-keyed table ``kernels/ops.select_blocks(kind="decode_attn")``.

Numerical edge cases share the XLA path's conventions: a slot with
length 0 (unadmitted) returns zeros; masked logits use ``finfo(f32).min``;
the final division guards with 1e-30. Out-of-range rows of a partial
S-block are masked *before* the PV matmul (Pallas pads partial blocks
with uninitialized values — 0·NaN would poison the accumulator).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kv_cache as kvc
from repro.kernels import ops

NEG_INF = jnp.finfo(jnp.float32).min


def _resolve(impl: str) -> str:
    """"auto" → pallas on TPU, xla elsewhere (qops.resolve_impl's rule,
    minus the sharding hint — decode attention never runs under GSPMD
    hints; model code passes the config-resolved impl explicitly)."""
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _interpret(interpret) -> bool:
    return jax.default_backend() == "cpu" if interpret is None else interpret


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------


def _rope_rows(x, pos, rope_dims: int, theta: float):
    """Rotate the trailing ``rope_dims`` dims of x (rows, d) f32 at ``pos``
    (rows, 1) int32 — the in-kernel RoPE prologue shared by flash-decode
    and flash-prefill. Reproduces ``layers.apply_rope`` bit-for-bit: the
    freqs exponent numerator 2i is formed exactly, the rotation uses the
    same half-split expressions, all in f32."""
    d = x.shape[-1]
    rd = rope_dims
    half = rd // 2
    base = x[:, d - rd:]
    # Mosaic builds integer iotas only; the cast keeps 2i exact in f32
    two_i = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(
        jnp.float32) * 2.0
    freqs = 1.0 / (theta ** (two_i / rd))  # (1, half)
    ang = pos.astype(jnp.float32) * freqs  # (rows, half)
    cos = jnp.cos(ang)
    sin = jnp.sin(ang)
    x1 = base[:, :half]
    x2 = base[:, half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rd == d:
        return rot
    return jnp.concatenate([x[:, : d - rd], rot], axis=-1)


def _online_update(q, k_tile, v_tile, start, n_valid, scale,
                   m_scr, l_scr, acc_scr, extra_mask=None):
    """One S-block step of the streaming softmax.

    q: (bm, dk) f32; k_tile: (bs, dk) f32; v_tile: (bs, dv) f32;
    ``start`` is the block's first absolute position within its tier,
    ``n_valid`` the tier's per-slot valid length. Scratch: m/l (bm, 1),
    acc (bm, dv) — carried across the S grid dimension. ``extra_mask``
    (1, bs) bool further restricts validity (the fused-RoPE decode path
    masks the ring slot its append is about to evict).
    """
    logits = jax.lax.dot_general(
        q, k_tile, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (bm, bs)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = pos < n_valid  # (bm, bs) — identical across rows
    if extra_mask is not None:
        valid &= extra_mask
    logits = jnp.where(valid, logits, NEG_INF)
    m_prev = m_scr[...]  # (bm, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    p = jnp.exp(logits - m_new) * valid.astype(jnp.float32)
    alpha = jnp.exp(m_prev - m_new)  # (bm, 1); 0 on the first valid block
    # mask v BEFORE the dot: a partial block's out-of-range rows are
    # uninitialized (NaN in interpret mode) and 0 * NaN = NaN
    pos_col = start + jax.lax.broadcasted_iota(
        jnp.int32, (v_tile.shape[0], 1), 0
    )
    v_safe = jnp.where(pos_col < n_valid, v_tile, 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v_safe, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new


def _kernel_gqa(lens_ref, q_ref, hk_ref, hv_ref, ck_ref, cv_ref, o_ref,
                m_scr, l_scr, acc_scr, *, scale, n_hot_blocks,
                hot_cap, cold_cap):
    """Grid (b, g, s_blocks): hot blocks [0, n_hot_blocks), cold after."""
    b_i = pl.program_id(0)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b_i]
    n_hot_valid = jnp.minimum(length, hot_cap)
    # clamped at cold_cap: covers both the linear layout (lengths never
    # exceed capacity) and the ring layout (wrapped window = all valid)
    n_cold_valid = jnp.clip(length - hot_cap, 0, cold_cap)
    q = q_ref[0, 0].astype(jnp.float32)  # (rep, dk)

    bs_hot = hk_ref.shape[1]
    start_hot = kk * bs_hot

    @pl.when((kk < n_hot_blocks) & (start_hot < n_hot_valid))
    def _hot():
        _online_update(
            q, hk_ref[0].astype(jnp.float32), hv_ref[0].astype(jnp.float32),
            start_hot, n_hot_valid, scale, m_scr, l_scr, acc_scr,
        )

    bs_cold = ck_ref.shape[1]
    start_cold = (kk - n_hot_blocks) * bs_cold

    @pl.when((kk >= n_hot_blocks) & (start_cold < n_cold_valid))
    def _cold():
        _online_update(
            q, ck_ref[0].astype(jnp.float32), cv_ref[0].astype(jnp.float32),
            start_cold, n_cold_valid, scale, m_scr, l_scr, acc_scr,
        )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finalize():
        # length-0 slot: l stays 0 -> output 0, matching the XLA path
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def _kernel_gqa_fused(lens_ref, act_ref, q_ref, hk_ref, hv_ref, ck_ref,
                      cv_ref, kn_ref, vn_ref, o_ref, ko_ref, m_scr, l_scr,
                      acc_scr, q_scr, *, scale, n_hot_blocks, hot_cap,
                      cold_cap, ring, theta):
    """The fused-RoPE twin of ``_kernel_gqa``: q and the pending token's
    k arrive UNROTATED and rotate in the prologue at position
    ``lens[b]``; the pending (k, v) joins the softmax as the final
    stream element for active slots (the cache append then happens
    *after* attention, consuming the rotated k this kernel emits). With
    ``ring=True`` the cold slot the append is about to evict is masked —
    the wrapped window [len-w+1, len] stays exact without pre-appending.
    """
    b_i = pl.program_id(0)
    kk = pl.program_id(2)
    length = lens_ref[b_i]
    active = act_ref[b_i] != 0

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q = q_ref[0, 0].astype(jnp.float32)  # (rep, dk)
        pos = jnp.full((q.shape[0], 1), length, jnp.int32)
        q_scr[...] = _rope_rows(q, pos, q.shape[-1], theta)

    n_hot_valid = jnp.minimum(length, hot_cap)
    n_cold_valid = jnp.clip(length - hot_cap, 0, cold_cap)
    q = q_scr[...]

    bs_hot = hk_ref.shape[1]
    start_hot = kk * bs_hot

    @pl.when((kk < n_hot_blocks) & (start_hot < n_hot_valid))
    def _hot():
        _online_update(
            q, hk_ref[0].astype(jnp.float32), hv_ref[0].astype(jnp.float32),
            start_hot, n_hot_valid, scale, m_scr, l_scr, acc_scr,
        )

    bs_cold = ck_ref.shape[1]
    start_cold = (kk - n_hot_blocks) * bs_cold

    @pl.when((kk >= n_hot_blocks) & (start_cold < n_cold_valid))
    def _cold():
        extra = None
        if ring:
            # the append (post-attention) will overwrite ring slot
            # (length - hot_cap) % cold_cap; once the window has wrapped
            # that slot holds position length - cold_cap — outside the
            # window of the token being decoded — so mask it out.
            j = start_cold + jax.lax.broadcasted_iota(
                jnp.int32, (1, bs_cold), 1
            )
            evictee = (length - hot_cap) % cold_cap
            wrapped = active & (length - hot_cap >= cold_cap)
            extra = ~(wrapped & (j == evictee))
        _online_update(
            q, ck_ref[0].astype(jnp.float32), cv_ref[0].astype(jnp.float32),
            start_cold, n_cold_valid, scale, m_scr, l_scr, acc_scr,
            extra_mask=extra,
        )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _finalize():
        k_rot = _rope_rows(
            kn_ref[0].astype(jnp.float32),
            jnp.full((1, 1), length, jnp.int32),
            kn_ref.shape[-1], theta,
        )  # (1, dk)
        ko_ref[0] = k_rot.astype(ko_ref.dtype)

        @pl.when(active)
        def _pending():
            # the pending token attends to itself, position `length`
            _online_update(
                q, k_rot, vn_ref[0].astype(jnp.float32),
                0, 1, scale, m_scr, l_scr, acc_scr,
            )

        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


def _kernel_latent(lens_ref, q_ref, hk_ref, ck_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, n_hot_blocks,
                   hot_cap, cold_cap, value_dim):
    """MLA absorbed form, grid (b, s_blocks): values = k-slot latent prefix."""
    b_i = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b_i]
    n_hot_valid = jnp.minimum(length, hot_cap)
    n_cold_valid = jnp.clip(length - hot_cap, 0, cold_cap)
    q = q_ref[0].astype(jnp.float32)  # (h, D)

    bs_hot = hk_ref.shape[1]
    start_hot = kk * bs_hot

    @pl.when((kk < n_hot_blocks) & (start_hot < n_hot_valid))
    def _hot():
        k_tile = hk_ref[0].astype(jnp.float32)
        _online_update(q, k_tile, k_tile[:, :value_dim], start_hot,
                       n_hot_valid, scale, m_scr, l_scr, acc_scr)

    bs_cold = ck_ref.shape[1]
    start_cold = (kk - n_hot_blocks) * bs_cold

    @pl.when((kk >= n_hot_blocks) & (start_cold < n_cold_valid))
    def _cold():
        k_tile = ck_ref[0].astype(jnp.float32)
        _online_update(q, k_tile, k_tile[:, :value_dim], start_cold,
                       n_cold_valid, scale, m_scr, l_scr, acc_scr)

    @pl.when(kk == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# Launch helpers
# ---------------------------------------------------------------------------


def _tier_blocks(buf, cap: int, block_s: int, dummy_shape, dummy_dtype):
    """Per-tier S blocking. A zero-capacity tier (SWA hot, max_len <=
    hot_cap cold) becomes a 1-token zeros dummy whose single block is
    never valid (the real cap still drives the validity formula), so the
    kernel arity stays fixed."""
    if cap == 0:
        return jnp.zeros(dummy_shape, dummy_dtype), 1, 1
    bs = min(block_s, cap)
    return buf, bs, pl.cdiv(cap, bs)


def _park_maps(hot_cap: int, cold_cap: int, bs_hot: int, bs_cold: int,
               n_hot: int):
    """Index maps for the tier refs: walk valid blocks, then park on the
    last valid one (consecutive identical indices elide the copy) for the
    rest of the S sweep — the block-level predication."""

    def hot_map(b_i, kk, lens):
        n_valid = jnp.minimum(lens[b_i], hot_cap)
        nvb = jnp.maximum(pl.cdiv(n_valid, bs_hot), 1)
        return b_i, jnp.minimum(kk, nvb - 1)

    def cold_map(b_i, kk, lens):
        n_valid = jnp.clip(lens[b_i] - hot_cap, 0, cold_cap)
        nvb = jnp.maximum(pl.cdiv(n_valid, bs_cold), 1)
        kc = jnp.maximum(kk - n_hot, 0)
        return b_i, jnp.minimum(kc, nvb - 1)

    return hot_map, cold_map


def _cold_operands(cache, g, dk, dv, block_s, b):
    """Cold-tier operands + blocking for a launch: contiguous caches use
    the per-slot (b, cold_cap, g*d) buffers with ``block_s`` S-blocks;
    paged caches stream the shared pool (n_pages, page_size, g*d) with
    one page per S-block — the per-slot page table turns into gather
    indices in the BlockSpec index map (``_paged_cold_map``)."""
    if isinstance(cache, kvc.PagedKVCache):
        ps = cache.page_size
        ck = cache.pool_k.reshape(cache.n_pages, ps, g * dk)
        cv = cache.pool_v.reshape(cache.n_pages, ps, g * dv)
        return ck, cv, ps, cache.pages_per_slot

    def flat(t, d):
        return t.reshape(b, t.shape[1], g * d)

    dt = cache.hot_k.dtype
    cold_cap = cache.cold_cap
    ck, bs_cold, n_cold = _tier_blocks(
        flat(cache.cold_k, dk), cold_cap, block_s, (b, 1, g * dk), dt)
    cv, _, _ = _tier_blocks(
        flat(cache.cold_v, dv), cold_cap, block_s, (b, 1, g * dv), dt)
    return ck, cv, bs_cold, n_cold


def _paged_cold_map(hot_cap: int, cold_cap: int, page_size: int, n_hot: int):
    """Paged twin of ``_park_maps``'s cold map: the S index selects the
    slot's logical page, the page table (scalar-prefetch) resolves it to
    a pool page. Parking works at the page level — an invalid S-block
    repeats the last *valid pool page* index, eliding the copy. Unused
    table entries hold pool index 0 (engine convention), so a length-0
    slot parks on a real page and ``pl.when`` skips the body."""

    def cold_map(b_i, kk, lens, pt):
        n_valid = jnp.clip(lens[b_i] - hot_cap, 0, cold_cap)
        nvb = jnp.maximum(pl.cdiv(n_valid, page_size), 1)
        kc = jnp.maximum(kk - n_hot, 0)
        return pt[b_i, jnp.minimum(kc, nvb - 1)], 0

    return cold_map


def _flash_gqa(q, cache, scale, block_s, interpret):
    b, h, dk = q.shape
    g = cache.hot_k.shape[2]
    rep = h // g
    assert rep * g == h, (h, g)
    dv = cache.hot_v.shape[-1]
    hot_cap, cold_cap = cache.hot_cap, cache.cold_cap
    paged = isinstance(cache, kvc.PagedKVCache)
    if block_s is None:
        block_s = ops.select_blocks(
            rep, max(dk, dv), cache.capacity, "pack2", kind="decode_attn"
        )[2]

    # (b, s, g, d) -> (b, s, g*d): trailing-dim reshape (no copy), so the
    # (1, bs, d) BlockSpec tiles land (sublane=s, lane=d)-aligned with the
    # group picked by the block index along the fused g*d axis.
    def flat(t, d):
        return t.reshape(b, t.shape[1], g * d)

    dt = cache.hot_k.dtype
    hk, bs_hot, n_hot = _tier_blocks(
        flat(cache.hot_k, dk), hot_cap, block_s, (b, 1, g * dk), dt)
    hv, _, _ = _tier_blocks(
        flat(cache.hot_v, dv), hot_cap, block_s, (b, 1, g * dv), dt)
    ck, cv, bs_cold, n_cold = _cold_operands(cache, g, dk, dv, block_s, b)

    hot_map2, cold_map2 = _park_maps(hot_cap, cold_cap, bs_hot, bs_cold, n_hot)
    if paged:
        cold_pt = _paged_cold_map(hot_cap, cold_cap, bs_cold, n_hot)
        hot_g = lambda b_i, g_i, kk, lens, pt: (  # noqa: E731
            *hot_map2(b_i, kk, lens), g_i)
        cold_g = lambda b_i, g_i, kk, lens, pt: (  # noqa: E731
            *cold_pt(b_i, kk, lens, pt), g_i)
        q_map = lambda b_i, g_i, kk, lens, pt: (b_i, g_i, 0, 0)  # noqa: E731
        prefetch = (cache.lengths.astype(jnp.int32),
                    cache.page_table.astype(jnp.int32))
        body = functools.partial(
            _kernel_gqa, scale=scale, n_hot_blocks=n_hot,
            hot_cap=hot_cap, cold_cap=cold_cap,
        )
        kern = lambda lens_ref, pt_ref, *rest: body(lens_ref, *rest)  # noqa: E731
    else:
        hot_g = lambda b_i, g_i, kk, lens: (  # noqa: E731
            *hot_map2(b_i, kk, lens), g_i)
        cold_g = lambda b_i, g_i, kk, lens: (  # noqa: E731
            *cold_map2(b_i, kk, lens), g_i)
        q_map = lambda b_i, g_i, kk, lens: (b_i, g_i, 0, 0)  # noqa: E731
        prefetch = (cache.lengths.astype(jnp.int32),)
        kern = functools.partial(
            _kernel_gqa, scale=scale, n_hot_blocks=n_hot,
            hot_cap=hot_cap, cold_cap=cold_cap,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, g, n_hot + n_cold),
        in_specs=[
            pl.BlockSpec((1, 1, rep, dk), q_map),
            pl.BlockSpec((1, bs_hot, dk), hot_g),
            pl.BlockSpec((1, bs_hot, dv), hot_g),
            pl.BlockSpec((1, bs_cold, dk), cold_g),
            pl.BlockSpec((1, bs_cold, dv), cold_g),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dv), q_map),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, rep, dv), q.dtype),
        interpret=interpret,
        name="flash_decode_attention",
    )(*prefetch, q.reshape(b, g, rep, dk), hk, hv, ck, cv)
    return out.reshape(b, h, dv)


def _flash_gqa_fused(q, cache, k_new, v_new, active, scale, theta, ring,
                     block_s, interpret):
    """Launch the fused-RoPE decode kernel: unrotated q/k_new in, rotated
    k_new out alongside the attention output."""
    b, h, dk = q.shape
    g = cache.hot_k.shape[2]
    rep = h // g
    assert rep * g == h, (h, g)
    dv = cache.hot_v.shape[-1]
    hot_cap, cold_cap = cache.hot_cap, cache.cold_cap
    if block_s is None:
        block_s = ops.select_blocks(
            rep, max(dk, dv), cache.capacity, "pack2", kind="decode_attn"
        )[2]

    def flat(t, d):
        return t.reshape(b, t.shape[1], g * d)

    dt = cache.hot_k.dtype
    hk, bs_hot, n_hot = _tier_blocks(
        flat(cache.hot_k, dk), hot_cap, block_s, (b, 1, g * dk), dt)
    hv, _, _ = _tier_blocks(
        flat(cache.hot_v, dv), hot_cap, block_s, (b, 1, g * dv), dt)
    ck, cv, bs_cold, n_cold = _cold_operands(cache, g, dk, dv, block_s, b)

    hot_map2, cold_map2 = _park_maps(hot_cap, cold_cap, bs_hot, bs_cold, n_hot)
    paged = isinstance(cache, kvc.PagedKVCache)
    act = (
        jnp.ones((b,), jnp.int32) if active is None
        else active.astype(jnp.int32)
    )
    body = functools.partial(
        _kernel_gqa_fused, scale=scale, n_hot_blocks=n_hot,
        hot_cap=hot_cap, cold_cap=cold_cap, ring=ring, theta=theta,
    )
    if paged:
        assert not ring, "ring layout is not supported for paged caches"
        cold_pt = _paged_cold_map(hot_cap, cold_cap, bs_cold, n_hot)
        hot_g = lambda b_i, g_i, kk, lens, a, pt: (  # noqa: E731
            *hot_map2(b_i, kk, lens), g_i)
        cold_g = lambda b_i, g_i, kk, lens, a, pt: (  # noqa: E731
            *cold_pt(b_i, kk, lens, pt), g_i)
        q_map = lambda b_i, g_i, kk, lens, a, pt: (  # noqa: E731
            b_i, g_i, 0, 0)
        pin = lambda b_i, g_i, kk, lens, a, pt: (b_i, 0, g_i)  # noqa: E731
        prefetch = (cache.lengths.astype(jnp.int32), act,
                    cache.page_table.astype(jnp.int32))
        kern = lambda lens_ref, act_ref, pt_ref, *rest: body(  # noqa: E731
            lens_ref, act_ref, *rest)
    else:
        hot_g = lambda b_i, g_i, kk, lens, a: (  # noqa: E731
            *hot_map2(b_i, kk, lens), g_i)
        cold_g = lambda b_i, g_i, kk, lens, a: (  # noqa: E731
            *cold_map2(b_i, kk, lens), g_i)
        q_map = lambda b_i, g_i, kk, lens, a: (b_i, g_i, 0, 0)  # noqa: E731
        pin = lambda b_i, g_i, kk, lens, a: (b_i, 0, g_i)  # noqa: E731
        prefetch = (cache.lengths.astype(jnp.int32), act)
        kern = body

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, g, n_hot + n_cold),
        in_specs=[
            pl.BlockSpec((1, 1, rep, dk), q_map),
            pl.BlockSpec((1, bs_hot, dk), hot_g),
            pl.BlockSpec((1, bs_hot, dv), hot_g),
            pl.BlockSpec((1, bs_cold, dk), cold_g),
            pl.BlockSpec((1, bs_cold, dv), cold_g),
            pl.BlockSpec((1, 1, dk), pin),
            pl.BlockSpec((1, 1, dv), pin),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, rep, dv), q_map),
            pl.BlockSpec((1, 1, dk), pin),
        ],
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, dv), jnp.float32),
            pltpu.VMEM((rep, dk), jnp.float32),
        ],
    )
    out, k_rot = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, g, rep, dv), q.dtype),
            jax.ShapeDtypeStruct((b, 1, g * dk), k_new.dtype),
        ],
        interpret=interpret,
        name=("flash_decode_attention_ring" if ring
              else "flash_decode_attention"),
    )(
        *prefetch, q.reshape(b, g, rep, dk),
        hk, hv, ck, cv, k_new.reshape(b, 1, g * dk),
        v_new.reshape(b, 1, g * dv),
    )
    return out.reshape(b, h, dv), k_rot.reshape(b, g, dk)


def _flash_latent(q, cache, value_dim, scale, block_s, interpret):
    b, h, dd = q.shape
    hot_cap, cold_cap = cache.hot_cap, cache.cold_cap
    if block_s is None:
        block_s = ops.select_blocks(
            h, dd, cache.capacity, "pack2", kind="decode_attn"
        )[2]
    dt = cache.hot_k.dtype
    hk, bs_hot, n_hot = _tier_blocks(
        cache.hot_k, hot_cap, block_s, (b, 1, dd), dt)
    ck, bs_cold, n_cold = _tier_blocks(
        cache.cold_k, cold_cap, block_s, (b, 1, dd), dt)
    hot_map, cold_map = _park_maps(hot_cap, cold_cap, bs_hot, bs_cold, n_hot)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_hot + n_cold),
        in_specs=[
            pl.BlockSpec((1, h, dd), lambda b_i, kk, lens: (b_i, 0, 0)),
            pl.BlockSpec((1, bs_hot, dd),
                         lambda b_i, kk, lens: (*hot_map(b_i, kk, lens), 0)),
            pl.BlockSpec((1, bs_cold, dd),
                         lambda b_i, kk, lens: (*cold_map(b_i, kk, lens), 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, h, value_dim), lambda b_i, kk, lens: (b_i, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel_latent, scale=scale, n_hot_blocks=n_hot,
            hot_cap=hot_cap, cold_cap=cold_cap, value_dim=value_dim,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, value_dim), jnp.float32),
        interpret=interpret,
        name="flash_decode_attention_latent",
    )(cache.lengths.astype(jnp.int32), q, hk, ck)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _decode_entry(q, cache, scale, impl, block_s, interpret, k_new, v_new,
                  active, rope_theta, ring):
    impl = _resolve(impl)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if k_new is None:
        if impl == "xla":
            return kvc.tiered_decode_attention(q, cache, scale)
        if impl != "pallas":
            raise ValueError(f"unknown impl {impl!r}")
        return _flash_gqa(q, cache, float(scale), block_s,
                          _interpret(interpret))
    # fused-RoPE form: q and k_new are UNROTATED, the cache holds the
    # PRE-append state; returns (o, rotated k_new) — the caller appends.
    assert rope_theta is not None, "fused decode needs rope_theta"
    if impl == "pallas":
        return _flash_gqa_fused(
            q, cache, k_new, v_new, active, float(scale),
            float(rope_theta), ring, block_s, _interpret(interpret),
        )
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    from repro.models.layers import apply_rope

    pos = cache.lengths.astype(jnp.int32)[:, None]  # (b, 1)
    q_rot = apply_rope(q[:, None], pos, rope_theta)[:, 0]
    k_rot = apply_rope(k_new[:, None], pos, rope_theta)[:, 0]
    app = kvc.append_decode_ring if ring else kvc.append_decode
    attended = app(cache, k_rot, v_new, active=active)
    return kvc.tiered_decode_attention(q_rot, attended, scale), k_rot


@functools.partial(
    jax.jit, static_argnames=("scale", "impl", "block_s", "interpret",
                              "rope_theta")
)
def flash_decode_attention(
    q: jax.Array,  # (b, h, d)
    cache: kvc.TieredKVCache,
    scale: float | None = None,
    *,
    impl: str = "auto",
    block_s: int | None = None,
    interpret: bool | None = None,
    k_new: jax.Array | None = None,  # (b, g, d) — UNROTATED pending token
    v_new: jax.Array | None = None,  # (b, g, dv)
    active: jax.Array | None = None,  # (b,) bool — slots really decoding
    rope_theta: float | None = None,
) -> jax.Array:
    """One-token GQA attention over both tiers. q: (b, h, d) -> (b, h, d).

    ``impl``: "pallas" runs the streaming kernel (interpret mode on CPU),
    "xla" the masked full-capacity reference
    (``kv_cache.tiered_decode_attention``), "auto" picks by backend.
    ``block_s`` overrides the ``select_blocks(kind="decode_attn")``
    S-block. Per-slot ``cache.lengths`` drive validity, so mixed-length
    batches each attend to exactly their own prefix and a length-0
    (unadmitted) slot returns zeros.

    **Fused-RoPE form** (``k_new``/``v_new``/``rope_theta`` given): q and
    the pending token's k arrive UNROTATED and rotate in the kernel
    prologue at position ``cache.lengths[b]``; the pending (k, v) joins
    the stream as the final softmax element for ``active`` slots, and the
    call returns ``(o, k_rot)`` so the caller's cache append consumes the
    kernel-rotated k — the decode step's separate XLA ``apply_rope``
    passes disappear. The cache argument is the PRE-append state.
    """
    return _decode_entry(q, cache, scale, impl, block_s, interpret,
                         k_new, v_new, active, rope_theta, ring=False)


@functools.partial(
    jax.jit, static_argnames=("scale", "impl", "block_s", "interpret",
                              "rope_theta")
)
def flash_decode_attention_ring(
    q: jax.Array,
    cache: kvc.TieredKVCache,
    scale: float | None = None,
    *,
    impl: str = "auto",
    block_s: int | None = None,
    interpret: bool | None = None,
    k_new: jax.Array | None = None,
    v_new: jax.Array | None = None,
    active: jax.Array | None = None,
    rope_theta: float | None = None,
) -> jax.Array:
    """GQA decode attention over a *ring-buffer* cold tier (SWA archs).

    In the plain (pre-rotated, post-append) form this is numerically
    identical to ``flash_decode_attention``: attention is permutation-
    invariant over KV positions, and the validity clamp ``clip(length -
    hot_cap, 0, cold_cap)`` marks the whole window valid once it wraps.
    The fused-RoPE form (``k_new``/``rope_theta``; pre-append cache) is
    where the layout matters: the kernel masks the ring slot the
    upcoming append will evict, keeping the wrapped window exact.
    """
    return _decode_entry(q, cache, scale, impl, block_s, interpret,
                         k_new, v_new, active, rope_theta, ring=True)


@functools.partial(
    jax.jit,
    static_argnames=("value_dim", "scale", "impl", "block_s", "interpret"),
)
def flash_decode_attention_latent(
    q: jax.Array,  # (b, h, D) — D = latent + rope dims
    cache: kvc.TieredKVCache,
    value_dim: int,
    scale: float,
    *,
    impl: str = "auto",
    block_s: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """MLA absorbed-form attention over a tiered *latent* cache.

    The cache k-slot holds (c_kv ‖ k_rope) per token; the v-slot is empty
    — values are the first ``value_dim`` dims of the k-slot, sliced per
    S-block in VMEM (the latent is stored exactly once and streamed
    once). Returns the per-head latent context (b, h, value_dim) f32.
    """
    if isinstance(cache, kvc.PagedKVCache):
        # MLA serving is not paged (engine restriction); gather back to
        # the contiguous layout so direct callers still get the numbers
        cache = kvc.as_tiered(cache)
    impl = _resolve(impl)
    if impl == "xla":
        return kvc.tiered_decode_attention_latent(q, cache, value_dim, scale)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    return _flash_latent(
        q, cache, value_dim, float(scale), block_s, _interpret(interpret)
    )
