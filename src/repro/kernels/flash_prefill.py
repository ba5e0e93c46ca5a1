"""Pallas flash-prefill attention over the tiered DR KV cache (paper §IV).

The prefill-side twin of ``kernels/flash_decode.py``. Prefill dominates
admission latency and — per BitROM's DR-eDRAM accounting — generates the
entire KV-cache *write* traffic, yet until this kernel it ran the pure-XLA
``blockwise_attention`` scan followed by a separate whole-sequence
cache-fill pass (``transformer._fill_attn_cache``: a one-hot einsum
scatter over the full (s, capacity) product), with q/k RoPE as separate
XLA passes materializing rotated HBM copies of the full (b, s, h, hd)
tensors. This kernel streams instead:

  * **grid (batch, kv_group, q_blocks, kv_stream)** — for each q block
    the innermost dimension walks the hot tier's S-blocks, then the cold
    tier's, then the *chunk's own* k/v blocks, carrying the online-softmax
    state (running max / denominator / numerator) in VMEM scratch. Cache
    prefix and fresh chunk merge in ONE launch; the tiers are never
    concatenated and the DR structure stays intact.
  * **RoPE in the kernel prologue** — q blocks rotate once per q block
    into VMEM scratch, k blocks rotate as they stream; positions come
    from the per-slot ``q_offset`` (= ``cache.lengths``) scalar-prefetch
    operand. No pre-rotated (b, s, h, hd) HBM copies exist. The rotation
    reproduces ``layers.apply_rope`` bit-for-bit (same freqs expression,
    same f32 arithmetic, same cast-back), which is what makes the emitted
    cache rows bit-identical to the XLA fill path.
  * **causal skip** — a kv block of the chunk that lies entirely in the
    upper triangle of a q block (``k_start > q_block_end``) is skipped in
    the body (``pl.when``) and its BlockSpec index *parks* on the last
    causally-live block (the flash-decode lengths trick applied to the
    causal structure): roughly half the chunk's KV copies are elided.
    Per-slot ``valid`` lengths predicate the tail the same way, so a slot
    whose prompt chunk is only partially real streams only that part.
  * **cache-fill epilogue** — with ``emit_kv=True`` the kernel emits the
    rotated k and the v of the chunk *in the cache tier's storage dtype*
    (fp8(e4m3) tiers quantize per block in VMEM), written once while the
    last q block streams the chunk. Placement into the hot/cold tiers is
    then a static slice (aligned prefill) or the masked per-slot scatter
    ``kv_cache.append(..., valid=, ring=)`` (chunked continuation) — the
    one-hot whole-sequence fill pass of ``_fill_attn_cache`` disappears
    from the serving path.

Two attention layouts share the kernel:

  * GQA/MQA (+ SWA windows): ``rep`` query heads per kv group fold into
    the q rows of a block (a q tile is (block_q · rep, hd));
  * MLA (non-absorbed prefill): g = h, rep = 1, ``rope_dims`` restricts
    the rotation to the trailing rope dims of the (nope ‖ rope) head,
    ``emit_kv=False`` (the latent cache row is not the per-head k; the
    caller stores the latent separately).

``q_offset`` continuation + per-slot ``valid`` are what let the serving
engine stream **chunked prefill**: mixed-length prompts admit as
fixed-shape (slots, chunk) dispatches against the live cache — one
compile total (see serving/engine.py and docs/serving.md).

Dispatch follows ``impl`` ("auto" → Pallas on TPU, XLA elsewhere — the
``qops.resolve_impl`` rule). The XLA fallback composes the existing
pieces: ``layers.apply_rope`` + ``kv_cache.tiered_chunk_attention`` (the
fp32 reference; for fresh aligned prefill, ``attention.blockwise_attention``
remains the production XLA path — see models/attention.py). S/Q block
sizes come from ``kernels/ops.select_blocks(kind="prefill_attn")``.

Numerical conventions match flash-decode: masked logits use
``finfo(f32).min``, the final division guards with 1e-30, and partial
S-block rows are masked *before* the PV matmul (interpret mode pads
partial blocks with uninitialized values).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import kv_cache as kvc
from repro.kernels import ops
from repro.kernels.flash_decode import (
    _interpret,
    _resolve,
    _rope_rows,
    _tier_blocks,
)

NEG_INF = jnp.finfo(jnp.float32).min


def rope_trailing(x, positions, rope_dims: int, theta: float):
    """XLA twin of the in-kernel rotation: rotate the trailing
    ``rope_dims`` dims of x (..., T, H, D) at ``positions`` (..., T) via
    the shared ``layers.apply_rope`` (bit-identical numerics)."""
    from repro.models.layers import apply_rope

    d = x.shape[-1]
    if rope_dims == d:
        return apply_rope(x, positions, theta)
    rot = apply_rope(x[..., d - rope_dims:], positions, theta)
    return jnp.concatenate([x[..., : d - rope_dims], rot], axis=-1)


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------


def _kernel_prefill(lens_ref, valid_ref, q_ref, hk_ref, hv_ref, ck_ref,
                    cv_ref, kn_ref, vn_ref, *refs, scale, n_hot, n_cold,
                    hot_cap, cold_cap, bq, rep, window, ring, rope_dims,
                    theta, emit_kv, k_in_dtype, v_in_dtype):
    """Grid (b, g, q_blocks, kv_stream): hot blocks, cold blocks, then the
    chunk's own kv blocks; scratch carries the online softmax across the
    innermost dimension (re-initialized per q block)."""
    if emit_kv:
        o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, q_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr, q_scr = refs
    b_i = pl.program_id(0)
    qi = pl.program_id(2)
    kk = pl.program_id(3)
    nq = pl.num_programs(2)
    nk = pl.num_programs(3)
    offset = lens_ref[b_i]  # tokens already cached = q_offset
    nv = valid_ref[b_i]  # valid rows of this slot's chunk
    rows = bq * rep
    # chunk-token index of each q row (rep query heads fold per token)
    q_tok = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // rep
    q_pos = offset + q_tok  # absolute position

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        q_scr[...] = _rope_rows(
            q_ref[0, 0].astype(jnp.float32), q_pos, rope_dims, theta
        )

    def update(k_tile, v_tile, mask, col_valid):
        """One streamed block: k/v (bs, d*) f32, mask (rows|1, bs) bool,
        col_valid (bs, 1) bool — zeroes uninitialized partial-block v rows
        before the PV matmul (interpret pads with NaN; 0 · NaN = NaN)."""
        q = q_scr[...]
        logits = jax.lax.dot_general(
            q, k_tile, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (rows, bs)
        mask = jnp.broadcast_to(mask, logits.shape)
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        v_safe = jnp.where(col_valid, v_tile, 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v_safe, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    # ---- hot tier (absolute positions 0..hot_cap) --------------------
    n_hot_valid = jnp.minimum(offset, hot_cap)
    bs_hot = hk_ref.shape[1]
    start_hot = kk * bs_hot

    @pl.when((kk < n_hot) & (start_hot < n_hot_valid))
    def _hot():
        jcol = start_hot + jax.lax.broadcasted_iota(jnp.int32, (1, bs_hot), 1)
        mask = jcol < n_hot_valid  # causal is automatic: pos < offset <= q_pos
        if window:
            mask = mask & ((q_pos - jcol) < window)
        jrow = start_hot + jax.lax.broadcasted_iota(jnp.int32, (bs_hot, 1), 0)
        update(hk_ref[0].astype(jnp.float32), hv_ref[0].astype(jnp.float32),
               mask, jrow < n_hot_valid)

    # ---- cold tier (linear: hot_cap+j; ring: wrapped SWA layout) -----
    n_cold_valid = jnp.clip(offset - hot_cap, 0, cold_cap)
    bs_cold = ck_ref.shape[1]
    start_cold = (kk - n_hot) * bs_cold

    @pl.when((kk >= n_hot) & (kk < n_hot + n_cold) & (start_cold < n_cold_valid))
    def _cold():
        jcol = start_cold + jax.lax.broadcasted_iota(jnp.int32, (1, bs_cold), 1)
        jrow = start_cold + jax.lax.broadcasted_iota(jnp.int32, (bs_cold, 1), 0)
        if ring:
            # ring slot j holds the largest p < offset with p ≡ j (mod
            # cap). Bound j at cold_cap explicitly: the modulo would wrap
            # a partial last block's out-of-range padding columns back
            # into seemingly-valid positions (uninitialized k/v rows).
            kpos = offset - 1 - ((offset - 1 - jcol) % cold_cap)
            mask = (kpos >= 0) & (jcol < cold_cap)
            col_valid = (
                (offset - 1 - ((offset - 1 - jrow) % cold_cap)) >= 0
            ) & (jrow < cold_cap)
        else:
            kpos = hot_cap + jcol
            mask = jcol < n_cold_valid
            col_valid = jrow < n_cold_valid
        if window:
            mask = mask & ((q_pos - kpos) < window)
        update(ck_ref[0].astype(jnp.float32), cv_ref[0].astype(jnp.float32),
               mask, col_valid)

    # ---- the chunk's own kv blocks (causal skip + valid predication) -
    bs_new = kn_ref.shape[1]
    start_new = (kk - n_hot - n_cold) * bs_new
    q_hi = qi * bq + bq - 1  # last chunk token of this q block

    @pl.when((kk >= n_hot + n_cold) & (start_new < nv) & (start_new <= q_hi))
    def _new():
        ccol = start_new + jax.lax.broadcasted_iota(jnp.int32, (1, bs_new), 1)
        crow = start_new + jax.lax.broadcasted_iota(jnp.int32, (bs_new, 1), 0)
        k_tile = _rope_rows(
            kn_ref[0].astype(jnp.float32), offset + crow, rope_dims, theta
        )
        mask = (ccol < nv) & (q_tok >= ccol)
        if window:
            mask = mask & ((q_tok - ccol) < window)
        update(k_tile, vn_ref[0].astype(jnp.float32), mask, crow < nv)

    @pl.when(kk == nk - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)

    # ---- cache-fill epilogue: the last q block streams every live chunk
    # block anyway (causal), so emit the rotated k / v in tier storage
    # dtype as it passes — rows past ``valid`` zero out (parked blocks
    # hold stale tiles; their `keep` mask is all-false).
    if emit_kv:

        @pl.when((qi == nq - 1) & (kk >= n_hot + n_cold))
        def _emit():
            crow = start_new + jax.lax.broadcasted_iota(
                jnp.int32, (bs_new, 1), 0
            )
            keep = crow < nv
            k_rot = _rope_rows(
                kn_ref[0].astype(jnp.float32), offset + crow, rope_dims, theta
            )
            # cast through the activation dtype first: bit-identical to
            # apply_rope (returns k.dtype) followed by the tier-dtype cast
            ko_ref[0] = jnp.where(keep, k_rot, 0.0).astype(k_in_dtype).astype(
                ko_ref.dtype
            )
            vo_ref[0] = jnp.where(
                keep, vn_ref[0].astype(jnp.float32), 0.0
            ).astype(v_in_dtype).astype(vo_ref.dtype)


# ---------------------------------------------------------------------------
# Launch
# ---------------------------------------------------------------------------


def _flash_prefill(q, k_new, v_new, cache, valid, scale, window, ring,
                   rope_dims, theta, emit_kv, kv_dtype, block_q, block_s,
                   interpret):
    b, c, h, dk = q.shape
    g = k_new.shape[2]
    rep = h // g
    assert rep * g == h, (h, g)
    dv = v_new.shape[-1]
    if block_q is None or block_s is None:
        # table key: grouped q rows when rep > 1; for rep = 1 forms (MLA,
        # plain MHA) the head count drives the row — the decode_attn
        # convention, where the wide-head latent form passes h
        auto = ops.select_blocks(
            rep if rep > 1 else h, max(dk, dv), c, "pack2",
            kind="prefill_attn",
        )
        block_q = block_q or auto[0]
        block_s = block_s or auto[2]
    bq = min(block_q, c)
    nq = pl.cdiv(c, bq)
    cq = nq * bq
    bs_new = min(block_s, c)
    n_new = pl.cdiv(c, bs_new)
    ck_len = n_new * bs_new

    paged = isinstance(cache, kvc.PagedKVCache)
    if cache is None:
        hot_cap = cold_cap = 0
        lens = jnp.zeros((b,), jnp.int32)
        hot_k = hot_v = cold_k = cold_v = None
        tier_dt = k_new.dtype
    else:
        hot_cap, cold_cap = cache.hot_cap, cache.cold_cap
        lens = cache.lengths.astype(jnp.int32)
        hot_k, hot_v = cache.hot_k, cache.hot_v
        cold_k, cold_v = (None, None) if paged else (cache.cold_k,
                                                     cache.cold_v)
        tier_dt = cache.hot_k.dtype
    kv_dtype = kv_dtype or tier_dt

    def flat(t, d, cap):
        if t is None:
            return None
        return t.reshape(b, cap, g * d)

    hk, bs_hot, n_hot = _tier_blocks(
        flat(hot_k, dk, hot_cap), hot_cap, block_s, (b, 1, g * dk), tier_dt)
    hv, _, _ = _tier_blocks(
        flat(hot_v, dv, hot_cap), hot_cap, block_s, (b, 1, g * dv), tier_dt)
    if paged:
        # cold tier = the shared pool, one page per S-block; the per-slot
        # page table rides as a third scalar-prefetch operand and resolves
        # logical -> pool pages inside cold_map (flash_decode's scheme)
        assert not ring, "ring layout is not supported for paged caches"
        bs_cold, n_cold = cache.page_size, cache.pages_per_slot
        ck = cache.pool_k.reshape(cache.n_pages, bs_cold, g * dk)
        cv = cache.pool_v.reshape(cache.n_pages, bs_cold, g * dv)
    else:
        ck, bs_cold, n_cold = _tier_blocks(
            flat(cold_k, dk, cold_cap), cold_cap, block_s, (b, 1, g * dk),
            tier_dt)
        cv, _, _ = _tier_blocks(
            flat(cold_v, dv, cold_cap), cold_cap, block_s, (b, 1, g * dv),
            tier_dt)

    # q: (b, c, h, dk) -> (b, g, cq*rep, dk), token-major rows per block
    qt = jnp.moveaxis(q.reshape(b, c, g, rep, dk), 1, 2)  # (b, g, c, rep, dk)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, cq - c), (0, 0), (0, 0)))
    qt = qt.reshape(b, g, cq * rep, dk)
    kn = jnp.pad(
        k_new.reshape(b, c, g * dk), ((0, 0), (0, ck_len - c), (0, 0)))
    vn = jnp.pad(
        v_new.reshape(b, c, g * dv), ((0, 0), (0, ck_len - c), (0, 0)))

    def hot_map(b_i, g_i, qi, kk, lens, valid, *rest):
        nvalid = jnp.minimum(lens[b_i], hot_cap)
        nvb = jnp.maximum(pl.cdiv(nvalid, bs_hot), 1)
        return b_i, jnp.minimum(kk, nvb - 1), g_i

    if paged:

        def cold_map(b_i, g_i, qi, kk, lens, valid, pt):
            nvalid = jnp.clip(lens[b_i] - hot_cap, 0, cold_cap)
            nvb = jnp.maximum(pl.cdiv(nvalid, bs_cold), 1)
            kc = jnp.maximum(kk - n_hot, 0)
            return pt[b_i, jnp.minimum(kc, nvb - 1)], 0, g_i

    else:

        def cold_map(b_i, g_i, qi, kk, lens, valid, *rest):
            nvalid = jnp.clip(lens[b_i] - hot_cap, 0, cold_cap)
            nvb = jnp.maximum(pl.cdiv(nvalid, bs_cold), 1)
            kc = jnp.maximum(kk - n_hot, 0)
            return b_i, jnp.minimum(kc, nvb - 1), g_i

    def new_map(b_i, g_i, qi, kk, lens, valid, *rest):
        kn_i = jnp.maximum(kk - n_hot - n_cold, 0)
        causal_last = (qi * bq + bq - 1) // bs_new
        valid_last = jnp.maximum(pl.cdiv(valid[b_i], bs_new), 1) - 1
        return b_i, jnp.minimum(kn_i, jnp.minimum(causal_last, valid_last)), g_i

    def emit_map(b_i, g_i, qi, kk, lens, valid, *rest):
        kn_i = jnp.clip(kk - n_hot - n_cold, 0, n_new - 1)
        return b_i, jnp.where(qi == nq - 1, kn_i, 0), g_i

    def q_map(b_i, g_i, qi, kk, lens, valid, *rest):
        return b_i, g_i, qi, 0

    in_specs = [
        pl.BlockSpec((1, 1, bq * rep, dk), q_map),
        pl.BlockSpec((1, bs_hot, dk), hot_map),
        pl.BlockSpec((1, bs_hot, dv), hot_map),
        pl.BlockSpec((1, bs_cold, dk), cold_map),
        pl.BlockSpec((1, bs_cold, dv), cold_map),
        pl.BlockSpec((1, bs_new, dk), new_map),
        pl.BlockSpec((1, bs_new, dv), new_map),
    ]
    out_shapes = [jax.ShapeDtypeStruct((b, g, cq * rep, dv), q.dtype)]
    out_specs = [
        pl.BlockSpec((1, 1, bq * rep, dv), q_map),
    ]
    if emit_kv:
        out_shapes += [
            jax.ShapeDtypeStruct((b, ck_len, g * dk), kv_dtype),
            jax.ShapeDtypeStruct((b, ck_len, g * dv), kv_dtype),
        ]
        out_specs += [
            pl.BlockSpec((1, bs_new, dk), emit_map),
            pl.BlockSpec((1, bs_new, dv), emit_map),
        ]

    prefetch = (lens, valid)
    body = functools.partial(
        _kernel_prefill, scale=scale, n_hot=n_hot, n_cold=n_cold,
        hot_cap=hot_cap, cold_cap=cold_cap, bq=bq, rep=rep,
        window=window, ring=ring, rope_dims=rope_dims, theta=theta,
        emit_kv=emit_kv, k_in_dtype=k_new.dtype, v_in_dtype=v_new.dtype,
    )
    if paged:
        prefetch = (lens, valid, cache.page_table.astype(jnp.int32))
        kern = lambda lens_ref, valid_ref, pt_ref, *rest: body(  # noqa: E731
            lens_ref, valid_ref, *rest)
    else:
        kern = body
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, g, nq, n_hot + n_cold + n_new),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq * rep, 1), jnp.float32),
            pltpu.VMEM((bq * rep, 1), jnp.float32),
            pltpu.VMEM((bq * rep, dv), jnp.float32),
            pltpu.VMEM((bq * rep, dk), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
        name="flash_prefill_attention",
    )(*prefetch, qt, hk, hv, ck, cv, kn, vn)

    o = outs[0].reshape(b, g, cq, rep, dv)[:, :, :c]
    o = jnp.moveaxis(o, 2, 1).reshape(b, c, h, dv)
    if not emit_kv:
        return o
    k_cast = outs[1][:, :c].reshape(b, c, g, dk)
    v_cast = outs[2][:, :c].reshape(b, c, g, dv)
    return o, k_cast, v_cast


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "ring", "rope_theta", "rope_dims",
                     "emit_kv", "kv_dtype", "impl", "block_q", "block_s",
                     "interpret"),
)
def flash_prefill_attention(
    q: jax.Array,  # (b, C, h, dk) — UNROTATED
    k_new: jax.Array,  # (b, C, g, dk) — UNROTATED
    v_new: jax.Array,  # (b, C, g, dv)
    cache: kvc.TieredKVCache | None = None,
    valid: jax.Array | None = None,  # (b,) valid chunk rows (default C)
    *,
    scale: float | None = None,
    window: int = 0,
    ring: bool = False,
    rope_theta: float = 1_000_000.0,
    rope_dims: int | None = None,  # None = whole head (GQA); MLA: rope dims
    emit_kv: bool = True,
    kv_dtype=None,  # tier storage dtype for the emitted k/v (default: cache's)
    impl: str = "auto",
    block_q: int | None = None,
    block_s: int | None = None,
    interpret: bool | None = None,
):
    """Causal/SWA prefill attention over [tiered cache prefix ‖ chunk].

    q/k arrive UNROTATED; RoPE happens inside (kernel prologue, or the
    shared ``apply_rope`` on the XLA path) at absolute positions
    ``cache.lengths[b] + row``. Returns ``(o, k_cast, v_cast)`` with the
    chunk's rotated k and its v cast to the tier storage dtype (rows past
    ``valid`` zeroed) when ``emit_kv``, else just ``o`` (b, C, h, dv).
    ``cache=None`` is the fresh aligned prefill (offset 0, no streamed
    tiers). ``impl``: "pallas" runs the streaming kernel (interpret mode
    on CPU), "xla" the ``kv_cache.tiered_chunk_attention`` reference,
    "auto" picks by backend.
    """
    impl = _resolve(impl)
    b, c, h, dk = q.shape
    scale = float(scale) if scale is not None else dk**-0.5
    rd = rope_dims if rope_dims is not None else dk
    if valid is None:
        valid = jnp.full((b,), c, jnp.int32)
    valid = valid.astype(jnp.int32)
    if impl == "pallas":
        return _flash_prefill(
            q, k_new, v_new, cache, valid, scale, window, ring, rd,
            float(rope_theta), emit_kv, kv_dtype, block_q, block_s,
            _interpret(interpret),
        )
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r}")
    offset = (
        cache.lengths.astype(jnp.int32)[:, None]
        if cache is not None else jnp.zeros((b, 1), jnp.int32)
    )
    positions = offset + jnp.arange(c, dtype=jnp.int32)[None]  # (b, C)
    q_rot = rope_trailing(q, positions, rd, rope_theta)
    k_rot = rope_trailing(k_new, positions, rd, rope_theta)
    o = kvc.tiered_chunk_attention(
        q_rot, k_rot, v_new, cache, valid, scale, window=window, ring=ring
    )
    if not emit_kv:
        return o
    tier_dt = kv_dtype or (cache.hot_k.dtype if cache is not None else k_new.dtype)
    keep = (jnp.arange(c, dtype=jnp.int32)[None] < valid[:, None])[..., None, None]
    k_cast = jnp.where(keep, k_rot, 0).astype(tier_dt)
    v_cast = jnp.where(keep, v_new, 0).astype(tier_dt)
    return o, k_cast, v_cast
