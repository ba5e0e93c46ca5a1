"""Attention variants: GQA/MQA (full + sliding-window), MLA, encoder.

Full-sequence attention (train / prefill) uses a blockwise streaming-softmax
formulation (flash-attention structure in pure JAX): lax.scan over query
chunks with an inner scan over KV chunks carrying (max, denom, acc). Memory
is O(chunk²) instead of O(S²), which is what makes the 32k prefill and the
4k train cells lower at scale.

Decode uses the two-tier DR KV cache (core/kv_cache.py) — hot early-token
buffer + cold tail — or a ring buffer for sliding-window archs (SWA evicts
early tokens, so DR tiering is N/A there; see DESIGN.md §4). The attention
read itself goes through kernels/flash_decode.py: a streaming online-
softmax Pallas kernel (both tiers merged in one launch, per-slot lengths
predicating the S-blocks) on TPU, with the masked full-capacity XLA path
in core/kv_cache.py as the reference fallback.

MLA (DeepSeek-V3) caches the compressed latent (c_kv ‖ k_rope, 576 B/token)
and decodes in *absorbed* form (W_uk folded into the query, W_uv folded out
of the context) so the per-step cost scales with the latent, not the heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kv_cache as kvc
from repro.kernels import flash_decode as fd
from repro.kernels import flash_prefill as fprefill
from repro.models import qops
from repro.models.layers import apply_rope, init_rms_norm, rms_norm

NEG_INF = jnp.finfo(jnp.float32).min
DEFAULT_CHUNK = 512


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention over full sequences
# ---------------------------------------------------------------------------


def _chunk(seq: int, target: int = DEFAULT_CHUNK) -> int:
    """Chunk size for the blockwise scan: the target, capped at the
    sequence. Non-dividing lengths are handled by padding + masking in
    ``blockwise_attention`` — the historical behavior of halving until
    the chunk divides collapsed to chunk=1 for prime/odd lengths (e.g.
    257), turning the scan into a length-S loop of 1-token blocks."""
    return min(seq, target)


def blockwise_attention(
    q: jax.Array,  # (b, g, r, sq, dk)
    k: jax.Array,  # (b, g, sk, dk)
    v: jax.Array,  # (b, g, sk, dv)
    *,
    causal: bool = True,
    window: int = 0,  # 0 = unbounded; else SWA: q_pos - kv_pos < window
    q_offset: int = 0,  # absolute position of q[0] (prefill continuation)
    scale: float | None = None,
    q_chunk: int | None = None,
    kv_chunk: int | None = None,
) -> jax.Array:  # (b, g, r, sq, dv)
    b, g, r, sq, dk = q.shape
    sk, dv = k.shape[2], v.shape[3]
    scale = scale if scale is not None else dk**-0.5
    cq = q_chunk or _chunk(sq)
    ck = kv_chunk or _chunk(sk)
    nq, nk = -(-sq // cq), -(-sk // ck)
    # pad to chunk multiples and mask: padded kv columns are masked out of
    # every row below (k_pos < sk), padded q rows are sliced off the output
    if nq * cq != sq:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, nq * cq - sq), (0, 0)))
    if nk * ck != sk:
        pad_k = ((0, 0), (0, 0), (0, nk * ck - sk), (0, 0))
        k = jnp.pad(k, pad_k)
        v = jnp.pad(v, pad_k)

    qs = jnp.moveaxis(q.reshape(b, g, r, nq, cq, dk), 3, 0)  # (nq, b,g,r,cq,dk)
    ks = jnp.moveaxis(k.reshape(b, g, nk, ck, dk), 2, 0)  # (nk, b,g,ck,dk)
    vs = jnp.moveaxis(v.reshape(b, g, nk, ck, dv), 2, 0)

    q_pos_base = jnp.arange(cq, dtype=jnp.int32)
    k_pos_base = jnp.arange(ck, dtype=jnp.int32)

    @jax.checkpoint
    def q_step(_, qi_qc):
        # rematerialized per q-chunk: the backward pass recomputes one
        # chunk's inner kv scan at a time instead of stashing the full
        # (nq x nk x cq x ck) attention matrix (observed to dominate temp
        # memory on the train_4k dry-run).
        qi, qc = qi_qc
        q_pos = q_offset + qi * cq + q_pos_base  # (cq,)

        def kv_step(carry, ki_kc):
            ki, kc, vc = ki_kc
            m, l, acc = carry
            k_pos = ki * ck + k_pos_base  # (ck,)
            logits = jnp.einsum(
                "bgrqd,bgkd->bgrqk", qc.astype(jnp.float32), kc.astype(jnp.float32)
            ) * scale
            mask = (k_pos < sk)[None, :] & jnp.ones((cq, 1), dtype=bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            logits = jnp.where(mask, logits, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bgrqk,bgkd->bgrqd", p, vc.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, g, r, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, g, r, cq), jnp.float32)
        a0 = jnp.zeros((b, g, r, cq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), ks, vs)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))  # (nq, b,g,r,cq,dv)
    out = jnp.moveaxis(outs, 0, 3).reshape(b, g, r, nq * cq, dv)
    return out[:, :, :, :sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA / MQA / SWA attention block
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "ln": init_rms_norm(d, dtype),
        "wq": qops.init_linear(ks[0], d, h * hd, dtype),
        "wk": qops.init_linear(ks[1], d, g * hd, dtype),
        "wv": qops.init_linear(ks[2], d, g * hd, dtype),
        "wo": qops.init_linear(ks[3], h * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd, dtype)
        p["k_norm"] = init_rms_norm(hd, dtype)
    if cfg.bitnet.lora_rank:
        from repro.core import lora as lora_lib

        if "v" in cfg.bitnet.lora_targets:
            p["lora_v"] = lora_lib.init(ks[4], d, g * hd, cfg.bitnet.lora_rank, dtype)
        if "o" in cfg.bitnet.lora_targets:
            p["lora_o"] = lora_lib.init(ks[5], h * hd, d, cfg.bitnet.lora_rank, dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, mode: str):
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    with jax.named_scope("qkv_proj"):
        hidden = rms_norm(x, p["ln"], cfg.norm_eps)
        if "wqkv" in p:
            # fused packed fast path (models/pack.py::fuse_packed): one
            # act-quant + one kernel launch produce q‖k‖v; the v-adapter
            # applies to its segment after the split.
            q, k, v = qops.fused_linear(
                p["wqkv"], hidden, cfg,
                out_shapes=((h, hd), (g, hd), (g, hd)),
                lora_leaves={2: p.get("lora_v")},
            )
        else:
            q = qops.linear(p["wq"], hidden, cfg, mode, out_shape=(h, hd))
            k = qops.linear(p["wk"], hidden, cfg, mode, out_shape=(g, hd))
            v = qops.linear(
                p["wv"], hidden, cfg, mode, out_shape=(g, hd), lora_leaf=p.get("lora_v")
            )
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        return q, k, v


def attention_full(
    p: dict,
    x: jax.Array,  # (b, s, d_model)
    cfg: ModelConfig,
    mode: str,
    positions: jax.Array,  # (s,)
    *,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill). Causal unless encoder."""
    b, s, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x, cfg, mode)  # (b,s,h,hd) / (b,s,g,hd)
    q = apply_rope(q, positions[None], cfg.rope_theta)
    k = apply_rope(k, positions[None], cfg.rope_theta)
    rep = h // g
    qg = jnp.moveaxis(q.reshape(b, s, g, rep, hd), 1, 3)  # (b,g,rep,s,hd)
    kg = jnp.moveaxis(k, 1, 2)  # (b,g,s,hd)
    vg = jnp.moveaxis(v, 1, 2)
    o = blockwise_attention(
        qg,
        kg,
        vg,
        causal=not cfg.is_encoder,
        window=cfg.swa_window if cfg.attn_type == "swa" else 0,
    )  # (b,g,rep,s,hd)
    o = jnp.moveaxis(o, 3, 1).reshape(b, s, h * hd)
    y = qops.linear(p["wo"], o, cfg, mode, lora_leaf=p.get("lora_o"))
    if return_kv:
        return y, (k, v)
    return y


def attention_prefill(
    p: dict,
    x: jax.Array,  # (b, s, d_model) — the whole (aligned) prompt
    cfg: ModelConfig,
    mode: str,
    cache: kvc.TieredKVCache,  # fresh per-layer cache rows (lengths 0)
    impl: str | None = None,
):
    """Full-prompt prefill attention + tiered cache fill for one layer.

    Returns (y, filled_cache). On the Pallas path the flash-prefill
    kernel (kernels/flash_prefill.py) rotates q/k in its prologue,
    streams causal attention with upper-triangle kv blocks skipped, and
    emits the chunk's k/v already cast to the tier storage dtype (fp8
    quantized per block in VMEM) — placement is then the static-slice
    ``kv_cache.fill_fresh``, so the legacy whole-sequence one-hot fill
    pass never runs. The XLA path composes the existing ops
    (``apply_rope`` + ``blockwise_attention``) and fills the same way —
    the two paths produce bit-identical caches.
    """
    b, s, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x, cfg, mode)  # (b,s,h,hd) / (b,s,g,hd)
    impl = impl or qops.resolve_impl(cfg)
    swa = cfg.attn_type == "swa"
    window = cfg.swa_window if swa else 0
    with jax.named_scope("attention"):
        if impl == "pallas":
            o, k_c, v_c = fprefill.flash_prefill_attention(
                q, k, v, None,
                window=window, rope_theta=cfg.rope_theta, emit_kv=True,
                kv_dtype=cache.hot_k.dtype, impl="pallas",
            )
            o = o.reshape(b, s, h * hd)
        else:
            positions = jnp.arange(s, dtype=jnp.int32)[None]
            qr = apply_rope(q, positions, cfg.rope_theta)
            kr = apply_rope(k, positions, cfg.rope_theta)
            rep = h // g
            qg = jnp.moveaxis(qr.reshape(b, s, g, rep, hd), 1, 3)
            o = blockwise_attention(
                qg, jnp.moveaxis(kr, 1, 2), jnp.moveaxis(v, 1, 2),
                causal=True, window=window,
            )
            o = jnp.moveaxis(o, 3, 1).reshape(b, s, h * hd)
            k_c, v_c = kr, v
    with jax.named_scope("kv_write"):
        cache = kvc.fill_fresh(cache, k_c, v_c, ring=swa)
    with jax.named_scope("o_proj"):
        y = qops.linear(p["wo"], o, cfg, mode, lora_leaf=p.get("lora_o"))
    return y, cache


def attention_prefill_chunk(
    p: dict,
    x: jax.Array,  # (b, C, d_model) — one prompt chunk per slot
    cfg: ModelConfig,
    mode: str,
    cache: kvc.TieredKVCache,  # live per-layer cache (per-slot lengths)
    n_valid: jax.Array,  # (b,) valid chunk rows; 0 = slot not prefilling
    impl: str | None = None,
    append: bool = True,
):
    """Chunked-prefill continuation for one layer: the C chunk tokens of
    each slot attend to the slot's cached prefix (``cache.lengths``
    tokens, both tiers) plus the causally-earlier rows of the chunk,
    then append their k/v at the slot's offset. Returns (y, cache).

    With ``append=False`` the cache is left untouched and the rotated
    chunk k/v are returned instead: ``(y, (k_c, v_c))``. This is the
    speculative-decoding verify form (serving/engine.py): attention
    never reads the chunk's rows *through* the cache (they stream in
    separately on both impls), so deferring the append until the
    accept/reject decision is known changes no numerics — and it is
    what makes verification safe on ring (SWA) layouts, where an
    append-then-rollback would already have clobbered the oldest
    window rows.

    Every shape is fixed by (slots, C) — per-slot offsets and valid
    counts are data — which is what gives the serving engine its
    one-compile chunked admission (docs/serving.md).
    """
    b, c, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x, cfg, mode)
    impl = impl or qops.resolve_impl(cfg)
    swa = cfg.attn_type == "swa"
    window = cfg.swa_window if swa else 0
    with jax.named_scope("attention"):
        if impl == "pallas":
            o, k_c, v_c = fprefill.flash_prefill_attention(
                q, k, v, cache, valid=n_valid,
                window=window, ring=swa, rope_theta=cfg.rope_theta,
                emit_kv=True, impl="pallas",
            )
        else:
            positions = cache.lengths.astype(jnp.int32)[:, None] + jnp.arange(
                c, dtype=jnp.int32
            )[None]
            qr = apply_rope(q, positions, cfg.rope_theta)
            kr = apply_rope(k, positions, cfg.rope_theta)
            o = kvc.tiered_chunk_attention(
                qr, kr, v, cache, n_valid, window=window, ring=swa
            )
            k_c, v_c = kr, v
    if append:
        with jax.named_scope("kv_write"):
            cache = kvc.append(cache, k_c, v_c, valid=n_valid, ring=swa)
    with jax.named_scope("o_proj"):
        y = qops.linear(
            p["wo"], o.reshape(b, c, h * hd), cfg, mode,
            lora_leaf=p.get("lora_o"),
        )
    return y, (cache if append else (k_c, v_c))


def attention_decode(
    p: dict,
    x: jax.Array,  # (b, d_model) — one token per slot
    cfg: ModelConfig,
    mode: str,
    cache: kvc.TieredKVCache,
    active: jax.Array | None = None,  # (b,) bool: slots that really decode
):
    """One decode step against the tiered cache. Returns (y, new_cache).

    RoPE positions come from the per-slot ``cache.lengths``, so slots at
    different sequence lengths decode side by side (continuous batching);
    ``active`` gates the KV append per slot. Attention runs the flash-
    decode fast path (``kernels/flash_decode.py``): on the Pallas impl
    the *fused-RoPE* form — q and the new token's k rotate in the kernel
    prologue, the pending (k, v) joins the softmax stream, and the cache
    append consumes the kernel-rotated k, so no separate XLA
    ``apply_rope`` passes run in the decode step. The XLA impl keeps the
    historical rotate → append → masked full-capacity read pipeline
    (``qops.resolve_impl`` — the same dispatch rule as the packed
    matmuls).
    """
    b, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x[:, None, :], cfg, mode)  # (b,1,h,hd)
    impl = qops.resolve_impl(cfg)
    swa = cfg.attn_type == "swa"
    app = kvc.append_decode_ring if swa else kvc.append_decode
    entry = fd.flash_decode_attention_ring if swa else fd.flash_decode_attention
    if impl == "pallas":
        with jax.named_scope("attention"):
            o, k_rot = entry(
                q[:, 0], cache, impl=impl,
                k_new=k[:, 0], v_new=v[:, 0], active=active,
                rope_theta=cfg.rope_theta,
            )
        with jax.named_scope("kv_write"):
            cache = app(cache, k_rot, v[:, 0], active=active)
    else:
        with jax.named_scope("attention"):
            pos = cache.lengths[:, None]  # (b, 1) per-slot absolute position
            q = apply_rope(q, pos, cfg.rope_theta)[:, 0]  # (b,h,hd)
            k = apply_rope(k, pos, cfg.rope_theta)[:, 0]  # (b,g,hd)
        with jax.named_scope("kv_write"):
            cache = app(cache, k, v[:, 0], active=active)
        with jax.named_scope("attention"):
            o = entry(q, cache, impl=impl)
    with jax.named_scope("o_proj"):
        y = qops.linear(
            p["wo"], o.reshape(b, h * hd), cfg, mode, lora_leaf=p.get("lora_o")
        )
    return y, cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): compressed-latent attention
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    p = {
        "ln": init_rms_norm(d, dtype),
        "w_dq": qops.init_linear(ks[0], d, m.q_lora_rank, dtype),
        "q_ln": init_rms_norm(m.q_lora_rank, dtype),
        "w_uq": qops.init_linear(ks[1], m.q_lora_rank, h * qk_head, dtype),
        "w_dkv": qops.init_linear(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_ln": init_rms_norm(m.kv_lora_rank, dtype),
        # factor matrices stay dict-leaves (fake-quant ternary) — DESIGN.md §2
        "w_uk": qops.init_linear(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim, dtype),
        "w_uv": qops.init_linear(ks[4], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "wo": qops.init_linear(ks[5], h * m.v_head_dim, d, dtype),
    }
    if cfg.bitnet.lora_rank:
        from repro.core import lora as lora_lib

        if "v" in cfg.bitnet.lora_targets:
            p["lora_v"] = lora_lib.init(
                ks[6], m.kv_lora_rank, h * m.v_head_dim, cfg.bitnet.lora_rank, dtype
            )
        if "o" in cfg.bitnet.lora_targets:
            p["lora_o"] = lora_lib.init(
                ks[7], h * m.v_head_dim, d, cfg.bitnet.lora_rank, dtype
            )
    return p


def _mla_down(p, hidden, cfg: ModelConfig, mode):
    """Both MLA down-projections of the shared hidden: -> (dq, dkv).

    With the pack-time-fused leaf (models/pack.py: w_dq‖w_dkv ->
    "w_dqkv") this is ONE act-quant + ONE kernel launch; the per-branch
    norms (q_ln on dq, kv_ln on the latent half of dkv) interleave AFTER
    the split, in ``_mla_queries`` / ``_mla_latent``, so fused == separate
    bit-for-bit.
    """
    if "w_dqkv" in p:
        return qops.fused_linear(p["w_dqkv"], hidden, cfg)
    return (
        qops.linear(p["w_dq"], hidden, cfg, mode),
        qops.linear(p["w_dkv"], hidden, cfg, mode),
    )


def _mla_queries(p, dq, cfg: ModelConfig, mode, positions):
    """dq (b,t,q_rank) -> q_nope (b,t,h,dn), q_rope (b,t,h,dr) with RoPE.

    ``positions`` is batch-broadcastable: (1, s) for a shared full
    sequence, (b, 1) for per-slot decode positions.
    """
    m, h = cfg.mla, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    cq = rms_norm(dq, p["q_ln"], cfg.norm_eps)
    q = qops.linear(p["w_uq"], cq, cfg, mode, out_shape=(h, qk_head))
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim :], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, dkv, cfg: ModelConfig, positions):
    """dkv (b,t,dl+dr) -> latent c_kv (b,t,dl) [normed], k_rope with RoPE.

    ``positions`` is batch-broadcastable, as in ``_mla_queries``.
    """
    m = cfg.mla
    c_kv = rms_norm(dkv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = apply_rope(
        dkv[..., m.kv_lora_rank :][:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    return c_kv, k_rope


def mla_full(p, x, cfg: ModelConfig, mode, positions, *, return_kv: bool = False):
    """Full-sequence MLA (non-absorbed): expand K/V per position once."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    hidden = rms_norm(x, p["ln"], cfg.norm_eps)
    dq, dkv = _mla_down(p, hidden, cfg, mode)
    q_nope, q_rope = _mla_queries(p, dq, cfg, mode, positions[None])
    c_kv, k_rope = _mla_latent(p, dkv, cfg, positions[None])
    k_nope = qops.linear(p["w_uk"], c_kv, cfg, mode, out_shape=(h, m.qk_nope_head_dim))
    v = qops.linear(
        p["w_uv"], c_kv, cfg, mode, out_shape=(h, m.v_head_dim), lora_leaf=p.get("lora_v")
    )
    q = jnp.concatenate([q_nope, q_rope], axis=-1)  # (b,s,h,dn+dr)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.qk_rope_head_dim))],
        axis=-1,
    )
    qg = jnp.moveaxis(q, 1, 2)[:, :, None]  # (b,h,1,s,d) g=h, rep=1
    kg = jnp.moveaxis(k, 1, 2)
    vg = jnp.moveaxis(v, 1, 2)
    o = blockwise_attention(qg, kg, vg, causal=not cfg.is_encoder)[:, :, 0]
    o = jnp.moveaxis(o, 1, 2).reshape(b, s, h * m.v_head_dim)
    y = qops.linear(p["wo"], o, cfg, mode, lora_leaf=p.get("lora_o"))
    if return_kv:
        # cache the latent: k-slot = (c_kv ‖ k_rope), v-slot is empty (0-dim)
        lat = jnp.concatenate([c_kv, k_rope], axis=-1)
        return y, (lat, jnp.zeros(lat.shape[:-1] + (0,), lat.dtype))
    return y


def mla_prefill(p, x, cfg: ModelConfig, mode, cache: kvc.TieredKVCache,
                impl: str | None = None):
    """Full-prompt MLA prefill + latent cache fill for one layer.

    The Pallas path runs the flash-prefill kernel attention-only
    (``emit_kv=False``, ``rope_dims`` = the rope head dims): the per-head
    (nope ‖ rope) k materializes *unrotated* and both q_rope and k_rope
    rotate in the kernel prologue. The cached row is the latent
    (c_kv ‖ k_rope) — not the per-head k — so the fill rotates the shared
    (b, s, dr) rope vector once outside (negligible next to the (b, s,
    h, ·) tensors the kernel no longer needs pre-rotated) and places it
    with the static-slice ``fill_fresh``. The XLA path delegates to
    ``mla_full``; both fill bit-identical caches.
    """
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    impl = impl or qops.resolve_impl(cfg)
    positions = jnp.arange(s, dtype=jnp.int32)
    if impl != "pallas":
        y, (lat, v_empty) = mla_full(p, x, cfg, mode, positions, return_kv=True)
        return y, kvc.fill_fresh(cache, lat, v_empty)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    hidden = rms_norm(x, p["ln"], cfg.norm_eps)
    dq, dkv = _mla_down(p, hidden, cfg, mode)
    # same per-branch norms as _mla_queries/_mla_latent, minus their RoPE
    cq = rms_norm(dq, p["q_ln"], cfg.norm_eps)
    q = qops.linear(p["w_uq"], cq, cfg, mode, out_shape=(h, qk_head))
    c_kv = rms_norm(dkv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope_raw = dkv[..., m.kv_lora_rank:]  # (b, s, dr) UNROTATED
    k_nope = qops.linear(p["w_uk"], c_kv, cfg, mode, out_shape=(h, m.qk_nope_head_dim))
    v = qops.linear(
        p["w_uv"], c_kv, cfg, mode, out_shape=(h, m.v_head_dim),
        lora_leaf=p.get("lora_v"),
    )
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(
            k_rope_raw[:, :, None, :], (b, s, h, m.qk_rope_head_dim))],
        axis=-1,
    )
    o = fprefill.flash_prefill_attention(
        q, k, v, None, rope_theta=cfg.rope_theta,
        rope_dims=m.qk_rope_head_dim, emit_kv=False, impl="pallas",
    )  # (b, s, h, v_head_dim)
    y = qops.linear(
        p["wo"], o.reshape(b, s, h * m.v_head_dim), cfg, mode,
        lora_leaf=p.get("lora_o"),
    )
    k_rope = apply_rope(
        k_rope_raw[:, :, None, :], positions[None], cfg.rope_theta
    )[:, :, 0]
    lat = jnp.concatenate([c_kv, k_rope], axis=-1)
    return y, kvc.fill_fresh(
        cache, lat, jnp.zeros(lat.shape[:-1] + (0,), lat.dtype)
    )


def mla_decode(p, x, cfg: ModelConfig, mode, cache: kvc.TieredKVCache,
               active: jax.Array | None = None):
    """Absorbed-form MLA decode over the tiered latent cache.

    Per-slot positions from ``cache.lengths``; ``active`` gates the latent
    append per slot (continuous batching).
    """
    m, h = cfg.mla, cfg.n_heads
    b, _ = x.shape
    hidden = rms_norm(x[:, None, :], p["ln"], cfg.norm_eps)
    pos = cache.lengths[:, None]  # (b, 1)
    dq, dkv = _mla_down(p, hidden, cfg, mode)
    q_nope, q_rope = _mla_queries(p, dq, cfg, mode, pos)  # (b,1,h,·)
    c_kv, k_rope = _mla_latent(p, dkv, cfg, pos)
    lat_new = jnp.concatenate([c_kv, k_rope], axis=-1)[:, 0]  # (b, dl+dr)
    with jax.named_scope("kv_write"):
        cache = kvc.append_decode(
            cache, lat_new, jnp.zeros((b, 0), lat_new.dtype), active=active)

    # absorb W_uk into the query: q_abs = q_nope @ W_uk^T  (per head)
    w_uk = p["w_uk"]["w"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim)
    from repro.core.ternary import act_quant_ste, weight_quant_ste

    quant = cfg.bitnet.enabled and mode != "none"
    w_uk_q = weight_quant_ste(w_uk) if quant else w_uk
    q_abs = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0], w_uk_q)  # (b,h,dl)
    q_full = jnp.concatenate([q_abs, q_rope[:, 0]], axis=-1)  # (b,h,dl+dr)

    # fake-quantize the cached latent exactly as the non-absorbed path does
    # when it feeds c_kv through the W_uk/W_uv BitLinears (keeps absorbed ==
    # non-absorbed numerics; rope dims are never act-quantized).
    if quant:

        def _q(buf):
            if buf.shape[1] == 0:
                return buf
            ckv = act_quant_ste(buf[..., : m.kv_lora_rank], bits=cfg.bitnet.act_bits)
            return jnp.concatenate([ckv, buf[..., m.kv_lora_rank :]], axis=-1)

        att_cache = cache._replace(hot_k=_q(cache.hot_k), cold_k=_q(cache.cold_k))
    else:
        att_cache = cache

    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    with jax.named_scope("attention"):
        ctx = fd.flash_decode_attention_latent(
            q_full, att_cache, value_dim=m.kv_lora_rank, scale=scale,
            impl=qops.resolve_impl(cfg),
        )  # (b,h,dl)

    w_uv = p["w_uv"]["w"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    w_uv_q = weight_quant_ste(w_uv) if cfg.bitnet.enabled and mode != "none" else w_uv
    o = jnp.einsum("bhl,lhv->bhv", ctx, w_uv_q).reshape(b, h * m.v_head_dim)
    with jax.named_scope("o_proj"):
        y = qops.linear(
            p["wo"], o.astype(x.dtype), cfg, mode, lora_leaf=p.get("lora_o"))
    return y, cache
