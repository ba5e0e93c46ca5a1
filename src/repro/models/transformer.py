"""Top-level model: init / forward / prefill / decode for every family.

Families (DESIGN.md §4):
  dense          — decoder LM (qwen3, deepseek-coder, gemma)
  moe            — decoder LM with MoE FFNs (mixtral, deepseek-v3 incl. MLA)
  ssm            — attention-free Mamba2 stack (mamba2-130m)
  hybrid         — Zamba2: groups of Mamba2 blocks + one *shared* attention
                   block (single param set, per-invocation LoRA)
  audio          — encoder-only (hubert): bidirectional attention, stub
                   frame-embedding frontend, no decode
  vlm            — llava: stub patch-embedding frontend concatenated with
                   text embeddings, then a dense decoder

Uniform layers are stacked and scanned (lax.scan over stacked params) so
the HLO stays O(1) in depth — essential for compiling 61-layer 671B
configs on the 512-device dry-run mesh. Blocks are rematerialized
(jax.checkpoint) in training mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kv_cache as kvc
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import qops
from repro.models import shard_ctx
from repro.models import ssm as ssm_lib
from repro.models.layers import apply_mlp, init_mlp, init_rms_norm, rms_norm

DEFAULT_HOT_CAP = 32  # paper: 32 buffered early tokens (S=128 edge case)


# ---------------------------------------------------------------------------
# Block init/apply per family
# ---------------------------------------------------------------------------


def _init_attn_block(key, cfg: ModelConfig, dtype, d_ff=None) -> dict:
    k1, k2 = jax.random.split(key)
    init_a = attn.init_mla if cfg.attn_type == "mla" else attn.init_attention
    return {"attn": init_a(k1, cfg, dtype), "mlp": init_mlp(k2, cfg, d_ff, dtype)}


def _init_moe_block(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    init_a = attn.init_mla if cfg.attn_type == "mla" else attn.init_attention
    return {"attn": init_a(k1, cfg, dtype), "moe": moe_lib.init_moe(k2, cfg, dtype)}


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    keys = jax.random.split(key, 12)
    d = cfg.d_model
    params: dict = {
        "embed": {"w": jax.random.normal(keys[0], (cfg.vocab_size, d), dtype) * 0.02},
        "final_ln": init_rms_norm(d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = qops.init_linear(keys[1], d, cfg.vocab_size, dtype)

    if cfg.frontend == "audio":
        params["frontend"] = qops.init_linear(keys[2], cfg.frontend_dim, d, dtype)
    elif cfg.frontend == "vision":
        k1, k2 = jax.random.split(keys[2])
        params["frontend"] = {
            "proj1": qops.init_linear(k1, cfg.frontend_dim, d, dtype),
            "proj2": qops.init_linear(k2, d, d, dtype),
        }

    if cfg.family in ("dense", "audio", "vlm"):
        params["blocks"] = _stack_init(
            lambda k: _init_attn_block(k, cfg, dtype), keys[3], cfg.n_layers
        )
    elif cfg.family == "moe":
        nd = cfg.moe.n_dense_layers
        if nd:
            dff = cfg.moe.d_ff_dense or cfg.d_ff
            params["dense_blocks"] = _stack_init(
                lambda k: _init_attn_block(k, cfg, dtype, d_ff=dff), keys[3], nd
            )
        params["moe_blocks"] = _stack_init(
            lambda k: _init_moe_block(k, cfg, dtype), keys[4], cfg.n_layers - nd
        )
    elif cfg.family == "ssm":
        params["blocks"] = _stack_init(
            lambda k: ssm_lib.init_mamba_block(k, cfg, dtype), keys[3], cfg.n_layers
        )
    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // every
        n_tail = cfg.n_layers - n_groups * every
        params["mamba_groups"] = jax.vmap(
            lambda k: _stack_init(
                lambda kk: ssm_lib.init_mamba_block(kk, cfg, dtype), k, every
            )
        )(jax.random.split(keys[3], n_groups))
        if n_tail:
            params["mamba_tail"] = _stack_init(
                lambda k: ssm_lib.init_mamba_block(k, cfg, dtype), keys[5], n_tail
            )
        # ONE shared attention+MLP block (Zamba2) + per-invocation LoRA
        params["shared"] = _init_attn_block(keys[6], cfg, dtype)
        if cfg.bitnet.lora_rank:
            from repro.core import lora as lora_lib

            g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
            params["shared_lora_v"] = jax.vmap(
                lambda k: lora_lib.init(k, d, g * hd, cfg.bitnet.lora_rank, dtype)
            )(jax.random.split(keys[7], n_groups))
    else:  # pragma: no cover
        raise ValueError(cfg.family)
    return params


# ---------------------------------------------------------------------------
# Embedding / frontend
# ---------------------------------------------------------------------------


def _embed_tokens(params, cfg: ModelConfig, tokens: jax.Array, dtype) -> jax.Array:
    from repro.core.bitlinear import Int8Linear

    emb = params["embed"]
    with jax.named_scope("embed"):
        if isinstance(emb, Int8Linear):  # int8 rows + per-row scale
            x = (
                jnp.take(emb.q, tokens, axis=0).astype(jnp.float32)
                * jnp.take(emb.scale, tokens, axis=0)
            ).astype(dtype)
        else:
            x = jnp.take(emb["w"], tokens, axis=0).astype(dtype)
        if cfg.scale_embed:
            x = x * jnp.asarray(cfg.d_model**0.5, dtype)
        return x


def _frontend_embed(params, cfg: ModelConfig, feats: jax.Array, mode: str) -> jax.Array:
    if cfg.frontend == "audio":
        return qops.linear(params["frontend"], feats, cfg, mode)
    # vision: 2-layer MLP projector (llava)
    h = jax.nn.gelu(qops.linear(params["frontend"]["proj1"], feats, cfg, mode))
    return qops.linear(params["frontend"]["proj2"], h, cfg, mode)


def _lm_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    from repro.core.bitlinear import Int8Linear

    if cfg.tie_embeddings:
        emb = params["embed"]
        if isinstance(emb, Int8Linear):
            logits = (x @ emb.q.T.astype(x.dtype)).astype(jnp.float32)
            return logits * emb.scale[:, 0][None]  # per-row scale -> per-col
        return (x @ emb["w"].T.astype(x.dtype)).astype(jnp.float32)
    head = params["lm_head"]
    if isinstance(head, Int8Linear):
        logits = (x @ head.q.astype(x.dtype)).astype(jnp.float32)
        return logits * head.scale  # (1, V) per-column scale
    return qops.linear(head, x, cfg, "none", quantize=False).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill body)
# ---------------------------------------------------------------------------


def _attn_block_fwd(bp, x, cfg, mode, positions, return_kv=False):
    f = attn.mla_full if cfg.attn_type == "mla" else attn.attention_full
    if return_kv:
        y, kv = f(bp["attn"], x, cfg, mode, positions, return_kv=True)
    else:
        y, kv = f(bp["attn"], x, cfg, mode, positions), None
    x = x + y
    if "moe" in bp:
        h, aux = moe_lib.apply_moe(bp["moe"], x, cfg, mode)
    else:
        h, aux = apply_mlp(bp["mlp"], x, cfg, mode), 0.0
    return x + h, aux, kv


def _sp(x):
    """Sequence-parallel residual-stream constraint (no-op without hints).

    Between blocks the hidden state lives (batch->data, seq->model, d) —
    Megatron-SP: the row-parallel projections' partial sums reduce-scatter
    onto the sequence axis instead of all-reducing, and norms run on 1/TP
    of the tokens. Only applied to 3-D full-sequence activations.
    """
    if x.ndim == 3 and shard_ctx.active():
        return shard_ctx.constrain(x, "BATCH", "SEQ", None)
    return x


def _scan_stack(fn, x, stacked, remat: bool):
    body = jax.checkpoint(fn) if remat else fn

    def step(carry, bp):
        h, aux = carry
        h2, aux2 = body(h, bp)
        return (_sp(h2), aux + aux2), None

    (x, aux), _ = jax.lax.scan(step, (_sp(x), jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def _scan_stack_collect(fn, x, stacked, remat: bool):
    """Like _scan_stack but also stacks each layer's extra output (e.g. KV)."""
    body = jax.checkpoint(fn) if remat else fn

    def step(carry, bp):
        h, aux = carry
        h2, aux2, extra = body(h, bp)
        return (_sp(h2), aux + aux2), extra

    (x, aux), extras = jax.lax.scan(step, (_sp(x), jnp.zeros((), jnp.float32)), stacked)
    return x, aux, extras


def forward(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    mode: str = "qat",
    remat: bool = True,
    collect_kv: bool = False,
):
    """Full-sequence forward. Returns (logits_f32, aux_loss[, kv_stacks]).

    batch: {"tokens": (b,s)} and/or {"frames"/"patches": features}.
    """
    dtype = params["final_ln"].dtype
    kv_out: dict = {}

    if cfg.family == "audio":
        x = _frontend_embed(params, cfg, batch["frames"].astype(dtype), mode)
    elif cfg.family == "vlm":
        patches = _frontend_embed(params, cfg, batch["patches"].astype(dtype), mode)
        text = _embed_tokens(params, cfg, batch["tokens"], dtype)
        x = jnp.concatenate([patches, text], axis=1)
    else:
        x = _embed_tokens(params, cfg, batch["tokens"], dtype)

    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)

    if cfg.family in ("dense", "audio", "vlm"):
        if collect_kv:
            fn = lambda h, bp: _attn_block_fwd(bp, h, cfg, mode, positions, True)  # noqa: E731
            x, aux, kvs = _scan_stack_collect(fn, x, params["blocks"], remat)
            kv_out["attn"] = kvs  # (L, 2-tuple of (b,s,g,hd))
        else:
            fn = lambda h, bp: _attn_block_fwd(bp, h, cfg, mode, positions)[:2]  # noqa: E731
            x, aux = _scan_stack(fn, x, params["blocks"], remat)
    elif cfg.family == "moe":
        aux = jnp.zeros((), jnp.float32)
        for name in ("dense_blocks", "moe_blocks"):
            if name not in params:
                continue
            if collect_kv:
                fn = lambda h, bp: _attn_block_fwd(bp, h, cfg, mode, positions, True)  # noqa: E731
                x, a2, kvs = _scan_stack_collect(fn, x, params[name], remat)
                kv_out[name] = kvs
            else:
                fn = lambda h, bp: _attn_block_fwd(bp, h, cfg, mode, positions)[:2]  # noqa: E731
                x, a2 = _scan_stack(fn, x, params[name], remat)
            aux = aux + a2
    elif cfg.family == "ssm":
        if collect_kv:
            fn = lambda h, bp: (  # noqa: E731
                *_ssm_fwd_state(bp, h, cfg, mode),
            )
            x, aux, states = _scan_stack_collect(fn, x, params["blocks"], remat)
            kv_out["ssm"] = states
        else:
            fn = lambda h, bp: (ssm_lib.apply_mamba_full(bp, h, cfg, mode), 0.0)  # noqa: E731
            x, aux = _scan_stack(fn, x, params["blocks"], remat)
    elif cfg.family == "hybrid":
        x, aux, kvs = _hybrid_forward(params, cfg, x, mode, positions, remat, collect_kv)
        kv_out.update(kvs)
    else:  # pragma: no cover
        raise ValueError(cfg.family)

    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = _lm_logits(params, cfg, x)
    if collect_kv:
        return logits, aux, kv_out
    return logits, aux


def _ssm_fwd_state(bp, h, cfg, mode):
    y, st = ssm_lib.apply_mamba_full(bp, h, cfg, mode, return_state=True)
    return y, 0.0, st


def _hybrid_forward(params, cfg, x, mode, positions, remat, collect_kv):
    """Zamba2: [group of `every` mamba blocks + shared attn] × G + tail."""
    kv_out: dict = {}

    def group_fn(h, xs):
        gp = xs["mamba"]
        extras = {}
        if collect_kv:
            fn = lambda hh, bp: _ssm_fwd_state(bp, hh, cfg, mode)  # noqa: E731
            h, _, states = _scan_stack_collect(fn, h, gp, remat)
            extras["ssm"] = states
        else:
            fn = lambda hh, bp: (ssm_lib.apply_mamba_full(bp, hh, cfg, mode), 0.0)  # noqa: E731
            h, _ = _scan_stack(fn, h, gp, remat)
        sp = dict(params["shared"])
        if "lora_v" in xs:
            sp = {"attn": {**params["shared"]["attn"], "lora_v": xs["lora_v"]},
                  "mlp": params["shared"]["mlp"]}
        h2, _, kv = _attn_block_fwd(sp, h, cfg, mode, positions, collect_kv)
        if collect_kv:
            extras["attn_kv"] = kv
        return h2, extras

    xs = {"mamba": params["mamba_groups"]}
    if "shared_lora_v" in params:
        xs["lora_v"] = params["shared_lora_v"]

    def scan_step(h, xs_i):
        h2, extras = group_fn(h, xs_i)
        return h2, extras

    x, extras = jax.lax.scan(scan_step, x, xs)
    if collect_kv:
        kv_out["hybrid"] = extras

    if "mamba_tail" in params:
        if collect_kv:
            fn = lambda hh, bp: _ssm_fwd_state(bp, hh, cfg, mode)  # noqa: E731
            x, _, st = _scan_stack_collect(fn, x, params["mamba_tail"], remat)
            kv_out["tail_ssm"] = st
        else:
            fn = lambda hh, bp: (ssm_lib.apply_mamba_full(bp, hh, cfg, mode), 0.0)  # noqa: E731
            x, _ = _scan_stack(fn, x, params["mamba_tail"], remat)
    return x, jnp.zeros((), jnp.float32), kv_out


# ---------------------------------------------------------------------------
# Serving: prefill + decode with the tiered DR cache
# ---------------------------------------------------------------------------


def _attn_cache_spec(cfg: ModelConfig):
    if cfg.attn_type == "mla":
        return (cfg.mla.kv_cache_dim,), (0,)
    g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return (g, hd), (g, hd)


def init_decode_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    hot_cap: int = DEFAULT_HOT_CAP,
    dtype=jnp.bfloat16,
    paged: bool = False,
    page_size: int = 256,
    n_pages: Optional[int] = None,
):
    """Empty cache pytree for this arch (stacked per layer-stack).

    With ``paged`` the attention stacks use the page-table-indirected
    cold tier (``kv_cache.PagedKVCache``): one shared ``n_pages`` pool
    per layer, page ids meaning the same physical page index in every
    stack's pool (the serving engine's host-side page accounting is a
    single id space across layers and stacks)."""

    def attn_cache(n_layers):
        kshape, vshape = _attn_cache_spec(cfg)
        kv_dtype = jnp.float8_e4m3fn if cfg.bitnet.kv_fp8 else dtype
        if cfg.attn_type == "swa":
            hc, cc = 0, min(cfg.swa_window, max_len)
        else:
            hc, cc = min(hot_cap, max_len), max_len - min(hot_cap, max_len)
        if paged:
            assert cfg.attn_type != "swa", "paged cold tier has no ring layout"
            one = kvc.init_paged_cache(
                batch, hc, cc, kshape, kv_dtype,
                page_size=page_size, n_pages=n_pages,
            )
            if vshape == (0,):
                one = one._replace(
                    hot_v=jnp.zeros(one.hot_v.shape[:2] + (0,), kv_dtype),
                    pool_v=jnp.zeros(one.pool_v.shape[:2] + (0,), kv_dtype),
                )
        else:
            one = kvc.init_cache(batch, hc, cc, kshape, kv_dtype)
            if vshape == (0,):
                one = one._replace(
                    hot_v=jnp.zeros(one.hot_v.shape[:2] + (0,), kv_dtype),
                    cold_v=jnp.zeros(one.cold_v.shape[:2] + (0,), kv_dtype),
                )
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n_layers,) + a.shape), one)

    def ssm_state(n_layers, lead=()):
        one = ssm_lib.init_mamba_state(batch, cfg, dtype)
        return jax.tree.map(
            lambda a: jnp.zeros(lead + (n_layers,) + a.shape, a.dtype), one
        )

    if cfg.family in ("dense", "vlm"):
        return {"attn": attn_cache(cfg.n_layers)}
    if cfg.family == "moe":
        nd = cfg.moe.n_dense_layers
        out = {"attn_moe": attn_cache(cfg.n_layers - nd)}
        if nd:
            out["attn_dense"] = attn_cache(nd)
        return out
    if cfg.family == "ssm":
        return {"ssm": ssm_state(cfg.n_layers)}
    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        ng = cfg.n_layers // every
        nt = cfg.n_layers - ng * every
        out = {
            "mamba": jax.tree.map(
                lambda a: jnp.zeros((ng,) + a.shape, a.dtype),
                ssm_state(every),
            ),
            "attn": attn_cache(ng),
        }
        if nt:
            out["tail"] = ssm_state(nt)
        return out
    raise ValueError(cfg.family)


def _fill_attn_cache(cache_stack, kvs, cfg):
    """Bulk-place prefill KV (L, b, s, ...) into a stacked fresh tiered
    cache — ``kv_cache.fill_fresh`` per layer (static slices; the ring
    realign for SWA windows lives there, in exactly one place)."""
    ks, vs = kvs
    ring = cfg.attn_type == "swa"
    return jax.vmap(
        lambda c, k, v: kvc.fill_fresh(c, k, v, ring=ring)
    )(cache_stack, ks, vs)


def _flash_prefill_capable(cfg: ModelConfig, impl: str) -> bool:
    """The per-layer flash-prefill scan path covers the attention-cache
    families; SSM/hybrid keep the collect-state forward (their cache is
    recurrent state, not KV) and the XLA impl keeps the legacy path so
    the GSPMD dry-run lowering is untouched."""
    return (
        impl == "pallas"
        and cfg.family in ("dense", "vlm", "moe")
        and cfg.attn_type in ("full", "swa", "mla")
    )


def prefill(
    params: dict,
    cfg: ModelConfig,
    batch: dict,
    hot_cap: int = DEFAULT_HOT_CAP,
    max_len: Optional[int] = None,
    mode: str = "packed",
    remat: bool = False,
    headroom: Optional[int] = None,
):
    """Process the prompt; return (last-token logits, filled decode cache).

    Cache capacity is ``max_len`` when given, else ``prompt_len +
    headroom`` (defaulting to ``cfg.decode_headroom``) — the headroom is
    the hard cap on how many tokens can subsequently be decoded, so
    callers that rely on the default must size it deliberately.

    On the Pallas impl (``qops.resolve_impl``) attention-cache families
    run the per-layer flash-prefill scan (``attention_prefill`` /
    ``mla_prefill``: fused RoPE + causal-skip streaming + tier-dtype
    cache-fill epilogue, kernels/flash_prefill.py); otherwise the legacy
    collect-KV forward + bulk fill runs, numerically as before.
    """
    tokens = batch.get("tokens")
    if cfg.family == "vlm":
        s = tokens.shape[1] + cfg.n_patches
        b = tokens.shape[0]
    elif cfg.family == "audio":
        raise ValueError("encoder-only arch has no decode/prefill phase")
    else:
        b, s = tokens.shape
    if max_len is None:
        max_len = s + (headroom if headroom is not None else cfg.decode_headroom)

    from repro.models import qops

    if _flash_prefill_capable(cfg, qops.resolve_impl(cfg)):
        return _prefill_flash(params, cfg, batch, b, s, hot_cap, max_len, mode)

    logits, aux, kvs = forward(params, cfg, batch, mode, remat=remat, collect_kv=True)
    cache = init_decode_cache(cfg, b, max_len, hot_cap, dtype=params["final_ln"].dtype)

    if cfg.family in ("dense", "vlm"):
        cache["attn"] = _fill_attn_cache(cache["attn"], kvs["attn"], cfg)
    elif cfg.family == "moe":
        cache["attn_moe"] = _fill_attn_cache(cache["attn_moe"], kvs["moe_blocks"], cfg)
        if "attn_dense" in cache:
            cache["attn_dense"] = _fill_attn_cache(
                cache["attn_dense"], kvs["dense_blocks"], cfg
            )
    elif cfg.family == "ssm":
        cache["ssm"] = kvs["ssm"]
    elif cfg.family == "hybrid":
        cache["mamba"] = kvs["hybrid"]["ssm"]
        cache["attn"] = _fill_attn_cache(cache["attn"], kvs["hybrid"]["attn_kv"], cfg)
        if "tail_ssm" in kvs:
            cache["tail"] = kvs["tail_ssm"]
    return logits[:, -1], cache


def _attn_block_prefill(bp, x, cfg, mode, cache_layer, n_valid=None):
    """One block of the flash-prefill scan: full-seq attention straight
    into the tiered cache rows, then the MLP/MoE. ``n_valid`` switches
    the chunked continuation form (serving engine)."""
    if n_valid is not None:
        y, cache_layer = attn.attention_prefill_chunk(
            bp["attn"], x, cfg, mode, cache_layer, n_valid
        )
    elif cfg.attn_type == "mla":
        y, cache_layer = attn.mla_prefill(bp["attn"], x, cfg, mode, cache_layer)
    else:
        y, cache_layer = attn.attention_prefill(bp["attn"], x, cfg, mode, cache_layer)
    x = x + y
    with jax.named_scope("mlp"):
        if "moe" in bp:
            h, _ = moe_lib.apply_moe(bp["moe"], x, cfg, mode)
        else:
            h = apply_mlp(bp["mlp"], x, cfg, mode)
    return x + h, cache_layer


def _prefill_scan(params, cfg, x, cache, mode, n_valid=None):
    """Scan the stacked attention blocks over (params, cache) pairs —
    decode_step's structure at full sequence length."""

    def scan_attn(x1, stack_params, cache_stack):
        def step(h, xs):
            bp, cl = xs
            return _attn_block_prefill(bp, h, cfg, mode, cl, n_valid)

        return jax.lax.scan(step, x1, (stack_params, cache_stack))

    with jax.named_scope("layers"):
        if cfg.family in ("dense", "vlm"):
            x, cache["attn"] = scan_attn(x, params["blocks"], cache["attn"])
        elif cfg.family == "moe":
            if "attn_dense" in cache:
                x, cache["attn_dense"] = scan_attn(
                    x, params["dense_blocks"], cache["attn_dense"]
                )
            x, cache["attn_moe"] = scan_attn(
                x, params["moe_blocks"], cache["attn_moe"])
        else:  # pragma: no cover — guarded by _flash_prefill_capable / engine
            raise ValueError(cfg.family)
    return x, cache


def _prefill_flash(params, cfg, batch, b, s, hot_cap, max_len, mode):
    """Pallas prefill: per-layer flash-attention + cache-fill scan."""
    dtype = params["final_ln"].dtype
    if cfg.family == "vlm":
        patches = _frontend_embed(params, cfg, batch["patches"].astype(dtype), mode)
        text = _embed_tokens(params, cfg, batch["tokens"], dtype)
        x = jnp.concatenate([patches, text], axis=1)
    else:
        x = _embed_tokens(params, cfg, batch["tokens"], dtype)
    cache = init_decode_cache(cfg, b, max_len, hot_cap, dtype=dtype)
    x, cache = _prefill_scan(params, cfg, x, cache, mode)
    x_last = rms_norm(x[:, -1], params["final_ln"], cfg.norm_eps)
    return _lm_logits(params, cfg, x_last), cache


def prefill_chunk_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,  # (slots, C) — one prompt chunk per slot
    cache,
    n_valid: jax.Array,  # (slots,) valid rows; 0 = slot not prefilling
    mode: str = "packed",
):
    """One chunked-prefill dispatch over the live slot state.

    Appends each slot's ``n_valid`` chunk tokens at its own
    ``cache.lengths`` offset and returns (last-valid-row logits (slots,
    V), cache). Every shape is fixed by (slots, C), so the serving
    engine compiles this exactly once regardless of the prompt-length
    mix (the compile-count assertion in tests/test_scheduler.py).
    Supported for attention-cache families without a frontend — the
    engine falls back to grouped whole-prompt admission elsewhere.
    """
    dtype = params["final_ln"].dtype
    x = _embed_tokens(params, cfg, tokens, dtype)  # (slots, C, d)
    x, cache = _prefill_scan(params, cfg, x, cache, mode, n_valid=n_valid)
    with jax.named_scope("lm_head"):
        # logits at each slot's last valid row (garbage for idle slots)
        idx = jnp.clip(n_valid.astype(jnp.int32) - 1, 0, tokens.shape[1] - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        x_last = rms_norm(x_last, params["final_ln"], cfg.norm_eps)
        return _lm_logits(params, cfg, x_last), cache


def _spec_scan(params, cfg, x, cache, mode, n_valid):
    """Verification scan: `_prefill_scan`'s structure, but attention runs
    in the no-append form and each layer's rotated chunk k/v is collected
    instead of written — the cache is READ, never mutated. The collected
    (k, v) stacks feed :func:`spec_commit_chunk` once the accept length
    is known."""

    def scan_attn(x1, stack_params, cache_stack):
        def step(h, xs):
            bp, cl = xs
            y, kv = attn.attention_prefill_chunk(
                bp["attn"], h, cfg, mode, cl, n_valid, append=False
            )
            h = h + y
            if "moe" in bp:
                h2, _ = moe_lib.apply_moe(bp["moe"], h, cfg, mode)
            else:
                h2 = apply_mlp(bp["mlp"], h, cfg, mode)
            return h + h2, kv

        return jax.lax.scan(step, x1, (stack_params, cache_stack))

    kvs = {}
    if cfg.family in ("dense", "vlm"):
        x, kvs["attn"] = scan_attn(x, params["blocks"], cache["attn"])
    elif cfg.family == "moe":
        if "attn_dense" in cache:
            x, kvs["attn_dense"] = scan_attn(
                x, params["dense_blocks"], cache["attn_dense"]
            )
        x, kvs["attn_moe"] = scan_attn(x, params["moe_blocks"], cache["attn_moe"])
    else:  # pragma: no cover — guarded by the engine's capability gate
        raise ValueError(cfg.family)
    return x, kvs


def spec_verify_chunk(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,  # (slots, K) — pending token ‖ draft proposals
    cache,
    n_valid: jax.Array,  # (slots,) valid chunk rows; 0 = slot inactive
    mode: str = "packed",
):
    """Speculative verification: ONE chunk-shaped dispatch that scores a
    K-token draft chunk against the live cache WITHOUT appending.

    Returns ``(logits, kvs)`` where ``logits`` is (slots, K, vocab) —
    the target model's distribution after every chunk position, which
    the engine's acceptance kernel argmaxes against the draft — and
    ``kvs`` maps each attention stack to its (L, slots, K, ...) rotated
    chunk k/v, ready for :func:`spec_commit_chunk`. Deferring the
    append is what makes rollback trivial (nothing to roll back) and
    ring (SWA) caches safe to speculate on. Shapes are fixed by
    (slots, K): one compile per engine, same contract as
    ``prefill_chunk_step``.
    """
    dtype = params["final_ln"].dtype
    x = _embed_tokens(params, cfg, tokens, dtype)  # (slots, K, d)
    x, kvs = _spec_scan(params, cfg, x, cache, mode, n_valid)
    x = rms_norm(x, params["final_ln"], cfg.norm_eps)
    return _lm_logits(params, cfg, x), kvs


def spec_commit_chunk(cfg: ModelConfig, cache, kvs, n_commit: jax.Array):
    """Append the first ``n_commit[b]`` verified chunk rows of each slot
    to the live cache (the accept step of draft-verify speculation).

    ``kvs`` is :func:`spec_verify_chunk`'s per-stack (L, slots, K, ...)
    k/v; the append vmaps over the layer axis, so tiered and paged
    stacks both work. Linear layouts may commit the full chunk and roll
    back via ``kv_cache.truncate``; ring layouts MUST pass the accepted
    count here (a ring append is destructive — see ``truncate``)."""
    ring = cfg.attn_type == "swa"
    out = dict(cache)
    for key, (k, v) in kvs.items():
        out[key] = jax.vmap(
            lambda c, kk, vv: kvc.append(c, kk, vv, valid=n_commit, ring=ring)
        )(cache[key], k, v)
    return out


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def _attn_block_decode(bp, x1, cfg, mode, cache_layer, active=None):
    f = attn.mla_decode if cfg.attn_type == "mla" else attn.attention_decode
    y, cache_layer = f(bp["attn"], x1, cfg, mode, cache_layer, active=active)
    x1 = x1 + y
    with jax.named_scope("mlp"):
        if "moe" in bp:
            h, _ = moe_lib.apply_moe(bp["moe"], x1[:, None, :], cfg, mode)
            h = h[:, 0]
        else:
            h = apply_mlp(bp["mlp"], x1[:, None, :], cfg, mode)[:, 0]
    return x1 + h, cache_layer


def decode_step(params: dict, cfg: ModelConfig, tokens: jax.Array, cache,
                mode: str = "packed", active: Optional[jax.Array] = None):
    """One token for the whole batch. tokens: (b,) int32 -> (logits, cache).

    Each batch row is an independent *slot* at its own sequence length
    (``cache.lengths``). ``active`` (b,) bool gates cache mutation per
    slot: inactive slots (retired or unadmitted, in continuous batching)
    still flow through the compute — their logits are garbage and ignored
    by the caller — but neither append KV nor advance recurrent state.
    """
    dtype = params["final_ln"].dtype
    x = _embed_tokens(params, cfg, tokens[:, None], dtype)[:, 0]  # (b, d)

    def scan_attn(x1, stack_params, cache_stack):
        def step(h, xs):
            bp, cl = xs
            h2, cl2 = _attn_block_decode(bp, h, cfg, mode, cl, active)
            return h2, cl2

        return jax.lax.scan(step, x1, (stack_params, cache_stack))

    def _mask_state(new_state, old_state):
        if active is None:
            return new_state
        return jax.tree.map(
            lambda n, o: jnp.where(
                active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o
            ),
            new_state,
            old_state,
        )

    def scan_ssm(x1, stack_params, state_stack):
        def step(h, xs):
            bp, st = xs
            h2, st2 = ssm_lib.apply_mamba_decode(bp, h, cfg, mode, st)
            return h2, _mask_state(st2, st)

        return jax.lax.scan(step, x1, (stack_params, state_stack))

    with jax.named_scope("layers"):
        if cfg.family in ("dense", "vlm"):
            x, cache["attn"] = scan_attn(x, params["blocks"], cache["attn"])
        elif cfg.family == "moe":
            if "attn_dense" in cache:
                x, cache["attn_dense"] = scan_attn(
                    x, params["dense_blocks"], cache["attn_dense"]
                )
            x, cache["attn_moe"] = scan_attn(x, params["moe_blocks"], cache["attn_moe"])
        elif cfg.family == "ssm":
            x, cache["ssm"] = scan_ssm(x, params["blocks"], cache["ssm"])
        elif cfg.family == "hybrid":

            def group_step(h, xs):
                gp, gstate, acache, lora_v = xs
                h, gstate2 = scan_ssm(h, gp, gstate)
                sp = {"attn": params["shared"]["attn"], "mlp": params["shared"]["mlp"]}
                if lora_v is not None:
                    sp = {"attn": {**sp["attn"], "lora_v": lora_v}, "mlp": sp["mlp"]}
                h, acache2 = _attn_block_decode(sp, h, cfg, mode, acache, active)
                return h, (gstate2, acache2)

            lora_stack = params.get("shared_lora_v")
            if lora_stack is None:
                def step(h, xs_i):
                    gp, gstate, acache = xs_i
                    return group_step(h, (gp, gstate, acache, None))
                x, (cache["mamba"], cache["attn"]) = jax.lax.scan(
                    step, x, (params["mamba_groups"], cache["mamba"], cache["attn"])
                )
            else:
                def step(h, xs_i):
                    gp, gstate, acache, lv = xs_i
                    return group_step(h, (gp, gstate, acache, lv))
                x, (cache["mamba"], cache["attn"]) = jax.lax.scan(
                    step, x, (params["mamba_groups"], cache["mamba"], cache["attn"], lora_stack)
                )
            if "tail" in cache:
                x, cache["tail"] = scan_ssm(x, params["mamba_tail"], cache["tail"])
        else:  # pragma: no cover
            raise ValueError(cfg.family)

    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = _lm_logits(params, cfg, x)
    return logits, cache
