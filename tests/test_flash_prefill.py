"""Flash-prefill attention kernel parity (kernels/flash_prefill.py).

The streaming Pallas kernel (interpret mode on CPU) against the XLA
reference paths (``kv_cache.tiered_chunk_attention`` for continuation,
``blockwise_attention`` composition for fresh prefill) and explicit fp32
oracles. Attention parity is fp32-reference parity to tight tolerance
(streaming merge order differs); the **cache-fill epilogue is asserted
bit-identical** — the emitted rotated k / v must equal the legacy
apply_rope + ``_fill_attn_cache``/``append`` pipeline exactly, fp8 tiers
included.

Covers the ISSUE 5 parity matrix:
  * causal boundaries at every q-block and kv-block edge (fresh prefill,
    blocks that do and don't divide the sequence);
  * SWA window masking, fresh and ring-continuation (wrapped window);
  * ``q_offset`` continuation over a populated tiered cache with mixed
    per-slot offsets AND mixed per-slot valid chunk lengths;
  * fp8(e4m3) cold-tier fill vs the ``_fill_attn_cache`` oracle
    (bit-identical, both tiers);
  * MLA prefill (rope_dims < head, attention-only kernel form);
  * b = 1..8;
  * the models/attention.py + transformer.py wiring: full-model prefill
    under impl="pallas" matches impl="xla" (logits to tolerance, caches
    bit-identical) for dense / SWA / MLA / VLM smoke archs;
  * the "prefill_attn" row of ops.select_blocks.

Everything runs in Pallas interpret mode on CPU — part of the CI
kernel-parity lane (pytest -m kernel_parity).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as kvc
from repro.kernels import flash_prefill as fp
from repro.kernels import ops
from repro.models.layers import apply_rope

pytestmark = pytest.mark.kernel_parity

TOL = dict(rtol=2e-5, atol=2e-5)
THETA = 1e4


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _build_cache(b, hot, cold, g, d, lens, dv=None, dtype=jnp.float32,
                 ring=False, seed=0):
    """Cache with per-slot lengths via active-masked decode appends, in
    the stored (g·d) layout."""
    dv = dv or d
    cache = kvc.init_cache(b, hot, cold, (g * d,), dtype)
    if dv != d:
        cache = cache._replace(
            hot_v=jnp.zeros((b, hot, g * dv), dtype),
            cold_v=jnp.zeros((b, cold, g * dv), dtype),
        )
    app = kvc.append_decode_ring if ring else kvc.append_decode
    for t in range(max(lens)):
        active = jnp.asarray([t < L for L in lens])
        k1 = jax.random.normal(jax.random.PRNGKey(seed + t), (b, g, d))
        v1 = jax.random.normal(jax.random.PRNGKey(seed + 500 + t), (b, g, dv))
        cache = app(cache, k1, v1, active=active)
    return cache


def _qkv(b, c, h, g, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (b, c, h, dk)),
        jax.random.normal(ks[1], (b, c, g, dk)),
        jax.random.normal(ks[2], (b, c, g, dv)),
    )


def _both(q, k, v, cache, **kw):
    got = fp.flash_prefill_attention(q, k, v, cache, impl="pallas",
                                     rope_theta=THETA, **kw)
    want = fp.flash_prefill_attention(q, k, v, cache, impl="xla",
                                      rope_theta=THETA, **kw)
    return got, want


def _assert_parity(got, want, valid=None, o_tol=TOL):
    """Attention rows within each slot's valid count to tolerance; the
    emitted cache-fill k/v bit-identical."""
    emit = isinstance(got, tuple)
    o_g = got[0] if emit else got
    o_w = want[0] if emit else want
    b, c = o_g.shape[:2]
    for i in range(b):
        nv = int(valid[i]) if valid is not None else c
        np.testing.assert_allclose(
            np.asarray(o_g, np.float32)[i, :nv],
            np.asarray(o_w, np.float32)[i, :nv], **o_tol,
        )
    if emit:
        np.testing.assert_array_equal(
            np.asarray(got[1], np.float32), np.asarray(want[1], np.float32))
        np.testing.assert_array_equal(
            np.asarray(got[2], np.float32), np.asarray(want[2], np.float32))


# ---------------------------------------------------------------------------
# fresh aligned prefill: causal boundaries at every block edge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s", [1, 3, 4, 5, 8, 11, 16])
def test_fresh_causal_every_block_edge(s):
    """block_q = block_s = 4: s sweeps below / at / past every q- and
    kv-block boundary, including non-dividing lengths."""
    q, k, v = _qkv(2, s, 4, 2, 8, 8, seed=s)
    got, want = _both(q, k, v, None, block_q=4, block_s=4)
    _assert_parity(got, want)


def test_fresh_matches_numpy_oracle():
    b, c, h, g, dk = 2, 12, 4, 2, 8
    q, k, v = _qkv(b, c, h, g, dk, dk, seed=9)
    pos = jnp.arange(c, dtype=jnp.int32)[None]
    qr = np.asarray(apply_rope(q, pos, THETA), np.float64)
    kr = np.asarray(apply_rope(k, pos, THETA), np.float64)
    vv = np.asarray(v, np.float64)
    rep = h // g
    oracle = np.zeros((b, c, h, dk))
    for i in range(b):
        for hh in range(h):
            for t in range(c):
                lg = (qr[i, t, hh] @ kr[i, : t + 1, hh // rep].T) * dk**-0.5
                p = np.exp(lg - lg.max())
                oracle[i, t, hh] = (p / p.sum()) @ vv[i, : t + 1, hh // rep]
    got = fp.flash_prefill_attention(
        q, k, v, None, impl="pallas", rope_theta=THETA, emit_kv=False,
        block_q=4, block_s=4,
    )
    np.testing.assert_allclose(np.asarray(got), oracle, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [1, 3, 8])
def test_fresh_swa_window(window):
    q, k, v = _qkv(2, 11, 4, 2, 8, 8, seed=window)
    got, want = _both(q, k, v, None, window=window, block_q=4, block_s=4)
    _assert_parity(got, want)


@pytest.mark.parametrize("b", [1, 2, 5, 8])
def test_fresh_batch_sizes(b):
    q, k, v = _qkv(b, 9, 6, 3, 8, 8, seed=40 + b)
    got, want = _both(q, k, v, None, block_q=4, block_s=4)
    _assert_parity(got, want)


def test_fresh_dv_not_dk():
    q, k, v = _qkv(2, 10, 4, 2, 16, 8, seed=3)
    got, want = _both(q, k, v, None, block_q=4, block_s=4)
    _assert_parity(got, want)


# ---------------------------------------------------------------------------
# q_offset continuation over a populated tiered cache
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lens,valid", [
    ([0, 5, 12], [8, 8, 8]),      # offsets at 0 / mid-hot / into cold
    ([4, 16, 7], [8, 3, 0]),      # offset at the hot/cold edge; idle slot
    ([3, 9, 15], [1, 5, 8]),      # partial chunks at every tier boundary
])
def test_continuation_mixed_offsets_and_valid(lens, valid):
    b, c, g, d = 3, 8, 2, 8
    cache = _build_cache(b, 4, 12, g, d, lens, seed=sum(lens))
    q, k, v = _qkv(b, c, 4, g, d, d, seed=sum(valid))
    val = jnp.asarray(valid, jnp.int32)
    got, want = _both(q, k, v, cache, valid=val, block_q=4, block_s=4)
    _assert_parity(got, want, valid=valid)


def test_continuation_oracle_prefix_plus_chunk():
    """Explicit fp32 oracle: chunk rows attend [cached prefix ‖ causal
    chunk rows] at absolute positions."""
    b, c, g, h, d = 2, 6, 2, 4, 8
    lens = [5, 11]
    cache = _build_cache(b, 4, 12, g, d, lens, seed=77)
    q, k, v = _qkv(b, c, h, g, d, d, seed=78)
    pos = cache.lengths.astype(jnp.int32)[:, None] + jnp.arange(c)[None]
    qr = np.asarray(apply_rope(q, pos, THETA), np.float64)
    kr = np.asarray(apply_rope(k, pos, THETA), np.float64)
    rep = h // g
    got = fp.flash_prefill_attention(
        q, k, v, cache, impl="pallas", rope_theta=THETA, emit_kv=False,
        block_q=2, block_s=4,
    )
    for i in range(b):
        L = lens[i]
        n_hot = min(L, 4)
        ks_hist = np.concatenate(
            [np.asarray(cache.hot_k[i, :n_hot], np.float64),
             np.asarray(cache.cold_k[i, : L - n_hot], np.float64)]
        ).reshape(L, g, d)
        vs_hist = np.concatenate(
            [np.asarray(cache.hot_v[i, :n_hot], np.float64),
             np.asarray(cache.cold_v[i, : L - n_hot], np.float64)]
        ).reshape(L, g, d)
        for t in range(c):
            for hh in range(h):
                gg = hh // rep
                keys = np.concatenate([ks_hist[:, gg], kr[i, : t + 1, gg]])
                vals = np.concatenate([vs_hist[:, gg],
                                       np.asarray(v, np.float64)[i, : t + 1, gg]])
                lg = (qr[i, t, hh] @ keys.T) * d**-0.5
                p = np.exp(lg - lg.max())
                np.testing.assert_allclose(
                    np.asarray(got)[i, t, hh], (p / p.sum()) @ vals,
                    rtol=2e-5, atol=2e-5,
                )


def test_continuation_ring_wrapped_window():
    """SWA ring continuation: the chunk's later rows slide the window past
    the oldest ring entries — absolute ring positions must mask them."""
    b, c, g, d, w = 2, 6, 2, 8, 8
    lens = [10, 3]  # slot 0 wrapped, slot 1 not
    cache = _build_cache(b, 0, w, g, d, lens, ring=True, seed=31)
    q, k, v = _qkv(b, c, 4, g, d, d, seed=32)
    val = jnp.asarray([6, 4], jnp.int32)
    got, want = _both(q, k, v, cache, valid=val, window=w, ring=True,
                      block_q=2, block_s=4)
    _assert_parity(got, want, valid=[6, 4])


def test_continuation_ring_non_dividing_block():
    """Ring window NOT a multiple of the S-block (w=6, block_s=4): the
    partial last cold block's padding columns must not wrap back into
    valid positions via the modulo (regression: uninitialized rows fed
    the softmax as NaN)."""
    b, c, g, d, w = 2, 4, 2, 8, 6
    lens = [9, 5]  # wrapped and unwrapped
    cache = _build_cache(b, 0, w, g, d, lens, ring=True, seed=41)
    q, k, v = _qkv(b, c, 4, g, d, d, seed=42)
    val = jnp.asarray([4, 3], jnp.int32)
    got, want = _both(q, k, v, cache, valid=val, window=w, ring=True,
                      block_q=2, block_s=4)
    assert np.isfinite(np.asarray(got[0], np.float32)).all()
    _assert_parity(got, want, valid=[4, 3])


# ---------------------------------------------------------------------------
# cache-fill epilogue: bit-identical to the legacy fill, fp8 included
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float8_e4m3fn])
def test_fill_matches_fill_attn_cache_oracle(dtype):
    """The cache-fill epilogue against the legacy pipeline, decomposed
    into its two contracts:

    * **rotation** — the kernel's in-kernel RoPE vs a standalone
      ``apply_rope`` graph agree to 1-2 f32 ulp (the expressions are
      identical; XLA fuses the multiply-adds differently across
      compilation contexts, so strict cross-graph bit-equality is not
      achievable — within one pairing, e.g. the pallas-vs-xla entry
      emits asserted all over this file, equality IS bitwise);
    * **placement + tier-dtype cast** — feeding the same rotated rows to
      ``fill_fresh`` (the kernel path's placement) and to the legacy
      ``_fill_attn_cache`` one-hot pass must fill hot AND cold tier
      bit-identically, fp8/bf16 quantization included.
    """
    from repro.models import transformer as T

    b, s, g, d, hot, cold = 2, 12, 2, 8, 4, 16
    _, k, v = _qkv(b, s, 4, g, d, d, seed=5)
    q = jax.random.normal(jax.random.PRNGKey(6), (b, s, 4, d))
    _, k_c, v_c = fp.flash_prefill_attention(
        q, k, v, None, impl="pallas", rope_theta=THETA, emit_kv=True,
        kv_dtype=dtype, block_q=4, block_s=4,
    )
    # rotation parity vs a standalone apply_rope graph (ulp-level)
    pos = jnp.arange(s, dtype=jnp.int32)[None]
    kr = apply_rope(k, pos, THETA).astype(dtype)
    np.testing.assert_allclose(
        np.asarray(k_c, np.float32), np.asarray(kr, np.float32),
        rtol=1e-6, atol=1e-6,
    )
    np.testing.assert_array_equal(
        np.asarray(v_c, np.float32), np.asarray(v.astype(dtype), np.float32))
    # placement + cast parity, bitwise, on the same rotated rows:
    # fill_fresh (the static-slice path _fill_attn_cache now delegates
    # to) vs the historical one-hot append on a fresh cache
    fresh = kvc.init_cache(b, hot, cold, (g * d,), dtype)
    filled = kvc.fill_fresh(fresh, k_c, v_c)
    legacy = kvc.append(fresh, k_c, v_c)
    for name in ("hot_k", "hot_v", "cold_k", "cold_v", "lengths"):
        np.testing.assert_array_equal(
            np.asarray(getattr(filled, name), np.float32),
            np.asarray(getattr(legacy, name), np.float32), err_msg=name,
        )


def test_fill_swa_ring_realign_matches_legacy():
    """SWA s > window: fill_fresh's ring realign (the single home of the
    ring-layout invariant — _fill_attn_cache delegates here) against an
    independent oracle: ring slot j must hold the token with position
    p ≡ j (mod w) from the last w."""
    b, s, g, d, w = 2, 13, 2, 8, 8  # s > window: realign path
    _, k, v = _qkv(b, s, 4, g, d, d, seed=8)
    q = jax.random.normal(jax.random.PRNGKey(9), (b, s, 4, d))
    _, k_c, v_c = fp.flash_prefill_attention(
        q, k, v, None, impl="pallas", rope_theta=THETA, emit_kv=True,
        window=w, block_q=4, block_s=4,
    )
    fresh = kvc.init_cache(b, 0, w, (g * d,), jnp.float32)
    filled = kvc.fill_fresh(fresh, k_c, v_c, ring=True)
    np.testing.assert_array_equal(np.asarray(filled.lengths), [s, s])
    for j in range(w):
        p = max(pp for pp in range(s) if pp % w == j)  # last writer of slot j
        np.testing.assert_array_equal(
            np.asarray(filled.cold_k[:, j]),
            np.asarray(k_c[:, p]).reshape(b, g * d),
            err_msg=f"slot {j} != position {p}",
        )
        np.testing.assert_array_equal(
            np.asarray(filled.cold_v[:, j]),
            np.asarray(v_c[:, p]).reshape(b, g * d))
    # and ring append on a fresh cache (the decode write path) agrees
    legacy = kvc.append(fresh, k_c, v_c, ring=True)
    for name in ("cold_k", "cold_v", "lengths"):
        np.testing.assert_array_equal(
            np.asarray(getattr(filled, name)),
            np.asarray(getattr(legacy, name)), err_msg=name,
        )


def test_chunk_append_fp8_matches_xla_fill():
    """Continuation fill: kernel-emitted fp8 chunk rows scattered by
    kv_cache.append(valid=) == the XLA-rotated rows scattered the same
    way, bitwise."""
    b, c, g, d = 2, 6, 2, 8
    lens = [4, 9]
    cache = _build_cache(b, 4, 12, g, d, lens, dtype=jnp.float8_e4m3fn, seed=51)
    q, k, v = _qkv(b, c, 4, g, d, d, seed=52)
    val = jnp.asarray([6, 3], jnp.int32)
    got, want = _both(q, k, v, cache, valid=val, block_q=2, block_s=4)
    filled_p = kvc.append(cache, got[1], got[2], valid=val)
    filled_x = kvc.append(cache, want[1], want[2], valid=val)
    for a, bb in zip(jax.tree.leaves(filled_p), jax.tree.leaves(filled_x)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(bb, np.float32))


# ---------------------------------------------------------------------------
# MLA (rope_dims < head dim, attention-only form) + model-level wiring
# ---------------------------------------------------------------------------


def test_mla_rope_dims_parity():
    b, c, h, dk, dv, rd = 2, 9, 4, 24, 16, 8
    q, k, v = _qkv(b, c, h, h, dk, dv, seed=61)  # g = h, rep = 1
    cache = _build_cache(b, 2, 10, h, dk, [3, 7], dv=dv, seed=62)
    got, want = _both(q, k, v, cache, rope_dims=rd, emit_kv=False,
                      block_q=4, block_s=4)
    _assert_parity(got, want)


def _impl_cfg(cfg, impl):
    return dataclasses.replace(
        cfg, bitnet=dataclasses.replace(cfg.bitnet, impl=impl)
    )


@pytest.mark.parametrize("arch", [
    "falcon3-1b", "mixtral-8x22b", "deepseek-v3-671b", "llava-next-34b",
])
def test_model_prefill_impl_parity(arch):
    """transformer.prefill under impl='pallas' (flash scan path) vs
    impl='xla' (legacy collect-KV forward + bulk fill): last-token logits
    to tolerance, every cache leaf bit-identical."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as T

    cfg = get_smoke_config(arch)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    b, p_len = 2, 11
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(7), (b, p_len), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(8), (b, cfg.n_patches, cfg.frontend_dim))
    lg_x, cache_x = T.prefill(
        params, _impl_cfg(cfg, "xla"), batch, hot_cap=4, max_len=24, mode="qat")
    lg_p, cache_p = T.prefill(
        params, _impl_cfg(cfg, "pallas"), batch, hot_cap=4, max_len=24, mode="qat")
    np.testing.assert_allclose(
        np.asarray(lg_p), np.asarray(lg_x), rtol=2e-4, atol=2e-4)
    # cache parity is ulp-level across the two *pipelines* (the in-kernel
    # rope and apply_rope fuse differently under XLA); placement itself
    # is asserted bitwise in the fill oracle tests above
    for lx, lp in zip(jax.tree.leaves(cache_x), jax.tree.leaves(cache_p)):
        np.testing.assert_allclose(
            np.asarray(lx, np.float32), np.asarray(lp, np.float32),
            rtol=1e-6, atol=1e-6)


def test_attention_prefill_chunk_impl_parity():
    """attention_prefill_chunk: pallas and xla produce the same outputs
    (tolerance) and the same post-append cache (bitwise)."""
    from repro.configs import get_smoke_config
    from repro.models import attention as attn

    cfg = get_smoke_config("falcon3-1b")
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    b, c = 3, 4
    lens = [0, 5, 9]
    cache = _build_cache(b, 4, 12, g, hd, lens, seed=71)
    x = jax.random.normal(jax.random.PRNGKey(72), (b, c, cfg.d_model)) * 0.1
    n_valid = jnp.asarray([4, 2, 0], jnp.int32)
    outs = {}
    for impl in ("pallas", "xla"):
        y, c2 = attn.attention_prefill_chunk(
            p, x, _impl_cfg(cfg, impl), "qat", cache, n_valid, impl=impl)
        outs[impl] = (np.asarray(y), c2)
    for i, nv in enumerate([4, 2, 0]):
        np.testing.assert_allclose(
            outs["pallas"][0][i, :nv], outs["xla"][0][i, :nv], **TOL)
    for a, bb in zip(jax.tree.leaves(outs["pallas"][1]),
                     jax.tree.leaves(outs["xla"][1])):
        # ulp-level: kernel rope vs apply_rope under different XLA fusion
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# block table
# ---------------------------------------------------------------------------


def test_select_blocks_prefill_attn_kind():
    # GQA rep row: 128-token q blocks, 256-token S-blocks, capped at C
    assert ops.select_blocks(4, 128, 4096, "pack2", kind="prefill_attn") == (
        128, 128, 256)
    assert ops.select_blocks(4, 128, 48, "pack2", kind="prefill_attn") == (
        48, 128, 48)
    # MLA row (rep > 16 never happens, but wide-head rows halve)
    assert ops.select_blocks(64, 576, 4096, "pack2", kind="prefill_attn") == (
        64, 128, 128)
    # codec is ignored for this kind (no packed operand)
    assert ops.select_blocks(4, 128, 4096, "pack243", kind="prefill_attn") == (
        128, 128, 256)
