"""Flash-decode attention kernel parity (kernels/flash_decode.py).

The streaming Pallas kernel (interpret mode on CPU) against the masked
full-capacity XLA paths in core/kv_cache.py and an explicit fp32 prefix
oracle. Unlike the integer matmul kernels, the contract here is fp32
*reference parity to tight tolerance*, not bit equality — the streaming
merge visits blocks in a different order than the two-tier XLA merge.

Covers the ISSUE 4 parity matrix:
  * mixed-length batches, including length-0 (unadmitted) slots;
  * M = 1 through admission-group batch sizes (b in {1, 2, 5, 8});
  * per-slot block predication at exact S-block boundaries;
  * ring cold-tier layout after wrap-around (SWA, hot_cap = 0);
  * fp8(e4m3) tiers — per-block VMEM dequant vs an f32 oracle over the
    upcast cache (tight) and vs the bf16-computing XLA path (loose);
  * MLA latent path (values = latent prefix of the k-slot, empty v-slot);
  * zero-capacity tiers (SWA hot, max_len <= hot_cap cold) and
    non-dividing / tiny S-blocks;
  * the models/attention.py wiring (attention_decode / mla_decode run the
    same numbers under impl="pallas" and impl="xla");
  * the "decode_attn" row of ops.select_blocks.

Everything runs in Pallas interpret mode on CPU — part of the CI
kernel-parity lane (pytest -m kernel_parity).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as kvc
from repro.kernels import flash_decode as fd
from repro.kernels import ops

pytestmark = pytest.mark.kernel_parity

TOL = dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _build_cache(b, hot, cold, g, d, lens, dtype=jnp.float32, ring=False,
                 seed=0):
    """Cache with per-slot lengths built via active-masked decode appends
    (the continuous-batching write path), in the stored (g·d) layout.
    Returns (cache, ks, vs) with ks/vs the full (b, max_len, g, d) f32
    history."""
    cache = kvc.init_cache(b, hot, cold, (g * d,), dtype)
    t_max = max(max(lens), 1)
    ks = jax.random.normal(jax.random.PRNGKey(seed), (b, t_max, g, d))
    vs = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, t_max, g, d))
    app = kvc.append_decode_ring if ring else kvc.append_decode
    for t in range(max(lens)):
        active = jnp.asarray([t < L for L in lens])
        cache = app(cache, ks[:, t], vs[:, t], active=active)
    return cache, ks, vs


def _cache_prefix(cache, i):
    """Valid (ks, vs) of slot i as stored (tier dtype -> f32): hot prefix
    then cold prefix. Order is irrelevant to attention (permutation
    invariance), which is what makes this the ring oracle too."""
    L = int(cache.lengths[i])
    n_hot = min(L, cache.hot_cap)
    n_cold = min(max(L - cache.hot_cap, 0), cache.cold_cap)
    ks = jnp.concatenate(
        [cache.hot_k[i, :n_hot], cache.cold_k[i, :n_cold]], axis=0
    ).astype(jnp.float32)
    vs = jnp.concatenate(
        [cache.hot_v[i, :n_hot], cache.cold_v[i, :n_cold]], axis=0
    ).astype(jnp.float32)
    return ks, vs


def _oracle_slot(q_i, ks, vs, scale):
    """Plain f32 softmax attention for ONE slot. q_i: (h, d); ks/vs:
    (t, g·d) as stored. Returns (h, dv); zeros for an empty prefix."""
    h, d = q_i.shape
    t, g = ks.shape[0], ks.shape[1] // d
    ks = np.asarray(ks, np.float32).reshape(t, g, d)
    vs = np.asarray(vs, np.float32).reshape(t, g, vs.shape[1] // g)
    if t == 0:
        return np.zeros((h, vs.shape[-1]), np.float32)
    rep = h // g
    qg = np.asarray(q_i, np.float32).reshape(g, rep, d)
    logits = np.einsum("grd,tgd->grt", qg, np.asarray(ks, np.float32)) * scale
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.einsum("grt,tgv->grv", p, np.asarray(vs, np.float32))
    return out.reshape(h, vs.shape[-1])


def _oracle(q, cache, scale=None):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return np.stack([
        _oracle_slot(q[i], *_cache_prefix(cache, i), scale)
        for i in range(q.shape[0])
    ])


# ---------------------------------------------------------------------------
# GQA: mixed lengths, batch sizes, predication boundaries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,lens", [
    (1, [5]),
    (2, [0, 3]),          # length-0 unadmitted slot rides along
    (5, [0, 1, 4, 9, 16]),  # hot-only, boundary, cold, full
    (8, [2, 7, 11, 0, 16, 4, 13, 1]),
])
def test_gqa_mixed_lengths_match_oracle_and_xla(b, lens):
    cache, _, _ = _build_cache(b, 4, 12, 2, 8, lens, seed=b)
    q = jax.random.normal(jax.random.PRNGKey(40 + b), (b, 4, 8))
    got = fd.flash_decode_attention(q, cache, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)
    want = fd.flash_decode_attention(q, cache, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("length", [0, 1, 3, 4, 5, 7, 8, 9, 15, 16])
def test_gqa_every_predication_boundary(length):
    """Lengths at and around every hot/cold S-block edge (hot_cap=4 with
    block_s=4 -> one hot block; cold blocks of 4)."""
    cache, _, _ = _build_cache(1, 4, 12, 1, 8, [length], seed=length)
    q = jax.random.normal(jax.random.PRNGKey(60 + length), (1, 2, 8))
    got = fd.flash_decode_attention(q, cache, impl="pallas", block_s=4)
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)


@pytest.mark.parametrize("block_s", [1, 3, 5, 256])
def test_gqa_non_dividing_blocks(block_s):
    """S-blocks that don't divide the tier capacities (partial last block
    padding is masked before the PV matmul)."""
    cache, _, _ = _build_cache(3, 4, 13, 2, 8, [2, 9, 17], seed=9)
    q = jax.random.normal(jax.random.PRNGKey(77), (3, 4, 8))
    got = fd.flash_decode_attention(q, cache, impl="pallas", block_s=block_s)
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)


def test_gqa_mqa_rep_folding():
    """MQA (g=1, rep=h) and rep=1 (h=g) both fold into the q block."""
    for g, h in ((1, 6), (4, 4)):
        cache, _, _ = _build_cache(2, 4, 12, g, 8, [3, 11], seed=g * 10 + h)
        q = jax.random.normal(jax.random.PRNGKey(g + h), (2, h, 8))
        got = fd.flash_decode_attention(q, cache, impl="pallas")
        np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)


def test_gqa_bf16_q_keeps_out_dtype():
    cache, _, _ = _build_cache(2, 4, 12, 2, 8, [5, 9], dtype=jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 8), jnp.bfloat16)
    got = fd.flash_decode_attention(q, cache, impl="pallas")
    assert got.dtype == jnp.bfloat16
    want = fd.flash_decode_attention(q, cache, impl="xla")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,  # bf16 output rounding
    )


def test_gqa_zero_cold_cap():
    """max_len <= hot_cap: the cold tier is a zero-capacity dummy."""
    cache, _, _ = _build_cache(2, 8, 0, 2, 8, [3, 8], seed=21)
    q = jax.random.normal(jax.random.PRNGKey(22), (2, 4, 8))
    got = fd.flash_decode_attention(q, cache, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)


# ---------------------------------------------------------------------------
# ring / SWA layout
# ---------------------------------------------------------------------------


def test_ring_after_wrap_matches_oracle():
    """hot_cap=0 ring tier: a wrapped slot attends to the whole window
    (validity clamps at cold_cap), an unwrapped one to its prefix; ring
    storage order doesn't matter (softmax permutation invariance)."""
    cache, _, _ = _build_cache(2, 0, 4, 1, 8, [7, 3], ring=True, seed=31)
    np.testing.assert_array_equal(np.asarray(cache.lengths), [7, 3])
    q = jax.random.normal(jax.random.PRNGKey(32), (2, 2, 8))
    got = fd.flash_decode_attention_ring(q, cache, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)
    want = fd.flash_decode_attention_ring(q, cache, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_ring_exactly_full_window():
    cache, _, _ = _build_cache(1, 0, 6, 2, 8, [6], ring=True, seed=33)
    q = jax.random.normal(jax.random.PRNGKey(34), (1, 4, 8))
    got = fd.flash_decode_attention_ring(q, cache, impl="pallas", block_s=4)
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)


# ---------------------------------------------------------------------------
# fp8 tiers
# ---------------------------------------------------------------------------


def test_fp8_tiers_match_f32_oracle_tight():
    """The kernel upcasts fp8 blocks to f32 in VMEM, so against an f32
    oracle over the (fp8-rounded) cache contents parity is tight; the
    XLA path computes fp8 logits in bf16, so that comparison is loose."""
    cache, _, _ = _build_cache(
        3, 4, 12, 2, 8, [2, 6, 14], dtype=jnp.float8_e4m3fn, seed=41
    )
    q = jax.random.normal(jax.random.PRNGKey(42), (3, 4, 8))
    got = fd.flash_decode_attention(q, cache, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), _oracle(q, cache), **TOL)
    want = fd.flash_decode_attention(q, cache, impl="xla")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2
    )


# ---------------------------------------------------------------------------
# MLA latent path
# ---------------------------------------------------------------------------


def _build_latent_cache(b, hot, cold, dd, lens, seed=0):
    cache = kvc.init_cache(b, hot, cold, (dd,), jnp.float32)
    cache = cache._replace(
        hot_v=jnp.zeros((b, hot, 0)), cold_v=jnp.zeros((b, cold, 0))
    )
    for t in range(max(max(lens), 0)):
        active = jnp.asarray([t < L for L in lens])
        lat = jax.random.normal(jax.random.PRNGKey(seed + t), (b, dd))
        cache = kvc.append_decode(cache, lat, jnp.zeros((b, 0)), active=active)
    return cache


def _latent_oracle(q, cache, value_dim, scale):
    out = []
    for i in range(q.shape[0]):
        ks, _ = _cache_prefix(cache, i)
        t = ks.shape[0]
        if t == 0:
            out.append(np.zeros((q.shape[1], value_dim), np.float32))
            continue
        logits = np.einsum(
            "hd,td->ht", np.asarray(q[i], np.float32), np.asarray(ks)
        ) * scale
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(p @ np.asarray(ks)[:, :value_dim])
    return np.stack(out)


@pytest.mark.parametrize("lens", [[1, 6, 13], [0, 4, 16]])
def test_latent_mixed_lengths(lens):
    b, dd, vdim, scale = 3, 24, 16, 0.17
    cache = _build_latent_cache(b, 4, 12, dd, lens, seed=50)
    q = jax.random.normal(jax.random.PRNGKey(51), (b, 5, dd))
    got = fd.flash_decode_attention_latent(
        q, cache, vdim, scale, impl="pallas"
    )
    np.testing.assert_allclose(
        np.asarray(got), _latent_oracle(q, cache, vdim, scale), **TOL
    )
    want = fd.flash_decode_attention_latent(q, cache, vdim, scale, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_latent_small_blocks():
    cache = _build_latent_cache(2, 3, 9, 24, [2, 11], seed=60)
    q = jax.random.normal(jax.random.PRNGKey(61), (2, 4, 24))
    got = fd.flash_decode_attention_latent(
        q, cache, 16, 0.2, impl="pallas", block_s=2
    )
    np.testing.assert_allclose(
        np.asarray(got), _latent_oracle(q, cache, 16, 0.2), **TOL
    )


# ---------------------------------------------------------------------------
# models/attention wiring: pallas and xla impls agree end to end
# ---------------------------------------------------------------------------


LAYER = jnp.int32(0)


def _stack(cache):
    """A one-layer stack: the decode-step form attention_decode and
    mla_decode take (a leading layer axis, read at ``LAYER``)."""
    return jax.tree.map(lambda a: a[None], cache)


def _impl_cfg(cfg, impl):
    return dataclasses.replace(
        cfg, bitnet=dataclasses.replace(cfg.bitnet, impl=impl)
    )


def test_attention_decode_impl_parity():
    from repro.configs import get_smoke_config
    from repro.models import attention as attn

    cfg = get_smoke_config("falcon3-1b")
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    b = 3
    cache = _stack(kvc.init_cache(b, 4, 12, (g * hd,), jnp.float32))
    x_hist = jax.random.normal(jax.random.PRNGKey(1), (8, b, cfg.d_model)) * 0.1
    lens = [2, 0, 7]
    outs = {}
    for impl in ("pallas", "xla"):
        c = cache
        for t in range(7):
            active = jnp.asarray([t < L for L in lens])
            _, c = attn.attention_decode(
                p, x_hist[t], _impl_cfg(cfg, impl), "qat", c, LAYER,
                active=active
            )
        y, c = attn.attention_decode(
            p, x_hist[7], _impl_cfg(cfg, impl), "qat", c, LAYER
        )
        outs[impl] = (np.asarray(y), np.asarray(c.lengths))
    np.testing.assert_array_equal(outs["pallas"][1], outs["xla"][1])
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0], **TOL)


def test_mla_decode_impl_parity():
    from repro.configs import get_smoke_config
    from repro.models import attention as attn

    cfg = get_smoke_config("deepseek-v3-671b")
    p = attn.init_mla(jax.random.PRNGKey(0), cfg)
    b, dd = 2, cfg.mla.kv_cache_dim
    cache = kvc.init_cache(b, 2, 6, (dd,), jnp.float32)
    cache = _stack(cache._replace(
        hot_v=jnp.zeros((b, 2, 0)), cold_v=jnp.zeros((b, 6, 0))
    ))
    x_hist = jax.random.normal(jax.random.PRNGKey(1), (5, b, cfg.d_model)) * 0.1
    outs = {}
    for impl in ("pallas", "xla"):
        c = cache
        for t in range(4):
            active = jnp.asarray([True, t < 2])
            _, c = attn.mla_decode(
                p, x_hist[t], _impl_cfg(cfg, impl), "qat", c, LAYER,
                active=active
            )
        y, c = attn.mla_decode(p, x_hist[4], _impl_cfg(cfg, impl), "qat", c,
                               LAYER)
        outs[impl] = (np.asarray(y), np.asarray(c.lengths))
    np.testing.assert_array_equal(outs["pallas"][1], outs["xla"][1])
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused-RoPE decode form: unrotated q/k_new in, pending token in-stream,
# rotated k out; cache is the PRE-append state (ISSUE 5 decode satellite)
# ---------------------------------------------------------------------------


def _fused_parity(b, hot, cold, g, h, d, lens, ring, active=None,
                  block_s=None, seed=0):
    cache, _, _ = _build_cache(b, hot, cold, g, d, lens, ring=ring, seed=seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 999), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    kn = jax.random.normal(ks[1], (b, g, d))
    vn = jax.random.normal(ks[2], (b, g, d))
    act = None if active is None else jnp.asarray(active)
    entry = fd.flash_decode_attention_ring if ring else fd.flash_decode_attention
    op, kp = entry(q, cache, impl="pallas", k_new=kn, v_new=vn, active=act,
                   rope_theta=1e4, block_s=block_s)
    ox, kx = entry(q, cache, impl="xla", k_new=kn, v_new=vn, active=act,
                   rope_theta=1e4, block_s=block_s)
    # rotated-k parity is ulp-level (kernel rope vs apply_rope fuse
    # differently under XLA); attention parity at the usual fp32 TOL
    np.testing.assert_allclose(np.asarray(kp), np.asarray(kx),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(op), np.asarray(ox), **TOL)


@pytest.mark.parametrize("lens", [[0, 5, 14], [3, 4, 15], [1, 8, 12]])
def test_fused_rope_linear_mixed_lengths(lens):
    # lens stay < capacity: the pre-append form appends one token, and a
    # full cache is out of contract (the engine's max_len bound)
    _fused_parity(3, 4, 12, 2, 4, 8, lens, ring=False, seed=sum(lens))


def test_fused_rope_active_mask_gates_pending_token():
    """Inactive slots attend only their old prefix — the pending (k, v)
    joins the stream for active slots alone."""
    _fused_parity(3, 4, 12, 2, 4, 8, [2, 7, 11], ring=False,
                  active=[True, False, True])


@pytest.mark.parametrize("lens", [[9, 3], [6, 12], [5, 6]])
def test_fused_rope_ring_masks_evictee(lens):
    """Wrapped ring: the slot the upcoming append will overwrite holds
    position len - w — outside the decode token's window — and must be
    masked; unwrapped slots keep their whole prefix."""
    _fused_parity(2, 0, 6, 2, 4, 8, lens, ring=True, block_s=2,
                  seed=sum(lens))


def test_fused_rope_ring_inactive_slot_keeps_evictee():
    """An inactive slot appends nothing, so nothing is evicted: its old
    wrapped window stays fully valid (matching the XLA reference)."""
    _fused_parity(2, 0, 6, 2, 4, 8, [12, 8], ring=True,
                  active=[False, True], block_s=2)


def test_attention_decode_fused_vs_xla_path():
    """models/attention.attention_decode: the Pallas fused-RoPE path and
    the legacy rotate->append->read XLA path produce the same outputs and
    (to rope ulp) the same caches over a multi-step mixed-length run."""
    from repro.configs import get_smoke_config
    from repro.models import attention as attn

    cfg = get_smoke_config("falcon3-1b")
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    g, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    b = 3
    cache = _stack(kvc.init_cache(b, 4, 12, (g * hd,), jnp.float32))
    x_hist = jax.random.normal(jax.random.PRNGKey(1), (8, b, cfg.d_model)) * 0.1
    lens = [2, 0, 7]
    outs = {}
    for impl in ("pallas", "xla"):
        c = cache
        for t in range(7):
            active = jnp.asarray([t < L for L in lens])
            _, c = attn.attention_decode(
                p, x_hist[t], _impl_cfg(cfg, impl), "qat", c, LAYER,
                active=active
            )
        y, c = attn.attention_decode(p, x_hist[7], _impl_cfg(cfg, impl), "qat",
                                     c, LAYER)
        outs[impl] = (np.asarray(y), c)
    np.testing.assert_array_equal(
        np.asarray(outs["pallas"][1].lengths), np.asarray(outs["xla"][1].lengths))
    np.testing.assert_allclose(outs["pallas"][0], outs["xla"][0],
                               rtol=5e-5, atol=5e-5)
    for a, bb in zip(jax.tree.leaves(outs["pallas"][1]),
                     jax.tree.leaves(outs["xla"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# block table
# ---------------------------------------------------------------------------


def test_select_blocks_decode_attn_kind():
    # GQA rep row: S-block 256, capped at capacity
    assert ops.select_blocks(4, 128, 544, "pack2", kind="decode_attn") == (
        16, 128, 256)
    assert ops.select_blocks(1, 64, 96, "pack2", kind="decode_attn") == (
        16, 128, 96)
    # MLA row (many q heads): narrower S-block; lane cap at round_up(n, 128)
    assert ops.select_blocks(64, 576, 4096, "pack2", kind="decode_attn") == (
        128, 128, 128)
    # codec is ignored for this kind (no packed operand)
    assert ops.select_blocks(4, 128, 544, "pack243", kind="decode_attn") == (
        16, 128, 256)
