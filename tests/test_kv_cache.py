"""Two-tier DR KV cache: routing, per-slot lengths, tiered attention vs
single-buffer oracle, ring wrap-around, vectorized traffic ledger."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache


def _mk(batch=2, hot=4, cold=12, heads=2, dim=8, dtype=jnp.float32):
    """A cache in the stored layout: (heads·dim,) per token."""
    return kv_cache.init_cache(batch, hot, cold, (heads * dim,), dtype)


def test_append_routes_early_tokens_hot():
    cache = _mk()
    b, h, d = 2, 2, 8
    for t in range(6):
        k = jnp.full((b, h, d), float(t + 1))
        cache = kv_cache.append_decode(cache, k, k * 10)
    np.testing.assert_array_equal(np.asarray(cache.lengths), [6, 6])
    # tokens 0..3 in hot, 4..5 in cold
    np.testing.assert_allclose(np.asarray(cache.hot_k[0, :, 0]), [1, 2, 3, 4])
    np.testing.assert_allclose(np.asarray(cache.cold_k[0, :2, 0]), [5, 6])
    np.testing.assert_allclose(np.asarray(cache.cold_v[0, :2, 0]), [50, 60])


def test_bulk_append_matches_decode_appends():
    cache_a = _mk()
    cache_b = _mk()
    ks = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 2, 8))
    vs = jax.random.normal(jax.random.PRNGKey(1), (2, 7, 2, 8))
    cache_a = kv_cache.append(cache_a, ks, vs)
    for t in range(7):
        cache_b = kv_cache.append_decode(cache_b, ks[:, t], vs[:, t])
    for fa, fb in zip(cache_a, cache_b):
        np.testing.assert_allclose(np.asarray(fa), np.asarray(fb), rtol=1e-6)


def _oracle_attention(q, ks, vs):
    """Plain single-buffer attention oracle. q: (b,h,d); ks/vs: (b,t,g,d)."""
    b, t, g, d = ks.shape
    h = q.shape[1]
    rep = h // g
    qg = q.reshape(b, g, rep, d)
    logits = jnp.einsum("bgrd,bsgd->bgrs", qg, ks) * (d**-0.5)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p, vs)
    return out.reshape(b, h, d)


@pytest.mark.parametrize("n_tokens", [1, 3, 4, 5, 11, 16])
def test_tiered_attention_matches_oracle(n_tokens):
    """Streaming-softmax merge over (hot, cold) == softmax over the concat."""
    cache = _mk()
    ks = jax.random.normal(jax.random.PRNGKey(2), (2, n_tokens, 2, 8))
    vs = jax.random.normal(jax.random.PRNGKey(3), (2, n_tokens, 2, 8))
    cache = kv_cache.append(cache, ks, vs)
    q = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 8))  # 4 q heads, 2 kv (GQA rep=2)
    got = kv_cache.tiered_decode_attention(q, cache)
    want = _oracle_attention(q, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_tiered_attention_hot_only():
    cache = _mk()
    ks = jax.random.normal(jax.random.PRNGKey(5), (2, 2, 2, 8))
    vs = jax.random.normal(jax.random.PRNGKey(6), (2, 2, 2, 8))
    cache = kv_cache.append(cache, ks, vs)
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 2, 8))
    got = kv_cache.tiered_decode_attention(q, cache)
    want = _oracle_attention(q, ks, vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_append_is_jittable_and_scan_safe():
    cache = _mk()

    def step(c, kv):
        k, v = kv
        return kv_cache.append_decode(c, k, v), None

    ks = jax.random.normal(jax.random.PRNGKey(8), (10, 2, 2, 8))
    vs = jax.random.normal(jax.random.PRNGKey(9), (10, 2, 2, 8))
    final, _ = jax.lax.scan(step, cache, (ks, vs))
    np.testing.assert_array_equal(np.asarray(final.lengths), [10, 10])


# ---------------------------------------------------------------------------
# per-slot (continuous batching) behaviour
# ---------------------------------------------------------------------------


def test_mixed_length_slots_attention_matches_oracle():
    """Slots at different lengths each attend to exactly their own prefix."""
    b, hot, cold = 3, 4, 12
    cache = kv_cache.init_cache(b, hot, cold, (2 * 8,), jnp.float32)
    lens = [2, 9, 14]
    ks = jax.random.normal(jax.random.PRNGKey(10), (b, 16, 2, 8))
    vs = jax.random.normal(jax.random.PRNGKey(11), (b, 16, 2, 8))
    # build per-slot lengths via active-masked decode appends
    for t in range(16):
        active = jnp.asarray([t < L for L in lens])
        cache = kv_cache.append_decode(cache, ks[:, t], vs[:, t], active=active)
    np.testing.assert_array_equal(np.asarray(cache.lengths), lens)
    q = jax.random.normal(jax.random.PRNGKey(12), (b, 4, 8))
    got = kv_cache.tiered_decode_attention(q, cache)
    for i, L in enumerate(lens):
        want = _oracle_attention(q[i : i + 1], ks[i : i + 1, :L], vs[i : i + 1, :L])
        np.testing.assert_allclose(
            np.asarray(got[i : i + 1]), np.asarray(want), rtol=2e-5, atol=2e-5
        )


def test_inactive_slots_do_not_write():
    cache = _mk()
    k1 = jnp.ones((2, 2, 8))
    cache = kv_cache.append_decode(cache, k1, k1, active=jnp.asarray([True, False]))
    np.testing.assert_array_equal(np.asarray(cache.lengths), [1, 0])
    assert float(jnp.abs(cache.hot_k[1]).sum()) == 0.0
    np.testing.assert_allclose(np.asarray(cache.hot_k[0, 0]),
                               np.asarray(k1[0]).reshape(-1))


def test_per_slot_bulk_append_from_unequal_starts():
    """append() continues from each slot's own length."""
    cache = _mk(batch=2, hot=2, cold=10)
    # advance slot 0 by 3 tokens, slot 1 stays empty
    for t in range(3):
        k = jnp.full((2, 2, 8), float(t + 1))
        cache = kv_cache.append_decode(cache, k, k, active=jnp.asarray([True, False]))
    ks = jnp.stack([jnp.full((2, 2, 8), 7.0), jnp.full((2, 2, 8), 9.0)])  # (b,2,g,d)
    cache = kv_cache.append(cache, ks, ks)
    np.testing.assert_array_equal(np.asarray(cache.lengths), [5, 2])
    # slot 0: positions 3,4 -> cold slots 1,2 (hot_cap=2)
    np.testing.assert_allclose(np.asarray(cache.cold_k[0, 1:3, 0]), [7, 7])
    # slot 1: positions 0,1 -> hot slots 0,1
    np.testing.assert_allclose(np.asarray(cache.hot_k[1, :2, 0]), [9, 9])


def test_ring_cold_tier_wraparound_per_slot():
    """append_decode_ring keeps exactly the last cold_cap tokens per slot,
    at slot (p - hot_cap) % cold_cap, including after wrap-around — and
    slots can wrap independently."""
    b, hot, cold = 2, 0, 4
    cache = kv_cache.init_cache(b, hot, cold, (1 * 4,), jnp.float32)
    lens = [7, 3]  # slot 0 wraps (7 > 4), slot 1 does not
    for t in range(7):
        k = jnp.stack([jnp.full((1, 4), float(10 + t)), jnp.full((1, 4), float(20 + t))])
        active = jnp.asarray([t < lens[0], t < lens[1]])
        cache = kv_cache.append_decode_ring(cache, k, k, active=active)
    np.testing.assert_array_equal(np.asarray(cache.lengths), lens)
    # slot 0 holds tokens 3..6 at ring positions p % 4
    want0 = [0.0] * 4
    for p in range(3, 7):
        want0[p % 4] = 10.0 + p
    np.testing.assert_allclose(np.asarray(cache.cold_k[0, :, 0]), want0)
    # slot 1 holds tokens 0..2 in order, last ring slot untouched
    np.testing.assert_allclose(np.asarray(cache.cold_k[1, :, 0]), [20, 21, 22, 0])
    # validity clamps at cold_cap: all 4 positions valid for the wrapped
    # slot, 3 for the unwrapped one
    q = jax.random.normal(jax.random.PRNGKey(13), (b, 1, 4))
    got = kv_cache.tiered_decode_attention(q, cache)
    # ring content (order irrelevant to attention), as (b, t, g, d)
    ks0 = cache.cold_k[0:1].reshape(1, 4, 1, 4)
    want = _oracle_attention(q[0:1], ks0, ks0)
    np.testing.assert_allclose(np.asarray(got[0:1]), np.asarray(want), rtol=2e-5, atol=2e-5)
    ks1 = cache.cold_k[1:2, :3].reshape(1, 3, 1, 4)
    want1 = _oracle_attention(q[1:2], ks1, ks1)
    np.testing.assert_allclose(np.asarray(got[1:2]), np.asarray(want1), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["contiguous", "ring", "paged"])
def test_stacked_decode_append_writes_only_its_layer(kind):
    """append_decode(..., layer=l) on a layer-stacked cache (the decode
    step's form) changes layer l exactly as the unstacked append changes
    that layer alone, and leaves every other layer's rows and lengths as
    they were."""
    n_layers, b, g, d = 3, 3, 2, 8
    ring = kind == "ring"
    hot, cold = (0, 6) if ring else (4, 12)
    if kind == "paged":
        one = kv_cache.init_paged_cache(b, hot, cold, (g * d,), jnp.float32,
                                        page_size=4)
    else:
        one = kv_cache.init_cache(b, hot, cold, (g * d,), jnp.float32)
    app = kv_cache.append_decode_ring if ring else kv_cache.append_decode
    key = jax.random.PRNGKey(20)
    stack = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (n_layers,) + a.shape), one)
    stack = stack._replace(
        hot_k=jax.random.normal(key, stack.hot_k.shape),
        lengths=jnp.broadcast_to(jnp.asarray([3, 7, 11], jnp.int32),
                                 stack.lengths.shape))
    active = jnp.asarray([True, False, True])
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, g, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, g, d))
    layer = 1
    got = jax.jit(lambda s, kk, vv, l: app(s, kk, vv, active=active,
                                          layer=l))(stack, k, v,
                                                    jnp.int32(layer))
    want = app(kv_cache.layer_view(stack, layer), k, v, active=active)
    for name in stack._fields:
        a, w0 = getattr(got, name), getattr(stack, name)
        np.testing.assert_array_equal(np.asarray(a[layer]),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(a[other]),
                                          np.asarray(w0[other]),
                                          err_msg=name)


# ---------------------------------------------------------------------------
# traffic ledger
# ---------------------------------------------------------------------------


def test_step_traffic_accounting():
    tb = 100  # bytes per token per step
    tr = kv_cache.step_traffic_bytes(length=40, hot_cap=32, token_bytes=tb)
    assert tr["ondie_read"] == 32 * tb
    assert tr["ext_read"] == 8 * tb
    assert tr["ext_write"] == tb  # position 40 >= hot_cap -> external write
    tr2 = kv_cache.step_traffic_bytes(length=10, hot_cap=32, token_bytes=tb)
    assert tr2["ext_read"] == 0 and tr2["ext_write"] == 0


def test_step_traffic_tokens_matches_scalar_form():
    """Vectorized per-slot ledger == scalar ledger at every length."""
    hot = 8
    lengths = jnp.asarray([0, 1, 7, 8, 9, 40], jnp.int32)
    vec = kv_cache.step_traffic_tokens(lengths, hot)
    for i, L in enumerate(np.asarray(lengths)):
        scal = kv_cache.step_traffic_bytes(int(L), hot, token_bytes=1)
        for k in kv_cache.TRAFFIC_KEYS:
            assert int(vec[k][i]) == scal[k], (k, int(L))


@pytest.mark.parametrize("p_len", [0, 1, 3, 8, 9, 17])
def test_prompt_traffic_closed_form_matches_step_sum(p_len):
    hot = 8
    want = {k: 0 for k in kv_cache.TRAFFIC_KEYS}
    for i in range(p_len):
        tr = kv_cache.step_traffic_bytes(i, hot, token_bytes=1)
        for k in want:
            want[k] += tr[k]
    got = kv_cache.prompt_traffic_tokens(p_len, hot)
    assert got == want
