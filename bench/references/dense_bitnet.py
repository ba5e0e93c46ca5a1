"""Plain reference of a dense BitNet b1.58 decoder (Llama blocks).

Written from the layer equations, in float32, with no kernel, cache or
batching of the program, and nothing imported from it. The weights come
again from the seed, layer by layer, from the module that makes the
program's: ``bench/models/llama_bitnet.py`` (``layer_weights`` and
``outer_weights``).

Per layer, for hidden states x (S, d):

    h = rmsnorm(x) * ln_attn
    q, k, v = Q(h) @ Wq, Q(h) @ Wk, Q(h) @ Wv       (ternary, per-projection scale)
    q, k = rope(q), rope(k)                          (rotate-half, theta, absolute positions)
    o = softmax(q k^T / sqrt(hd), causal) v          (GQA: query head j reads kv head j // (H/G))
    x = x + Q(o) @ Wo
    h = rmsnorm(x) * ln_mlp
    x = x + Q(silu(Q(h) @ Wgate) * (Q(h) @ Wup)) @ Wdown

where ``Q(a) @ W`` quantises each row of ``a`` to int8 by its absmax
(BitNet b1.58 A8: ``xq = clip(round(a * 127 / max|a|), -128, 127)``) and
returns ``(xq @ trits) * scale_W / (127 / max|a|)``. The logits are
``rmsnorm(x) * ln_final @ lm_head``.

The integer products ``xq @ trits`` are exact in int32 (|xq| <= 128,
K <= 2^14), so they are computed as an int8 matmul with int32
accumulation: the same numbers a float32 matmul would give. Everything
else runs in float32 at the ``highest`` matmul precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.model import seed_key
from bench.models import llama_bitnet as M

Q_BLOCK = 512  # query rows per attention block
ROW_BLOCK = 1024  # rows per MLP block


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _qmatmul(a, trits, scale, bits):
    qmax, qmin = 2.0 ** (bits - 1) - 1, -(2.0 ** (bits - 1))
    s = qmax / jnp.maximum(jnp.max(jnp.abs(a), -1, keepdims=True), 1e-5)
    aq = jnp.clip(jnp.round(a * s), qmin, qmax).astype(jnp.int8)
    acc = jnp.dot(aq, trits, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (scale / s)


def _rope(x, pos, theta):
    """x (S, H, D), pos (S,): rotate the two halves of each head."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, n_kv):
    """Causal GQA attention; q (S, H, D), k/v (S, G, D) -> (S, H*D)."""
    s, h, d = q.shape
    rep = h // n_kv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        logits = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(d))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        logits = jnp.where(qpos[None, :, None] >= kpos[None, None, :],
                           logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))
    return out.reshape(s, h * d)


@functools.partial(jax.jit, static_argnames=("skey",))
def _layer(x, w, skey):
    s = dict(skey)
    eps, bits, g, hd = s["eps"], s["act_bits"], s["kv_heads"], s["head_dim"]
    n = x.shape[0]
    pos = jnp.arange(n)

    def mm(a, name):
        trits, scale = w[name]
        return _qmatmul(a, trits, scale, bits)

    h = _rmsnorm(x, w[("attn", "ln")].astype(jnp.float32), eps)
    q = _rope(mm(h, ("attn", "wq")).reshape(n, -1, hd), pos, s["rope_theta"])
    k = _rope(mm(h, ("attn", "wk")).reshape(n, g, hd), pos, s["rope_theta"])
    v = mm(h, ("attn", "wv")).reshape(n, g, hd)
    x = x + mm(_attention(q, k, v, g), ("attn", "wo"))

    def mlp(xb):
        hb = _rmsnorm(xb, w[("mlp", "ln")].astype(jnp.float32), eps)
        a = jax.nn.silu(mm(hb, ("mlp", "gate"))) * mm(hb, ("mlp", "up"))
        return xb + mm(a, ("mlp", "down"))

    xb = x.reshape(n // ROW_BLOCK, ROW_BLOCK, -1)
    return jax.lax.map(mlp, xb).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("skey",))
def _weights(key, layer, skey):
    return M.layer_weights(key, layer, dict(skey))


@functools.partial(jax.jit, static_argnames=("skey",))
def _outer(key, skey):
    return M.outer_weights(key, dict(skey))


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x_rows, final_ln, head, eps):
    h = _rmsnorm(x_rows, final_ln.astype(jnp.float32), eps)
    return jnp.dot(h, head.astype(jnp.float32))


def logits(conf: dict, seed: int, sequences, length: int):
    """Reference logits of each sequence's scored positions.

    ``sequences`` is a list of ``(tokens, rows)``: the int tokens of one
    sequence and the positions whose next-token logits are wanted.
    Every sequence is padded to ``length`` tokens, the longest one's,
    rounded up to a multiple of the block sizes, so one compiled layer
    serves them all; causal masking keeps the padding out of the scored
    rows. Returns one float32 array
    (len(rows), vocab) per sequence, on the host.
    """
    s = M.sizes(conf)
    skey = tuple(sorted(s.items()))
    key = seed_key(seed)
    pad = -(-length // ROW_BLOCK) * ROW_BLOCK
    with jax.default_matmul_precision("highest"):
        outer = _outer(key, skey)
        xs = []
        for tokens, _ in sequences:
            t = np.zeros((pad,), np.int32)
            t[: len(tokens)] = tokens
            xs.append(jnp.take(outer["embed"], jnp.asarray(t), axis=0
                               ).astype(jnp.float32))
        for layer in range(s["layers"]):
            w = _weights(key, layer, skey)
            xs = [_layer(x, w, skey) for x in xs]
            del w
        out = []
        for x, (_, rows) in zip(xs, sequences):
            rows = jnp.asarray(np.asarray(rows, np.int32))
            out.append(np.asarray(_logits(
                jnp.take(x, rows, axis=0), outer["final_ln"],
                outer["lm_head"], s["eps"])))
    return out
