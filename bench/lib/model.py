"""A configuration file as run, and its weights made from the seed.

The configuration files under ``bench/configs/`` hold the published sizes
under the public ``config.json`` key names. ``model_config`` maps them onto
the program's ``ModelConfig``. The weights are random and come from the
seed alone: ternary trits with one scale per projection, bf16 norms,
embedding and lm_head. ``program_params`` makes the whole tree for the
program on the device in one jitted call, packed with the program's own
codec. ``layer_weights`` makes one layer's trits and scales again for the
plain reference, from the same seed and without the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# (block, name) -> (input size key, output size key) of a stacked layer's
# ternary projections, in the program's tree layout
PROJECTIONS = (
    ("attn", "wq", "d", "q"),
    ("attn", "wk", "d", "kv"),
    ("attn", "wv", "d", "kv"),
    ("attn", "wo", "q", "d"),
    ("mlp", "gate", "d", "f"),
    ("mlp", "up", "d", "f"),
    ("mlp", "down", "f", "d"),
)
# share of +1 and of -1 trits: what absmean rounding gives a Gaussian
# weight (P(|z| < 0.399) = 0.31 zeros), in 256ths of a random byte
TRIT_ONE_BELOW = 88
TRIT_MINUS_FROM = 168


def sizes(conf: dict) -> dict:
    """The widths the weights and costs need, from a configuration file."""
    d = conf["hidden_size"]
    h = conf["num_attention_heads"]
    g = conf["num_key_value_heads"]
    hd = conf.get("head_dim") or d // h
    return {
        "layers": conf["num_hidden_layers"], "d": d, "heads": h,
        "kv_heads": g, "head_dim": hd, "q": h * hd, "kv": g * hd,
        "f": conf["intermediate_size"], "vocab": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
        "act_bits": conf["quantization"]["activation_bits"],
        "codec": conf["quantization"]["codec"],
        "dtype": conf["torch_dtype"],
    }


# lower-precision paths of the program that the check's control switches on:
# int4 activations where the configuration states int8 (BitNet a4.8), and
# fp8 (e4m3) KV-cache tiers where it states bf16
CONTROLS = ("act_int4", "kv_fp8")


def model_config(conf: dict, control: str | None = None):
    """The program's ``ModelConfig`` for a configuration file.

    ``control`` names one of the program's own lower-precision paths
    (``CONTROLS``) to switch on: the check's control, which has to come
    out as not correct (see ``bench/lib/check.py``).
    """
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    from repro.configs.base import BitNetConfig, ModelConfig

    s = sizes(conf)
    if conf["model_type"] != "llama" or conf.get("tie_word_embeddings"):
        raise ValueError(f"{conf['name']}: only untied llama blocks are mapped")
    return ModelConfig(
        name=conf["name"], family="dense", n_layers=s["layers"],
        d_model=s["d"], n_heads=s["heads"], n_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["f"], vocab_size=s["vocab"],
        rope_theta=s["rope_theta"], norm_eps=s["eps"],
        bitnet=BitNetConfig(act_bits=4 if control == "act_int4"
                            else s["act_bits"], codec=s["codec"],
                            kv_fp8=control == "kv_fp8", lora_rank=0),
        source=conf["source"],
    )


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def layer_weights(key: jax.Array, layer, s: dict) -> dict:
    """One layer's weights: {(block, name): (trits int8 (K, N), scale f32)}
    and {(block, "ln"): bf16 (d,)}. Pure in (key, layer), so the program's
    stacked build and the reference's layer-by-layer build agree."""
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, (block, name, a, b) in enumerate(PROJECTIONS):
        k_in, n_out = s[a], s[b]
        tk, sk = jax.random.split(jax.random.fold_in(lk, i))
        r = jax.random.bits(tk, (k_in, n_out), jnp.uint8)
        trits = (jnp.where(r < TRIT_ONE_BELOW, 1, 0)
                 - jnp.where(r >= TRIT_MINUS_FROM, 1, 0)).astype(jnp.int8)
        # absmean of a N(0, 1/K) weight is 0.8/sqrt(K); each projection
        # gets its own scale, so a scale applied to the wrong segment shows
        scale = jax.random.uniform(sk, (), jnp.float32, 0.7, 0.9) / k_in ** 0.5
        out[(block, name)] = (trits, scale)
    for j, block in enumerate(("attn", "mlp")):
        nk = jax.random.fold_in(lk, 100 + j)
        out[(block, "ln")] = (
            1.0 + 0.1 * jax.random.normal(nk, (s["d"],), jnp.float32)
        ).astype(jnp.bfloat16)
    return out


def outer_weights(key: jax.Array, s: dict) -> dict:
    """Embedding (V, d), final norm (d,) and lm_head (d, V), in bf16."""
    ek, nk, hk = (jax.random.fold_in(key, 10_000 + i) for i in range(3))
    d, v = s["d"], s["vocab"]
    return {
        "embed": jax.random.normal(ek, (v, d), jnp.bfloat16),
        "final_ln": (1.0 + 0.1 * jax.random.normal(nk, (d,), jnp.float32)
                     ).astype(jnp.bfloat16),
        "lm_head": jax.random.normal(hk, (d, v), jnp.bfloat16)
        * jnp.bfloat16(d ** -0.5),
    }


@functools.partial(jax.jit, static_argnames=("skey",))
def _program_arrays(key, skey):
    from repro.core import packing

    s = dict(skey)
    pack = packing.pack2 if s["codec"] == "pack2" else packing.pack243

    def one(layer):
        return {k: v if k[1] == "ln" else (pack(v[0]), v[1])
                for k, v in layer_weights(key, layer, s).items()}

    stacked = jax.lax.map(one, jnp.arange(s["layers"]))
    return stacked, outer_weights(key, s)


def program_params(seed: int, conf: dict) -> dict:
    """The program's parameter tree, made on the device in one jitted call:
    each projection a ``PackedLinear`` stacked over layers (packed by the
    program's codec), norms, embedding and lm_head in bf16. ``Engine``
    fuses the projections as it is built."""
    from repro.core.bitlinear import PackedLinear

    s = sizes(conf)
    stacked, outer = _program_arrays(seed_key(seed), tuple(sorted(s.items())))
    blocks = {"attn": {}, "mlp": {}}
    for (block, name, a, _b) in PROJECTIONS:
        packed, scale = stacked[(block, name)]
        blocks[block][name] = PackedLinear(packed=packed, scale=scale,
                                           k=s[a], codec=s["codec"])
    for block in ("attn", "mlp"):
        blocks[block]["ln"] = stacked[(block, "ln")]
    return {
        "embed": {"w": outer["embed"]},
        "final_ln": outer["final_ln"],
        "lm_head": {"w": outer["lm_head"]},
        "blocks": blocks,
    }
