"""Dense BitNet b1.58 decoders of Llama blocks: GQA attention and a
SwiGLU MLP, every projection ternary.

The configuration files hold the published sizes under the public
``config.json`` key names; ``model_config`` maps them onto the program's
``ModelConfig``. The weights are random and come from the seed alone:
ternary trits with one scale per projection, bf16 norms, embedding and
lm_head. ``program_params`` makes the whole tree for the program on the
device in one jitted call, packed with the program's own codec.
``layer_weights`` and ``outer_weights`` make them again for the plain
reference (``bench/references/dense_bitnet.py``), from the same seed and
without the program. ``kernel_least`` and ``math_least`` price a traced
run's kernels and model math with ``bench/lib/costs.py``.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp

from bench.lib import costs
from bench.lib.model import CONTROLS, draw_trits, seed_key
from bench.lib.readers import PROGRAMS

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
LEAF_REGIONS = ("qkv_proj", "o_proj", "mlp")
KERNELS = {
    "ternary_matmul": ("ternary_matmul_actq_pallas",),
    "flash_decode": ("flash_decode_attention",),
    "flash_prefill": ("flash_prefill_attention",),
}


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


def layer_weights(key: jax.Array, layer, s: dict) -> dict:
    """One layer's weights: {(block, name): (trits int8 (K, N), scale f32)}
    and {(block, "ln"): bf16 (d,)}. Pure in (key, layer), so the program's
    stacked build and the reference's layer-by-layer build agree."""
    lk = jax.random.fold_in(key, layer)
    out = {}
    for i, (block, name, a, b) in enumerate(PROJECTIONS):
        k_in, n_out = s[a], s[b]
        tk, sk = jax.random.split(jax.random.fold_in(lk, i))
        trits = draw_trits(tk, (k_in, n_out))
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


def projections(params) -> List[costs.Projection]:
    """Every packed projection leaf of the served tree (fused or not)."""
    out = []
    for block_name, block in sorted(params["blocks"].items()):
        for name, leaf in sorted(block.items()):
            packed = getattr(leaf, "packed", None)
            if packed is None:
                continue
            layers = packed.shape[0]
            scale = leaf.scale
            per_layer = (packed.size * packed.dtype.itemsize
                         + scale.size * scale.dtype.itemsize) // layers
            out.append(costs.Projection(f"{block_name}.{name}", layers,
                                        int(leaf.k), int(packed.shape[-1]),
                                        int(per_layer)))
    return out


def kernel_least(run, kernel: str, program: str) -> float:
    """Least seconds of a kernel's calls in the traced ``program``: every
    projection of every layer in each execution, or attention over each
    traced call's valid prefixes in every layer."""
    pk, sz = run.peaks, run.sizes
    layers = sz["layers"]
    least = 0.0
    if kernel == "ternary_matmul":
        execs, _ = run.trace.program_time(PROGRAMS[program])
        m = run.slots if program == "decode" else run.slots * run.chunk
        per_exec = sum(p.layers * costs.least(*costs.ternary_call(m, p),
                                              pk["int8_ops_per_s"],
                                              pk["hbm_bytes_per_s"])
                       for p in projections(run.served))
        least = execs * per_exec
    elif kernel == "flash_decode":
        for lengths in run.calls["decode"]:
            least += layers * costs.least(
                *costs.attention_decode(lengths, sz["heads"], sz["kv_heads"],
                                        sz["head_dim"], run.kv_itemsize),
                pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    elif kernel == "flash_prefill":
        for chunks, _ in run.calls["chunk"]:
            least += layers * costs.least(
                *costs.attention_prefill(chunks, sz["heads"], sz["kv_heads"],
                                         sz["head_dim"], run.kv_itemsize),
                pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"])
    else:
        raise KeyError(f"no kernel {kernel!r} in {sorted(KERNELS)}")
    return least


def math_least(run, program: str) -> float:
    """Least seconds of the traced ``program``'s model math: ternary
    matmuls at the int8 peak; attention over the valid prefixes and the
    lm_head at the bf16 peak. Every projection serves every token."""
    pk, sz = run.peaks, run.sizes
    per_token = costs.ternary_ops_per_token(projections(run.served))
    least = 0.0

    def math_time(tokens, attn_ops, logit_rows):
        lm_ops, _ = costs.lm_head(logit_rows, sz["d"], sz["vocab"])
        return (tokens * per_token / pk["int8_ops_per_s"]
                + (sz["layers"] * attn_ops + lm_ops) / pk["bf16_flops_per_s"])

    if program == "decode":
        for lengths in run.calls["decode"]:
            ops, _ = costs.attention_decode(lengths, sz["heads"],
                                            sz["kv_heads"], sz["head_dim"],
                                            run.kv_itemsize)
            least += math_time(len(lengths), ops, len(lengths))
    else:
        for chunks, n_last in run.calls["chunk"]:
            ops, _ = costs.attention_prefill(chunks, sz["heads"],
                                             sz["kv_heads"], sz["head_dim"],
                                             run.kv_itemsize)
            least += math_time(sum(n for _, n in chunks), ops, n_last)
    return least
