"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each case lowers a kernel at falcon3-1b's published widths (d 2048,
GQA 8/4 x 256, FFN 8192) with ``interpret=False`` against a *described*
v5e chip and compiles it with the TPU compiler, which ships with jaxlib.
Nothing runs: these tests catch what the Pallas interpreter accepts and
Mosaic refuses (vector ops it cannot legalize, tiling, VMEM limits), at
no chip time. Each asserts the compiled program holds the kernel
(``tpu_custom_call``) and that the kernel call carries its ``name=``,
the entry point's name, which a profiler trace shows for the kernel
and the benchmark's readers match.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import kv_cache as kvc
from repro.core import packing
from repro.kernels import ops
from repro.kernels.flash_decode import (
    flash_decode_attention,
    flash_decode_attention_latent,
)
from repro.kernels.flash_prefill import flash_prefill_attention
from repro.kernels.ternary_matmul import (
    ternary_matmul_actq_pallas,
    ternary_matmul_fused_batched_pallas,
    ternary_matmul_fused_pallas,
    ternary_matmul_pallas,
)

D, H, G, HD, FFN = 2048, 8, 4, 256, 8192  # configs/falcon3_1b.py
THETA = 1_000_042.0
QKV_N = (H + 2 * G) * HD  # fused wq|wk|wv output width
DECODE_M, PREFILL_M = 32, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    """Abstract ``tree`` (arrays or shape structs) onto the described chip."""
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _assert_kernel(jitted, *args, **kwargs):
    text = jitted.lower(*args, **kwargs).compile().as_text()
    assert "tpu_custom_call" in text
    # pallas_call(name=...) puts the name on the kernel's op_name path
    assert f"/{jitted.__name__}/pallas_call" in text


def _padded(m, n, k, codec, kind, resolve=ops._resolve_blocks):
    """Block sizes and padded operand extents as ops.py would pick them
    (``resolve=ops._actq_blocks`` for the act-quant kernel)."""
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    bm, bn, bk = resolve(m, n, k, codec, None, None, None, kind)
    return (bm, bn, bk), (ops._round_up(m, bm), ops._round_up(n, bn),
                          ops._round_up(ops._round_up(k, group), bk), group)


# the served decode shapes of the benchmark's cells, K x N:
# falcon3-7b (d 3072, FFN 23040) and falcon3-1b fused gate|up
SERVED = {"7b-gate_up": (3072, 2 * 23040), "7b-down": (23040, 3072),
          "1b-gate_up": (D, 2 * FFN)}
ACTQ_CASES = [
    pytest.param(k, n, codec, m, id=f"{name}-{codec}-{m}")
    for name, (k, n) in (("qkv", (D, QKV_N)), ("down", (FFN, D)))
    for codec in ("pack2", "pack243")
    for m in (DECODE_M, PREFILL_M)
] + [pytest.param(k, n, "pack2", DECODE_M, id=f"{name}-pack2-{DECODE_M}")
     for name, (k, n) in SERVED.items()]


@pytest.mark.parametrize("k,n,codec,m", ACTQ_CASES)
def test_actq_matmul_compiles(one_chip, k, n, codec, m):
    (bm, bn, bk), (mp, np_, kp, group) = _padded(m, n, k, codec, "actq",
                                                 ops._actq_blocks)
    _assert_kernel(
        ternary_matmul_actq_pallas,
        _spec(one_chip, (1, mp, kp), jnp.bfloat16),
        _spec(one_chip, (1, kp // group, np_), jnp.uint8),
        _spec(one_chip, (1, 1, np_), jnp.float32),
        codec=codec, block_m=bm, block_n=bn, block_k=bk,
        out_dtype=jnp.bfloat16, interpret=False,
    )


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M])
def test_fused_matmul_compiles(one_chip, m):
    (bm, bn, bk), (mp, np_, kp, group) = _padded(m, 2 * FFN, D, "pack2",
                                                 "fused")
    _assert_kernel(
        ternary_matmul_fused_pallas,
        _spec(one_chip, (mp, kp), jnp.int8),
        _spec(one_chip, (kp // group, np_), jnp.uint8),
        _spec(one_chip, (mp, 1), jnp.float32),
        _spec(one_chip, (1, np_), jnp.float32),
        codec="pack2", block_m=bm, block_n=bn, block_k=bk,
        out_dtype=jnp.bfloat16, interpret=False,
    )


@pytest.mark.parametrize("carried_scale", [False, True],
                         ids=["actq", "carried-scale"])
def test_expert_matmul_compiles(one_chip, carried_scale):
    e, c = 8, DECODE_M
    (bm, bn, bk), (mp, np_, kp, group) = _padded(
        c, 2 * FFN // e, D, "pack2", "expert",
        ops._resolve_blocks if carried_scale else ops._actq_blocks)
    w = _spec(one_chip, (e, kp // group, np_), jnp.uint8)
    ws = _spec(one_chip, (e, 1, np_), jnp.float32)
    blocks = dict(codec="pack2", block_m=bm, block_n=bn, block_k=bk,
                  out_dtype=jnp.bfloat16, interpret=False)
    if carried_scale:
        _assert_kernel(
            ternary_matmul_fused_batched_pallas,
            _spec(one_chip, (e, mp, kp), jnp.int8), w,
            _spec(one_chip, (e, mp, 1), jnp.float32), ws, **blocks)
    else:
        _assert_kernel(ternary_matmul_actq_pallas,
                       _spec(one_chip, (e, mp, kp), jnp.bfloat16), w, ws,
                       **blocks)


def _cache(sharding, slots, hot_cap, max_len, dtype, paged):
    """A cache in the stored (G·HD) layout, abstract on the chip."""
    cold = max_len - hot_cap
    if paged:
        page = ops.default_page_size(H // G, HD, max_len)
        make = lambda: kvc.init_paged_cache(  # noqa: E731
            slots, hot_cap, cold, (G * HD,), dtype, page_size=page)
    else:
        make = lambda: kvc.init_cache(slots, hot_cap, cold, (G * HD,), dtype)  # noqa: E731
    return _on(sharding, jax.eval_shape(make))


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8"])
def test_flash_decode_fused_rope_compiles(one_chip, paged, kv_dtype):
    slots = 8
    cache = _cache(one_chip, slots, 32, 2048, kv_dtype, paged)
    _assert_kernel(
        flash_decode_attention,
        _spec(one_chip, (slots, H, HD), jnp.bfloat16), cache,
        k_new=_spec(one_chip, (slots, G, HD), jnp.bfloat16),
        v_new=_spec(one_chip, (slots, G, HD), jnp.bfloat16),
        active=_spec(one_chip, (slots,), jnp.bool_),
        rope_theta=THETA, impl="pallas", interpret=False,
    )


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_flash_prefill_continuation_compiles(one_chip, paged):
    slots, chunk = 4, 256
    cache = _cache(one_chip, slots, 32, 2048, jnp.bfloat16, paged)
    _assert_kernel(
        flash_prefill_attention,
        _spec(one_chip, (slots, chunk, H, HD), jnp.bfloat16),
        _spec(one_chip, (slots, chunk, G, HD), jnp.bfloat16),
        _spec(one_chip, (slots, chunk, G, HD), jnp.bfloat16),
        cache, _spec(one_chip, (slots,), jnp.int32),
        rope_theta=THETA, impl="pallas", interpret=False,
    )


@pytest.mark.parametrize("kernel", ["ternary_matmul", "flash_decode_plain",
                                    "flash_decode_latent"])
def test_off_path_kernels_carry_their_names(one_chip, kernel):
    """The kernels the serving steps do not run at these widths: the
    int8-in ternary matmul, the pre-rotated flash-decode form and the
    MLA latent form (DeepSeek-V3 widths: 128 heads, latent 512 + rope
    64)."""
    if kernel == "ternary_matmul":
        (bm, bn, bk), (mp, np_, kp, group) = _padded(DECODE_M, D, FFN,
                                                     "pack2", "fused")
        _assert_kernel(
            ternary_matmul_pallas,
            _spec(one_chip, (mp, kp), jnp.int8),
            _spec(one_chip, (kp // group, np_), jnp.uint8),
            codec="pack2", block_m=bm, block_n=bn, block_k=bk,
            interpret=False)
    elif kernel == "flash_decode_plain":
        slots = 8
        cache = _cache(one_chip, slots, 32, 2048, jnp.bfloat16, False)
        _assert_kernel(flash_decode_attention,
                       _spec(one_chip, (slots, H, HD), jnp.bfloat16), cache,
                       impl="pallas", interpret=False)
    else:
        slots, h, dl, dr = 8, 128, 512, 64

        def make():
            c = kvc.init_cache(slots, 32, 2048 - 32, (dl + dr,),
                               jnp.bfloat16)
            return c._replace(hot_v=jnp.zeros((slots, 32, 0), jnp.bfloat16),
                              cold_v=jnp.zeros((slots, 2048 - 32, 0),
                                               jnp.bfloat16))

        _assert_kernel(flash_decode_attention_latent,
                       _spec(one_chip, (slots, h, dl + dr), jnp.bfloat16),
                       _on(one_chip, jax.eval_shape(make)), dl,
                       (192.0) ** -0.5, impl="pallas", interpret=False)


# an op of these kinds whose result holds a whole layer's page pool is a
# per-layer copy of the cache: a slice of a layer, its write-back, or a
# copy of the stack
_POOL_COPY_OPS = ("copy", "copy-start", "dynamic-slice",
                  "dynamic-update-slice")
_HLO_OP = re.compile(r"%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _assert_cache_in_place(one_chip, monkeypatch, build):
    """Compile ``build(cfg, slots) -> (jitted step, its argument specs
    after params and cache)`` at falcon3-1b widths (2 layers, 12 slots,
    8k context, a 64-page pool) with the cache donated, and assert the
    step keeps the stacked KV cache where it lives: its temporaries stay
    below one layer's pool, and no copy, slice or write-back of a
    pool-sized buffer is left in the compiled program. The model code
    picks the Pallas kernels (compiled, not interpreted) by
    ``jax.default_backend()``, which reads "tpu" here as on the chip."""
    import dataclasses

    from repro.configs import get_config
    from repro.models import pack as pack_lib
    from repro.models import transformer as T

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = get_config("falcon3-1b")
    cfg = dataclasses.replace(
        base, n_layers=2, bitnet=dataclasses.replace(base.bitnet, lora_rank=0))
    slots, max_len, n_pages = 12, 8192, 64
    params = jax.eval_shape(lambda: pack_lib.pack_params(
        T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16), cfg))
    cache = jax.eval_shape(lambda: T.init_decode_cache(
        cfg, slots, max_len, 32, jnp.bfloat16, paged=True, page_size=256,
        n_pages=n_pages))
    step, specs = build(T, cfg, slots)
    compiled = step.lower(
        _on(one_chip, params), _on(one_chip, cache),
        *[_spec(one_chip, shape, dt) for shape, dt in specs],
    ).compile()
    pool = cache["attn"].pool_k
    layer_pool = pool.size // pool.shape[0]  # elements of one layer's K pool
    layer_bytes = 2 * layer_pool * pool.dtype.itemsize  # K + V
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    copies = []
    for name, dims, op in _HLO_OP.findall(text):
        n = int(np.prod([int(d) for d in dims.split(",") if d]))
        if n >= layer_pool and (
                op in _POOL_COPY_OPS
                or any(k in name for k in ("dynamic-slice", "dynamic_slice",
                                           "dynamic-update-slice",
                                           "dynamic_update_slice"))
                or name.startswith("copy")):
            copies.append(f"{name} {op}[{dims}]")
    assert not copies, copies


def test_decode_step_reads_the_stacked_cache_in_place(one_chip, monkeypatch):
    """The decode step (``_assert_cache_in_place``): flash-decode reads
    each layer of the stack in place and the append writes one row per
    slot into it."""

    def build(T, cfg, slots):
        step = jax.jit(
            lambda p, c, t, a: T.decode_step(p, cfg, t, c, mode="packed",
                                             active=a),
            donate_argnums=(1,))
        return step, [((slots,), jnp.int32), ((slots,), jnp.bool_)]

    _assert_cache_in_place(one_chip, monkeypatch, build)


def test_chunk_step_reads_the_stacked_cache_in_place(one_chip, monkeypatch):
    """The chunked-prefill step (``_assert_cache_in_place``), at a
    16-token chunk so its activations stay small beside the pool:
    flash-prefill reads each layer of the stack in place and the append
    scatters the chunk's rows into it."""

    def build(T, cfg, slots):
        step = jax.jit(
            lambda p, c, t, n: T.prefill_chunk_step(p, cfg, t, c, n,
                                                    mode="packed"),
            donate_argnums=(1,))
        return step, [((slots, 16), jnp.int32), ((slots,), jnp.int32)]

    _assert_cache_in_place(one_chip, monkeypatch, build)
