"""Fused ternary fast path: epilogue-fused kernel, fused projections, blocks.

Covers the production path end to end (ISSUE 2 + ISSUE 3):
  * epilogue-fused Pallas kernel vs the XLA dot+rescale (interpret on CPU),
    including the int-exact accumulator (unit scales) and odd shapes;
  * two-phase act-quant PROLOGUE kernel vs the quantize-then-matmul
    reference — bit-exact, both codecs, M=1/odd shapes, A8 and A4;
  * E-loop expert kernel (one launch over all experts) vs the vmapped
    per-expert forward — bit-exact, incl. the fused gate‖up MoE path and
    the carried-scale (fuse_act_quant=False) form, which no longer falls
    back to the vmapped XLA path;
  * MLA down-projection fusion (w_dq‖w_dkv -> "w_dqkv", post-split norms);
  * plane decode of the act-quant kernel (K-planes against plane-ordered
    activations) bit-identical to the K-order decode and to XLA;
  * shape-aware block selection (decode-shaped auto blocks stay exact);
  * pack2/pack243 zero-code padding repair regression (operator precedence);
  * fuse_packed / FusedPackedLinear: fused QKV and gate-up vs separate
    projections, bit-exact at the projection level, both impls;
  * config-threaded impl selection (BitNetConfig.impl).

Everything here runs in Pallas interpret mode on CPU — this module is the
CI kernel-parity lane (pytest -m kernel_parity).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import bitlinear, packing
from repro.kernels import ops, ref

pytestmark = pytest.mark.kernel_parity

CODECS = ("pack2", "pack243")
ODD_SHAPES = [
    (1, 256, 128),   # GEMV decode
    (5, 33, 7),      # everything ragged
    (8, 64, 16),     # tiny
    (16, 512, 256),  # one aligned block
    (32, 520, 96),   # K not a block/group multiple
]


def _case(seed, m, k, n):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    xq = jax.random.randint(kx, (m, k), -128, 128, dtype=jnp.int8)
    wq = jax.random.randint(kw, (k, n), -1, 2, dtype=jnp.int8)
    return xq, wq


def _pack(wq, codec):
    return (packing.pack2 if codec == "pack2" else packing.pack243)(wq)


# ---------------------------------------------------------------------------
# Epilogue-fused kernel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("m,k,n", ODD_SHAPES)
def test_fused_epilogue_matches_oracle(codec, m, k, n):
    xq, wq = _case(m * 131 + k * 7 + n, m, k, n)
    packed = _pack(wq, codec)
    xs = jax.random.uniform(jax.random.PRNGKey(1), (m, 1)) + 0.5
    cs = jax.random.uniform(jax.random.PRNGKey(2), (n,)) + 0.5
    want = (
        (np.asarray(xq, np.float64) @ np.asarray(wq, np.float64))
        * np.asarray(cs, np.float64)[None, :]
        / np.asarray(xs, np.float64)
    )
    for impl in ("pallas", "xla"):
        got = ops.ternary_matmul_fused(
            xq, packed, xs, cs, k=k, codec=codec, impl=impl
        )
        np.testing.assert_allclose(np.asarray(got, np.float64), want,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("codec", CODECS)
def test_fused_epilogue_int_accumulator_exact(codec):
    """With unit scales the fused output IS the int32 accumulator — the
    integer pipeline of the fused kernel is bit-identical to the raw one."""
    m, k, n = 7, 130, 40
    xq, wq = _case(99, m, k, n)
    packed = _pack(wq, codec)
    got = ops.ternary_matmul_fused(
        xq, packed, jnp.ones((m, 1)), jnp.ones((n,)), k=k, codec=codec,
        impl="pallas",
    )
    want = ref.ternary_matmul_ref(xq, packed, k=k, codec=codec)
    np.testing.assert_array_equal(
        np.asarray(got, np.int64), np.asarray(want, np.int64)
    )


def test_fused_epilogue_batched_leading_dims():
    xq = jax.random.randint(jax.random.PRNGKey(1), (2, 3, 64), -128, 128,
                            dtype=jnp.int8)
    wq = jax.random.randint(jax.random.PRNGKey(2), (64, 32), -1, 2,
                            dtype=jnp.int8)
    packed = packing.pack2(wq)
    xs = jax.random.uniform(jax.random.PRNGKey(3), (2, 3, 1)) + 0.5
    cs = jax.random.uniform(jax.random.PRNGKey(4), (32,)) + 0.5
    got = ops.ternary_matmul_fused(xq, packed, xs, cs, k=64, codec="pack2",
                                   impl="pallas")
    acc = jnp.einsum("btk,kn->btn", xq.astype(jnp.int32), wq.astype(jnp.int32))
    want = acc.astype(jnp.float32) * cs / xs
    assert got.shape == (2, 3, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Two-phase act-quant prologue kernel
# ---------------------------------------------------------------------------


def _raw_case(seed, m, k, n, codec):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k))
    wq = jax.random.randint(kw, (k, n), -1, 2, dtype=jnp.int8)
    return x, _pack(wq, codec)


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("m,k,n", ODD_SHAPES)
def test_actq_prologue_matches_quantize_then_matmul(codec, m, k, n):
    """The tentpole guarantee: in-kernel act-quant (absmax K-sweep + int8
    quantize in VMEM) is BIT-EXACT against the two-pass reference —
    act_quant as a separate op feeding the known-scale fused kernel, and
    the XLA quantize+dot+rescale path."""
    from repro.core.ternary import act_quant

    x, packed = _raw_case(m * 37 + k * 5 + n, m, k, n, codec)
    cs = jax.random.uniform(jax.random.PRNGKey(3), (n,)) + 0.5
    got = ops.ternary_matmul_actq(x, packed, cs, k=k, codec=codec,
                                  impl="pallas")
    q = act_quant(x)
    want_fused = ops.ternary_matmul_fused(q.xq, packed, q.scale, cs, k=k,
                                          codec=codec, impl="pallas")
    want_xla = ops.ternary_matmul_actq(x, packed, cs, k=k, codec=codec,
                                       impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_fused))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want_xla))


@pytest.mark.parametrize("codec", CODECS)
def test_actq_prologue_a4(codec):
    """A4 activations (BitNet a4.8 / TriMLA-native) quantize identically
    in the prologue: qmax 7 / qmin -8 threads through."""
    m, k, n = 5, 130, 40
    x, packed = _raw_case(21, m, k, n, codec)
    cs = jnp.ones((n,))
    got = ops.ternary_matmul_actq(x, packed, cs, k=k, codec=codec,
                                  act_bits=4, impl="pallas")
    want = ops.ternary_matmul_actq(x, packed, cs, k=k, codec=codec,
                                   act_bits=4, impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_actq_prologue_batched_leading_dims_and_bf16():
    """Leading batch dims flatten through, and bf16 inputs quantize to the
    same int8 values as act_quant's f32 upcast does."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 64)).astype(jnp.bfloat16)
    wq = jax.random.randint(jax.random.PRNGKey(2), (64, 32), -1, 2,
                            dtype=jnp.int8)
    packed = packing.pack2(wq)
    cs = jax.random.uniform(jax.random.PRNGKey(4), (32,)) + 0.5
    got = ops.ternary_matmul_actq(x, packed, cs, k=64, codec="pack2",
                                  impl="pallas")
    want = ops.ternary_matmul_actq(x, packed, cs, k=64, codec="pack2",
                                   impl="xla")
    assert got.shape == (2, 3, 32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("codec", CODECS)
def test_actq_prologue_scale_persists_across_column_tiles(codec):
    """The absmax sweep runs only at the first output-column tile (j == 0)
    and the finished scale in VMEM scratch serves every later j — pin it
    with a grid that has several j AND several i tiles."""
    m, k, n = 40, 96, 120  # blocks below force gm=2, gn=4, gk=2
    x, packed = _raw_case(77, m, k, n, codec)
    cs = jax.random.uniform(jax.random.PRNGKey(9), (n,)) + 0.5
    got = ops.ternary_matmul_actq(
        x, packed, cs, k=k, codec=codec, impl="pallas",
        block_m=32, block_n=32, block_k=40,
    )
    want = ops.ternary_matmul_actq(x, packed, cs, k=k, codec=codec,
                                   impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_actq_prologue_rejects_unsupported_bits():
    """pallas and xla reject unsupported act_bits identically."""
    x, packed = _raw_case(1, 4, 64, 32, "pack2")
    cs = jnp.ones((32,))
    for impl in ("pallas", "xla"):
        with pytest.raises(ValueError, match="unsupported activation bits"):
            ops.ternary_matmul_actq(x, packed, cs, k=64, act_bits=6,
                                    impl=impl)


def test_actq_prologue_zero_row():
    """An all-zero activation row must produce an all-zero output (EPS
    guard in the in-kernel scale), not NaN/Inf."""
    m, k, n = 4, 64, 32
    x, packed = _raw_case(30, m, k, n, "pack2")
    x = x.at[1].set(0.0)
    cs = jnp.ones((n,))
    got = ops.ternary_matmul_actq(x, packed, cs, k=k, codec="pack2",
                                  impl="pallas")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got[1]), 0.0)


def test_packed_matmul_carried_scale_fallback():
    """packed_matmul accepts an already-quantized activation (the
    carried-scale fallback): same result as handing it the raw floats."""
    from repro.core.ternary import act_quant

    pw = bitlinear.quantize_pack(
        {"w": jax.random.normal(jax.random.PRNGKey(5), (96, 48))})
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 96))
    y_raw = bitlinear.packed_matmul(pw, x, impl="pallas")
    y_carried = bitlinear.packed_matmul(pw, act_quant(x), impl="pallas")
    y_unfused = bitlinear.packed_matmul(pw, x, impl="pallas", fuse_actq=False)
    np.testing.assert_array_equal(np.asarray(y_raw), np.asarray(y_carried))
    np.testing.assert_array_equal(np.asarray(y_raw), np.asarray(y_unfused))


def test_linear_fuse_act_quant_config_threading():
    """BitNetConfig.fuse_act_quant=False pins the separate-act-quant path;
    results stay identical either way (same numerics, different fusion)."""
    import dataclasses as dc

    from repro.models import qops

    cfg = get_smoke_config("falcon3-1b")
    cfg_p = dc.replace(cfg, bitnet=dc.replace(cfg.bitnet, impl="pallas"))
    cfg_np = dc.replace(
        cfg, bitnet=dc.replace(cfg.bitnet, impl="pallas", fuse_act_quant=False)
    )
    leaf = bitlinear.quantize_pack(
        _random_linear(jax.random.PRNGKey(3), 64, 48))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 64))
    y_f = qops.linear(leaf, x, cfg_p, "packed")
    y_s = qops.linear(leaf, x, cfg_np, "packed")
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_s))


# ---------------------------------------------------------------------------
# E-loop expert kernel (one launch over all experts)
# ---------------------------------------------------------------------------


def _expert_case(seed, e, c, k, n, codec):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (e, c, k))
    wq = jax.random.randint(kw, (e, k, n), -1, 2, dtype=jnp.int8)
    pack = packing.pack2 if codec == "pack2" else packing.pack243
    return x, jax.vmap(pack)(wq)


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("c", [1, 5, 16])
def test_expert_eloop_matches_vmapped(codec, c):
    """One E-loop launch (leading expert grid dim) == the vmapped
    per-expert quantize-then-matmul, bit-for-bit."""
    e, k, n = 4, 96, 72
    x, packed = _expert_case(c * 11 + 1, e, c, k, n, codec)
    cs = jax.random.uniform(jax.random.PRNGKey(3), (e, n)) + 0.5
    got = ops.ternary_matmul_expert(x, packed, cs, k=k, codec=codec,
                                    impl="pallas")
    want = ops.ternary_matmul_expert(x, packed, cs, k=k, codec=codec,
                                     impl="xla")
    assert got.shape == (e, c, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("codec", CODECS)
def test_expert_packed_matmul_paths_agree(codec):
    """bitlinear.expert_packed_matmul: E-loop pallas == vmapped xla for
    both leaf kinds (scalar-scale PackedLinear, per-column fused)."""
    from repro.models.pack import fuse_packed

    e, c, k, ff = 3, 4, 64, 32
    keys = jax.random.split(jax.random.PRNGKey(17), 3)
    w_g = jax.random.normal(keys[0], (e, k, ff)) * k**-0.5
    w_u = jax.random.normal(keys[1], (e, k, ff)) * k**-0.5
    from repro.models.pack import _pack_weight

    pg = _pack_weight(w_g, codec)
    pu = _pack_weight(w_u, codec)
    fused = fuse_packed([pg, pu])
    assert fused.packed.ndim == 3 and fused.scale.shape == (e, 2 * ff)
    x = jax.random.normal(keys[2], (e, c, k))
    for leaf in (pg, fused):
        y_p = bitlinear.expert_packed_matmul(leaf, x, impl="pallas")
        y_x = bitlinear.expert_packed_matmul(leaf, x, impl="xla")
        np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_x))


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("c", [1, 5, 16])
def test_expert_carried_scale_eloop_matches_vmapped(codec, c):
    """The carried-scale E-loop kernel (fuse_act_quant=False form:
    pre-quantized int8 x + per-row scale, no absmax phase) == the vmapped
    per-expert known-scale pipeline, bit-for-bit."""
    from repro.core.ternary import act_quant

    e, k, n = 4, 96, 72
    x, packed = _expert_case(c * 13 + 5, e, c, k, n, codec)
    cs = jax.random.uniform(jax.random.PRNGKey(4), (e, n)) + 0.5
    q = act_quant(x)
    got = ops.ternary_matmul_expert_fused(
        q.xq, packed, q.scale, cs, k=k, codec=codec, impl="pallas")
    want = ops.ternary_matmul_expert_fused(
        q.xq, packed, q.scale, cs, k=k, codec=codec, impl="xla")
    assert got.shape == (e, c, n)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and both equal the prologue-fused kernel (same int ops end to end)
    fused = ops.ternary_matmul_expert(x, packed, cs, k=k, codec=codec,
                                      impl="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fused))


@pytest.mark.parametrize("codec", CODECS)
def test_expert_packed_matmul_carried_scale_no_xla_fallback(codec):
    """ROADMAP gap closed: with fuse_actq=False (or a QuantizedActivation
    producer) the Pallas path runs the carried-scale E-loop kernel and
    stays bit-identical to the vmapped XLA path for both leaf kinds."""
    from repro.core.ternary import act_quant
    from repro.models.pack import _pack_weight, fuse_packed

    e, c, k, ff = 3, 4, 64, 32
    keys = jax.random.split(jax.random.PRNGKey(19), 3)
    w_g = jax.random.normal(keys[0], (e, k, ff)) * k**-0.5
    w_u = jax.random.normal(keys[1], (e, k, ff)) * k**-0.5
    pg = _pack_weight(w_g, codec)
    fused = fuse_packed([pg, _pack_weight(w_u, codec)])
    x = jax.random.normal(keys[2], (e, c, k))
    for leaf in (pg, fused):
        want = bitlinear.expert_packed_matmul(leaf, x, impl="xla",
                                              fuse_actq=False)
        got = bitlinear.expert_packed_matmul(leaf, x, impl="pallas",
                                             fuse_actq=False)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        got_q = bitlinear.expert_packed_matmul(leaf, act_quant(x),
                                               impl="pallas")
        np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want))


def test_moe_fused_gate_up_eloop_exact():
    """apply_moe with the pack-time-fused w_gu leaf == the unfused tree,
    on the XLA path AND the E-loop Pallas path (bit-exact end to end)."""
    import dataclasses as dc

    from repro.models import moe as moe_lib
    from repro.models import pack as pack_lib

    cfg = get_smoke_config("mixtral-8x22b")
    p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
    pf = pack_lib.pack_params(p, cfg)
    pu = pack_lib.pack_params(p, cfg, fuse=False)
    assert "w_gu" in pf and "w_gate" in pu
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 6, cfg.d_model))
    y_u, _ = moe_lib.apply_moe(pu, x, cfg, "packed")
    y_f, _ = moe_lib.apply_moe(pf, x, cfg, "packed")
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))
    cfg_p = dc.replace(cfg, bitnet=dc.replace(cfg.bitnet, impl="pallas"))
    y_p, _ = moe_lib.apply_moe(pf, x, cfg_p, "packed")
    np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_u))


# ---------------------------------------------------------------------------
# MLA down-projection fusion (w_dq‖w_dkv -> "w_dqkv")
# ---------------------------------------------------------------------------


def test_mla_fused_down_projection_exact():
    """mla_full with the fused w_dqkv leaf == separate w_dq/w_dkv (the
    per-branch q_ln/kv_ln norms apply post-split), bit-exact."""
    from repro.models import attention as attn
    from repro.models import pack as pack_lib

    cfg = get_smoke_config("deepseek-v3-671b")
    p = attn.init_mla(jax.random.PRNGKey(0), cfg)
    pf = pack_lib.pack_params(p, cfg)
    pu = pack_lib.pack_params(p, cfg, fuse=False)
    assert "w_dqkv" in pf and "w_dq" in pu
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    pos = jnp.arange(5)
    y_f = attn.mla_full(pf, x, cfg, "packed", pos)
    y_u = attn.mla_full(pu, x, cfg, "packed", pos)
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_u))


# ---------------------------------------------------------------------------
# Plane decode of the act-quant kernel
# ---------------------------------------------------------------------------

PLANE_K = {"pack2": 1024, "pack243": 1280}  # two plane tiles each
PLANE_CASES = {  # case -> (M, K short of PLANE_K); "expert" is the E-loop form
    "m12": (12, 0), "m32": (32, 0), "m256": (256, 0), "zero_row": (12, 0),
    "pad_k": (12, 24), "expert": (5, 0), "k64": (12, None),
}


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("case", list(PLANE_CASES))
def test_actq_plane_decode_bit_identical(codec, case):
    """The act-quant kernel contracts each K-plane of a packed tile against
    its slice of the plane-ordered activations; the result is
    bit-identical to the XLA quantize-then-matmul path and to the
    known-scale kernel, which decodes the trits in K order. K = 64 pads
    up to one whole plane tile."""
    from repro.core.ternary import act_quant

    m, k_cut = PLANE_CASES[case]
    k, n = (64 if k_cut is None else PLANE_K[codec] - k_cut), 512
    if case == "expert":
        x, packed = _expert_case(k + m, 2, m, k, n, codec)
        cs = jax.random.uniform(jax.random.PRNGKey(5), (2, n)) + 0.5
        fn, k_order = ops.ternary_matmul_expert, ops.ternary_matmul_expert_fused
    else:
        x, packed = _raw_case(k + m, m, k, n, codec)
        cs = jax.random.uniform(jax.random.PRNGKey(5), (n,)) + 0.5
        fn, k_order = ops.ternary_matmul_actq, ops.ternary_matmul_fused
    x = x.astype(jnp.bfloat16)
    if case == "zero_row":
        x = x.at[3].set(0.0)

    got = fn(x, packed, cs, k=k, codec=codec, impl="pallas")
    want = fn(x, packed, cs, k=k, codec=codec, impl="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    q = act_quant(x)
    want = k_order(q.xq, packed, q.scale, cs, k=k, codec=codec, impl="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "zero_row":
        np.testing.assert_array_equal(np.asarray(got[3]), 0.0)


def test_actq_blocks_whole_plane_tiles():
    """The act-quant entry points round block_k up to whole plane tiles
    (block_k / g a multiple of 128 lanes), and the served decode and
    prefill blocks are already whole, so the tables' blocks stand."""
    def bk(m, k, codec, block_k=None, kind="actq"):
        return ops._actq_blocks(m, 512, k, codec, None, None, block_k, kind)[2]

    assert bk(12, 64, "pack2") == 512 and bk(12, 65, "pack243") == 640
    assert bk(12, 1024, "pack2", block_k=256) == 512
    assert bk(12, 1280, "pack243", block_k=320, kind="expert") == 640
    for k in (2048, 3072, 8192, 23040):
        for m in (32, 256):
            for codec in CODECS:
                for kind in ("actq", "expert"):
                    args = (m, 4096, k, codec, None, None, None, kind)
                    assert ops._actq_blocks(*args) == ops._resolve_blocks(*args)


# ---------------------------------------------------------------------------
# Shape-aware block selection
# ---------------------------------------------------------------------------


def test_select_blocks_decode_vs_prefill():
    # decode-shaped M stays on the skinny row of the table, not pad-to-256
    for m in (1, 8, 32):
        bm, bn, bk = ops.select_blocks(m, 2048, 2048, "pack2")
        assert bm == 32 and bn == 512 and bk == 1024
    assert ops.select_blocks(64, 2048, 2048, "pack2")[0] == 64
    assert ops.select_blocks(4096, 4096, 4096, "pack2") == (256, 256, 512)
    # caps: block_n / block_k never exceed the padded operand
    bm, bn, bk = ops.select_blocks(1, 96, 200, "pack243")
    assert bn == 128 and bk % packing.PACK243_GROUP == 0 and bk <= 205
    # pack243 lane alignment: block_k snaps to lcm(5, 128) = 640 so the
    # (bm, bk) x tile and (bk/5, bn) packed tile compile on real TPU
    for m in (1, 32, 4096):
        bk243 = ops.select_blocks(m, 2048, 2048, "pack243")[2]
        assert bk243 == 640, bk243


def test_select_blocks_kinds():
    """The two-phase and E-loop grids get their own table rows: the actq
    decode row halves block_k (raw-float x tile, read twice); the expert
    decode row narrows block_n."""
    assert ops.select_blocks(1, 2048, 2048, "pack2", kind="actq") == (32, 512, 512)
    assert ops.select_blocks(1, 2048, 2048, "pack2", kind="expert") == (32, 256, 512)
    # prefill tier is shared across kinds
    for kind in ("fused", "actq", "expert"):
        assert ops.select_blocks(4096, 4096, 4096, "pack2", kind=kind) == (256, 256, 512)
    # pack243 lane alignment applies to every table
    assert ops.select_blocks(1, 2048, 2048, "pack243", kind="actq")[2] == 640


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("m", [1, 8, 32])
def test_auto_blocks_decode_shapes_exact(codec, m):
    """Auto-selected decode blocks (no explicit block args) stay bit-exact."""
    k, n = 192, 72
    xq, wq = _case(m * 17 + 3, m, k, n)
    got = ops.ternary_matmul(xq, _pack(wq, codec), k=k, codec=codec,
                             impl="pallas")
    np.testing.assert_array_equal(
        np.asarray(got, np.int64), np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    )


# ---------------------------------------------------------------------------
# Padding zero-code repair (regression: `and`/`or` precedence, ops.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", CODECS)
def test_column_padding_zero_code_repair(codec):
    """Non-aligned N forces column padding; pack2 hits the (previously
    mis-parenthesized) repair branch, pack243 needs the 121 rewrite."""
    m, k, n = 4, 40, 7  # n far below any block_n -> heavy column padding
    xq, wq = _case(5, m, k, n)
    got = ops.ternary_matmul(
        xq, _pack(wq, codec), k=k, codec=codec, impl="pallas",
        block_m=8, block_n=32, block_k=20,
    )
    np.testing.assert_array_equal(
        np.asarray(got, np.int64), np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    )


@pytest.mark.parametrize("codec", CODECS)
def test_pad_operands_padding_decodes_to_zero_trits(codec):
    """Direct invariant: every padded weight byte must decode to zero trits
    (TriMLA skip-ops) for BOTH codecs and BOTH padding directions. This is
    the regression for the `a and b or c` precedence hazard: under the old
    parse the repair branch ran for pack2 column padding (saved only by the
    inner zero_code check) — assert the invariant itself, not the luck."""
    m, k, n = 4, 33, 7
    xq, wq = _case(8, m, k, n)
    packed = _pack(wq, codec)
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    x2, wp, lead, m2, n2 = ops._pad_operands(xq, packed, codec, 8, 32, 20)
    unpack = packing.unpack2 if codec == "pack2" else packing.unpack243
    trits = np.asarray(unpack(wp))  # (Kp, Np) int8, no trim
    assert wp.shape[0] > packed.shape[0] and wp.shape[1] > n  # both pads hit
    np.testing.assert_array_equal(trits[packed.shape[0] * group :, :], 0)
    np.testing.assert_array_equal(trits[:, n:], 0)
    np.testing.assert_array_equal(np.asarray(x2[:, k:]), 0)


def test_pack243_row_padding_only_repair():
    """K-only padding (N block-aligned): pack243 pad rows must decode to
    zero trits, not byte-0 = (-1,-1,-1,-1,-1)."""
    m, k, n = 4, 33, 32  # packed K = 35 bytes*5, block_k=20 -> pad to 40
    xq, wq = _case(6, m, k, n)
    got = ops.ternary_matmul(
        xq, _pack(wq, "pack243"), k=k, codec="pack243", impl="pallas",
        block_m=8, block_n=32, block_k=20,
    )
    np.testing.assert_array_equal(
        np.asarray(got, np.int64), np.asarray(xq, np.int64) @ np.asarray(wq, np.int64)
    )


# ---------------------------------------------------------------------------
# Fused projections (fuse_packed / FusedPackedLinear)
# ---------------------------------------------------------------------------


def _random_linear(key, k, n):
    return {"w": jax.random.normal(key, (k, n)) * k**-0.5}


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("m", [1, 5, 16])
def test_fused_group_matches_separate(codec, impl, m):
    """wq‖wk‖wv fused into one launch == three separate projections,
    bit-for-bit (same int accumulators, same per-segment scales)."""
    from repro.models.pack import fuse_packed

    k = 96
    widths = (64, 32, 32)  # h*hd, g*hd, g*hd
    keys = jax.random.split(jax.random.PRNGKey(11), len(widths) + 1)
    leaves = [_random_linear(kk, k, w) for kk, w in zip(keys, widths)]
    pws = [bitlinear.quantize_pack(lf, codec=codec) for lf in leaves]
    fused = fuse_packed(pws)
    assert fused.splits == widths
    assert fused.packed.shape[-1] == sum(widths)

    x = jax.random.normal(keys[-1], (m, k))
    y = bitlinear.packed_matmul(fused, x, impl=impl)
    off = 0
    for pw, w in zip(pws, widths):
        want = bitlinear.packed_matmul(pw, x, impl=impl)
        np.testing.assert_array_equal(
            np.asarray(y[:, off : off + w]), np.asarray(want)
        )
        off += w


@pytest.mark.parametrize("codec", CODECS)
def test_fused_pallas_matches_separate_xla(codec):
    """The production combination: fused + Pallas epilogue vs the historical
    separate + XLA path, float tolerance 1e-5 (acceptance criterion)."""
    from repro.models.pack import fuse_packed

    k, widths = 130, (48, 24, 24)
    keys = jax.random.split(jax.random.PRNGKey(13), len(widths) + 1)
    pws = [
        bitlinear.quantize_pack(_random_linear(kk, k, w), codec=codec)
        for kk, w in zip(keys, widths)
    ]
    fused = fuse_packed(pws)
    x = jax.random.normal(keys[-1], (5, k))
    y = bitlinear.packed_matmul(fused, x, impl="pallas")
    want = jnp.concatenate(
        [bitlinear.packed_matmul(pw, x, impl="xla") for pw in pws], axis=-1
    )
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bitlinear_apply_dispatches_fused():
    """bitlinear.apply (the mode-dispatching forward) routes fused leaves
    to the packed path, not apply_qat."""
    from repro.models.pack import fuse_packed

    pws = [
        bitlinear.quantize_pack(_random_linear(jax.random.PRNGKey(i), 64, 16))
        for i in range(2)
    ]
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 64))
    y = bitlinear.apply(fuse_packed(pws), x)
    want = jnp.concatenate([bitlinear.apply(pw, x) for pw in pws], axis=-1)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))


def test_attention_fused_qkv_exact():
    """_project_qkv via the fused leaf == separate projections (with the
    v-segment LoRA applied after the split), prefill and decode shapes."""
    from repro.models import attention as attn
    from repro.models import pack as pack_lib

    cfg = get_smoke_config("zamba2-7b")  # qk_norm off, lora_v on
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    pf = pack_lib.pack_params(p, cfg)
    pu = pack_lib.pack_params(p, cfg, fuse=False)
    assert "wqkv" in pf and "wq" in pu
    for shape in ((2, 5, cfg.d_model), (3, 1, cfg.d_model)):
        x = jax.random.normal(jax.random.PRNGKey(2), shape)
        for a, b in zip(
            attn._project_qkv(pf, x, cfg, "packed"),
            attn._project_qkv(pu, x, cfg, "packed"),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mlp_fused_gate_up_exact():
    from repro.models import pack as pack_lib
    from repro.models.layers import apply_mlp, init_mlp

    cfg = get_smoke_config("falcon3-1b")
    p = init_mlp(jax.random.PRNGKey(0), cfg)
    pf = pack_lib.pack_params(p, cfg)
    pu = pack_lib.pack_params(p, cfg, fuse=False)
    assert "wgu" in pf and "gate" in pu
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.d_model))
    np.testing.assert_array_equal(
        np.asarray(apply_mlp(pf, x, cfg, "packed")),
        np.asarray(apply_mlp(pu, x, cfg, "packed")),
    )


def test_model_prefill_decode_fused_vs_unfused():
    """End-to-end smoke: fused vs unfused trees agree. Tolerance is loose
    on purpose — a 1-ulp float wobble from XLA refusing can flip an int8
    act-quant bucket downstream (~3e-2 on one logit row); the strict
    guarantees live in the projection-level tests above."""
    from repro.models import pack as pack_lib
    from repro.models import transformer as T

    cfg = get_smoke_config("falcon3-1b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    fused = pack_lib.pack_params(params, cfg)
    unfused = pack_lib.pack_params(params, cfg, fuse=False)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    lg_f, cache_f = T.prefill(fused, cfg, {"tokens": toks}, max_len=24)
    lg_u, cache_u = T.prefill(unfused, cfg, {"tokens": toks}, max_len=24)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_u),
                               rtol=1e-2, atol=5e-2)
    nxt = jnp.argmax(lg_f, -1).astype(jnp.int32)
    d_f, _ = T.decode_step(fused, cfg, nxt, cache_f)
    d_u, _ = T.decode_step(unfused, cfg, nxt, cache_u)
    np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_u),
                               rtol=1e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Config-threaded impl selection
# ---------------------------------------------------------------------------


def test_resolve_impl():
    from repro.models import qops

    cfg = get_smoke_config("falcon3-1b")
    # auto on CPU -> xla (Pallas would run in the slow interpreter)
    assert jax.default_backend() == "cpu"
    assert qops.resolve_impl(cfg) == "xla"
    forced = dataclasses.replace(
        cfg, bitnet=dataclasses.replace(cfg.bitnet, impl="pallas")
    )
    assert qops.resolve_impl(forced) == "pallas"


def test_linear_pallas_impl_via_config():
    """qops.linear honors BitNetConfig.impl (the serving engine's path)."""
    import dataclasses as dc

    from repro.models import qops

    cfg = get_smoke_config("falcon3-1b")
    cfg_p = dc.replace(cfg, bitnet=dc.replace(cfg.bitnet, impl="pallas"))
    leaf = bitlinear.quantize_pack(_random_linear(jax.random.PRNGKey(3), 64, 48))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 64))
    y_p = qops.linear(leaf, x, cfg_p, "packed")
    y_x = qops.linear(leaf, x, cfg, "packed")
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x),
                               rtol=1e-5, atol=1e-5)
