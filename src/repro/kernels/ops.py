"""Jit'd dispatch wrappers around the ternary kernels.

``impl`` selects the execution path:
  * "pallas" — the Pallas TPU kernel (interpret=True automatically on CPU,
    executing the kernel body in Python for correctness validation);
  * "xla"    — unpack-then-dot in plain XLA. Used for the sharded
    multi-pod lowering (dry-run) where a hand-written kernel would block
    GSPMD propagation; keeps the same packed HBM layout so the memory
    roofline term is identical.

Handles arbitrary leading batch dims and non-aligned M/N/K by zero padding
(zero trits are TriMLA skip-ops; zero activations contribute nothing).

Shape-aware block selection
---------------------------
When the caller does not pin block sizes, ``select_blocks`` picks them from
a static table keyed on (M, N, K). The two regimes it distinguishes:

  * decode (M <= 32, continuous-batching GEMV-ish shapes) — block_m = 32
    (the int8 sublane tile) instead of padding the batch up to 256, a 8x
    cut in streamed/accumulated M rows; block_n widens to 512 and block_k
    to 1024 so each launch amortizes the in-VMEM trit decode and the x
    tile reload across more output columns / contraction depth;
  * prefill / train (large M) — classic MXU-aligned 256/256/512 blocks.

    M range   | block_m | block_n | block_k
    ----------|---------|---------|--------
    1..32     |   32    |   512   |  1024      (decode fast path)
    33..64    |   64    |   256   |   512
    65..128   |  128    |   256   |   512
    129..     |  256    |   256   |   512      (prefill/train)

(under pack243, block_k snaps to multiples of 640 = lcm(5 trits/byte,
128 lanes) so both the x tile and the packed tile stay lane-aligned)

block_n / block_k are additionally capped by the (padded) N / K of the
operand and block_k is aligned down to the codec group (4 or 5 trits per
byte).

Fused epilogue / fused act-quant prologue
-----------------------------------------
``ternary_matmul_fused`` is the *known-scale* entry point: it takes already
int8-quantized activations with their per-row scale and the per-column
weight scale and returns the *scaled float* output in one kernel launch
(Pallas) or one dot + one elementwise rescale (XLA fallback, numerically
identical ops to the historical unfused path). The per-column weight scale
is what makes fused QKV / gate-up projections (one launch for wq‖wk‖wv)
exact: each segment keeps its own absmean scale.

``ternary_matmul_actq`` is the production entry point
(core/bitlinear.packed_matmul): it takes the RAW bf16/f32 activations and
fuses the int8 act-quant (per-row absmax + scale) into the kernel prologue
via the two-phase grid, so neither the int8 activations nor the int32
accumulator ever exist in HBM. ``ternary_matmul_expert`` is its E-loop
variant for expert-batched MoE weights (E, K/g, N): ONE launch with a
leading expert grid dimension replaces E vmapped per-expert launches
(which were impossible on the Pallas path anyway — ``pallas_call`` has no
batching rule on this jax version, so the vmapped path was pinned to XLA).
``ternary_matmul_expert_fused`` is the *carried-scale* E-loop form: when
the activations arrive pre-quantized (``fuse_act_quant=False`` / a
``QuantizedActivation`` producer), experts still run as one launch via
the batched known-scale kernel instead of falling back to the vmapped
XLA path. Both act-quant entry points round block_k up to whole plane
tiles (``_actq_blocks``): the kernel decodes each packed tile into its g
K-planes and contracts each against a lane-aligned slice of the x tile
(kernels/ternary_matmul.py).

``select_blocks(kind="decode_attn")`` serves a different grid entirely:
the flash-decode attention kernel (kernels/flash_decode.py) keys its
S-block size off the same static-table machinery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.kernels.ternary_matmul import (
    ternary_matmul_actq_pallas,
    ternary_matmul_fused_batched_pallas,
    ternary_matmul_fused_pallas,
    ternary_matmul_pallas,
)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Static block tables, keyed by grid kind: (max_m, block_m, block_n, block_k).
#
#   * "fused"  — the known-scale int8 grids (raw + epilogue-fused kernels);
#     see the module doc for the decode/prefill regime rationale.
#   * "actq"   — the two-phase act-quant-prologue grid. The x tile streams
#     RAW bf16/f32 (2-4 bytes/elem instead of int8) and is read twice
#     (absmax sweep + quantized accumulate), so the decode row halves
#     block_k (1024 -> 512) to keep the double-buffered VMEM footprint at
#     the known-scale level; prefill tiers keep the MXU-aligned 256/256/512.
#   * "expert" — the E-loop grid. Identical per-step footprint, but the
#     leading E dimension multiplies the number of streamed weight tiles,
#     so the decode row narrows block_n (512 -> 256) to shorten each
#     expert's pipeline ramp (capacity C is usually small: C ~ tokens *
#     top_k / E, frequently < 32 rows per expert at decode).
_BLOCK_TABLES = {
    "fused": (
        (32, 32, 512, 1024),
        (64, 64, 256, 512),
        (128, 128, 256, 512),
        (None, 256, 256, 512),
    ),
    "actq": (
        (32, 32, 512, 512),
        (64, 64, 256, 512),
        (128, 128, 256, 512),
        (None, 256, 256, 512),
    ),
    "expert": (
        (32, 32, 256, 512),
        (64, 64, 256, 512),
        (128, 128, 256, 512),
        (None, 256, 256, 512),
    ),
    # decode_attn keys on the flash-decode grid (kernels/flash_decode.py):
    # M = q rows per kv group (GQA rep, or all h heads for the MLA latent
    # form), N = the head/latent lane width, K = cache *capacity*, and the
    # returned block_k is the S-block the kernel streams per grid step.
    # GQA rows (rep <= 16): S = 256 — a (256, 128) bf16 KV tile pair is
    # ~128 KiB double-buffered, and wider S amortizes each tile's copy
    # across more softmax columns. The MLA row halves S: the latent tile
    # is ~4.5x wider (576 lanes) and the (h, value_dim) f32 accumulator
    # already holds ~256 KiB of VMEM.
    "decode_attn": (
        (16, 16, 128, 256),
        (None, 128, 128, 128),
    ),
    # prefill_attn keys on the flash-prefill grid (kernels/flash_prefill.py):
    # M = GQA rep when rep > 1, else the head count (MLA / plain MHA —
    # the same convention as decode_attn's latent form), N = the head
    # lane width, K = the chunk / prompt length. block_m is the Q-BLOCK
    # in *tokens* (the kernel folds rep query heads into each token row,
    # so a q tile is (block_m * rep, head)), block_k the streamed KV
    # S-block. GQA (rep <= 16): 128-token q blocks against 256-token kv
    # blocks keep the f32 (bq*rep, dv) accumulator + double-buffered
    # tiles within VMEM; many-head rep-1 forms (MLA's ~192-lane heads,
    # 128 of them) halve both — per-head grid rows keep each tile small,
    # but the wider lanes double every streamed k/v copy.
    "prefill_attn": (
        (16, 128, 128, 256),
        (None, 64, 128, 128),
    ),
}


def select_blocks(m: int, n: int, k: int, codec: str, kind: str = "fused") -> tuple:
    """(M, N, K) -> (block_m, block_n, block_k) from the static table.

    ``kind`` picks the grid's table: "fused" (known-scale int8 grids),
    "actq" (two-phase act-quant prologue), "expert" (E-loop MoE grid),
    "decode_attn" (flash-decode S blocks; M/N/K are the q rows per kv
    group, head width and cache capacity — block_k is the S-block) or
    "prefill_attn" (flash-prefill; M/N/K are the q rows per token and kv
    group, head width and chunk length — block_m is the q block in
    tokens, block_k the S-block) — see the table comment for how the
    rows differ. The matmul kinds cap block_n / block_k at the padded
    operand extent and align block_k to the codec group so a block never
    spans a partial packed byte. For pack243 the group (5) is coprime
    with the 128-lane tile, so block_k additionally snaps to multiples
    of lcm(5, 128) = 640 whenever K allows — otherwise the (bm, bk) x
    tile and (bk/5, bn) packed tile would be lane-misaligned on real TPU
    (interpret mode doesn't care, Mosaic does). The attention kinds have
    no packed operand, so ``codec`` is ignored and block_k caps at the
    capacity / chunk length directly (the flash kernels handle partial
    S-blocks by masking).
    """
    for max_m, bm, bn, bk in _BLOCK_TABLES[kind]:
        if max_m is None or m <= max_m:
            break
    if kind in ("decode_attn", "prefill_attn"):
        bn = min(bn, _round_up(max(n, 1), 128))
        if kind == "prefill_attn":
            bm = min(bm, max(k, 1))
        return bm, bn, min(bk, max(k, 1))
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    bn = min(bn, _round_up(max(n, 1), 128))
    kp = _round_up(max(k, 1), group)
    bk = min(bk, kp)
    if codec == "pack243" and kp >= 640:
        bk = max(640, bk // 640 * 640)
    else:
        bk = max(group, bk // group * group)
    return bm, bn, bk


def default_page_size(rep: int, d: int, capacity: int) -> int:
    """Page size for the paged KV cache (core/kv_cache.PagedKVCache).

    One page = one flash S-block: the paged cold tier streams through the
    attention kernels with the page table as BlockSpec gather indices, so
    sizing pages off the ``decode_attn`` row keeps the paged launch's
    block geometry identical to the contiguous one — the indirection adds
    an index lookup, never a different tiling. ``rep``/``d`` follow the
    ``select_blocks`` decode-attn convention (q rows per kv group, head
    width); ``capacity`` caps the page at the cold tier's size.
    """
    return select_blocks(rep, d, max(capacity, 1), "pack2",
                         kind="decode_attn")[2]


def _xla_path(xq: jax.Array, packed: jax.Array, k: int, codec: str) -> jax.Array:
    unpack = packing.unpack2 if codec == "pack2" else packing.unpack243
    wq = unpack(packed, k=k)  # (K, N) int8
    return jax.lax.dot_general(
        xq.astype(jnp.int8),
        wq,
        dimension_numbers=(((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _pad_operands(xq, packed, codec, block_m, block_n, block_k):
    """Flatten leading dims and zero-pad to block multiples.

    Returns (x2 (Mp, Kp) int8, wp (Kp/g, Np) uint8, lead shape, m, n).
    Padding is computation-neutral: zero activation rows/columns contribute
    nothing, and padded *weight* bytes are repaired to the all-zero-trit
    code where the byte encoding requires it (pack243's zero code is 121,
    not 0x00 — note the parenthesization below: the repair is only ever
    needed for pack243, for *either* K-row or N-column padding; pack2's
    zero code is 0x00, which jnp.pad already produces).
    """
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    lead = xq.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = xq.reshape(m, xq.shape[-1])

    n = packed.shape[1]
    kp_logical = packed.shape[0] * group  # K padded to group already
    mp = _round_up(max(m, 1), block_m)
    np_ = _round_up(n, block_n)
    kpp = _round_up(kp_logical, block_k)
    x2 = jnp.pad(
        x2, ((0, mp - m), (0, kpp - xq.shape[-1]))
    )  # pad K with zero activations
    wp = _pad_packed(packed, kpp // group, np_, codec)
    return x2, wp, lead, m, n


def _pad_packed(packed, rows: int, cols: int, codec: str):
    """Zero-pad a packed array to (…, rows, cols) and repair the padding
    to the codec's all-zero-trit code.

    byte 0 decodes to trits (-1,-1,-1,-1,-1) under pack243; rewrite padded
    bytes to the all-zero-trit code 121 = sum((0+1) * 3^i). The repair is
    only ever needed for pack243, for *either* K-row or N-column padding;
    pack2's zero code is 0x00, which jnp.pad already produces. Works for
    2-D (K/g, N) and expert-stacked 3-D (E, K/g, N) packed arrays (leading
    dims pass through; the repair mask broadcasts over them).
    """
    valid_rows, valid_cols = packed.shape[-2], packed.shape[-1]
    pad = ((0, 0),) * (packed.ndim - 2) + (
        (0, rows - valid_rows), (0, cols - valid_cols))
    wp = jnp.pad(packed, pad)
    if codec != "pack243" or (rows == valid_rows and cols == valid_cols):
        return wp
    mask_r = jnp.arange(rows) >= valid_rows
    mask_c = jnp.arange(cols) >= valid_cols
    mask = mask_r[:, None] | mask_c[None, :]
    return jnp.where(mask, jnp.uint8(121), wp)


def _resolve_blocks(m, n, k, codec, block_m, block_n, block_k, kind="fused"):
    auto = select_blocks(m, n, k, codec, kind=kind)
    bm = block_m if block_m is not None else auto[0]
    bn = block_n if block_n is not None else auto[1]
    bk = block_k if block_k is not None else auto[2]
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    bk = max(group, bk // group * group)  # align block to codec group
    bk = min(bk, _round_up(k, group))  # don't exceed (padded) K
    return bm, bn, bk


@functools.partial(
    jax.jit, static_argnames=("k", "codec", "impl", "block_m", "block_n", "block_k")
)
def ternary_matmul(
    xq: jax.Array,
    packed: jax.Array,
    *,
    k: int,
    codec: str = "pack2",
    impl: str = "xla",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """int8 activations (..., K) x packed trits -> int32 (..., N).

    Block sizes default to the shape-aware table (``select_blocks``).
    """
    if impl == "xla":
        return _xla_path(xq, packed, k, codec)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    m = 1
    for d in xq.shape[:-1]:
        m *= d
    bm, bn, bk = _resolve_blocks(
        m, packed.shape[1], packed.shape[0] * group, codec, block_m, block_n, block_k
    )
    x2, wp, lead, m, n = _pad_operands(xq, packed, codec, bm, bn, bk)

    interpret = jax.default_backend() == "cpu"
    out = ternary_matmul_pallas(
        x2, wp, codec=codec, block_m=bm, block_n=bn, block_k=bk,
        interpret=interpret,
    )
    return out[:m, :n].reshape(lead + (n,))


@functools.partial(
    jax.jit,
    static_argnames=("k", "codec", "impl", "out_dtype",
                     "block_m", "block_n", "block_k"),
)
def ternary_matmul_fused(
    xq: jax.Array,
    packed: jax.Array,
    x_scale: jax.Array,
    col_scale: jax.Array,
    *,
    k: int,
    codec: str = "pack2",
    impl: str = "pallas",
    out_dtype=jnp.float32,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Epilogue-fused ternary matmul: int8 x packed -> scaled float (..., N).

    ``x_scale``: (..., 1) f32 per-row activation scale (act_quant
    convention, dequant = xq / scale); ``col_scale``: (N,) f32 per-column
    weight scale. Returns ``(xq @ trits) * col_scale / x_scale`` without
    materializing the (M, N) int32 accumulator in HBM on the Pallas path.
    """
    n = packed.shape[1]
    if impl == "xla":
        acc = _xla_path(xq, packed, k, codec)
        y = acc.astype(jnp.float32) * (col_scale / x_scale)
        return y.astype(out_dtype)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    m = 1
    for d in xq.shape[:-1]:
        m *= d
    bm, bn, bk = _resolve_blocks(
        m, n, packed.shape[0] * group, codec, block_m, block_n, block_k
    )
    x2, wp, lead, m, n = _pad_operands(xq, packed, codec, bm, bn, bk)
    mp, np_ = x2.shape[0], wp.shape[1]
    # padded rows divide by 1 (not 0); padded columns scale to exactly 0
    xs = jnp.pad(
        x_scale.reshape(m, 1).astype(jnp.float32), ((0, mp - m), (0, 0)),
        constant_values=1.0,
    )
    ws = jnp.pad(
        col_scale.reshape(1, n).astype(jnp.float32), ((0, 0), (0, np_ - n))
    )

    interpret = jax.default_backend() == "cpu"
    out = ternary_matmul_fused_pallas(
        x2, wp, xs, ws, codec=codec, block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype, interpret=interpret,
    )
    return out[:m, :n].reshape(lead + (n,))


@functools.partial(
    jax.jit,
    static_argnames=("k", "codec", "impl", "atol", "eps_factor"),
)
def ternary_matmul_abft(
    xq: jax.Array,
    packed: jax.Array,
    x_scale: jax.Array,
    col_scale: jax.Array,
    wsum: jax.Array,
    *,
    k: int,
    codec: str = "pack2",
    impl: str = "xla",
    atol: float = 1e-4,
    eps_factor: float = 64.0,
):
    """Epilogue-fused ternary matmul PLUS the ABFT row-sum check, one
    jitted dispatch (docs/kernels.md "ABFT checksums").

    ``wsum`` is the pack-time scale-weighted column checksum
    (``ternary_matmul.abft_wsum``); the predicted output row-sum is the
    GEMV ``(xq @ wsum) / x_scale`` — factor-N cheaper than the matmul it
    guards. Returns ``(y, residual, tol)``: a sound result has
    ``residual <= tol`` everywhere, where ``tol = atol + eps_factor *
    eps_f32 * mag`` bounds the f32 reassociation error of the two sums
    by their positive-term magnitude ``mag``. A flipped trit at row k
    shifts the row-sum by ``±|xq[r, k]| * scale`` — outside ``tol``
    whenever the row's activation quant at k is nonzero (zero-quant rows
    are the blind spot the exact crc scrub covers).
    """
    y = ternary_matmul_fused(
        xq, packed, x_scale, col_scale, k=k, codec=codec, impl=impl)
    xqf = xq.astype(jnp.float32)
    xs = x_scale[..., 0]
    wsum = wsum.astype(jnp.float32)
    pred = (xqf @ wsum) / xs
    residual = jnp.abs(jnp.sum(y, axis=-1) - pred)
    mag = ((jnp.abs(xqf) @ jnp.abs(wsum)) / jnp.abs(xs)
           + jnp.sum(jnp.abs(y), axis=-1))
    tol = atol + eps_factor * jnp.finfo(jnp.float32).eps * mag
    return y, residual, tol


def _actq_blocks(m, n, k, codec, block_m, block_n, block_k, kind):
    """``_resolve_blocks`` for the act-quant kernel, with block_k rounded
    up to whole plane tiles: the kernel contracts each of a packed tile's
    g K-planes against its own (bm, bk/g) slice of x, which has to span
    whole 128-lane tiles (bk a multiple of 512 for pack2, 640 for
    pack243). The served blocks already are; zero K padding is exact."""
    bm, bn, bk = _resolve_blocks(m, n, k, codec, block_m, block_n, block_k,
                                 kind=kind)
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    return bm, bn, _round_up(bk, group * 128)


def _actq_xla(x, packed, col_scale, k, codec, act_bits, out_dtype):
    """Quantize-then-matmul reference path: separate act-quant + dot +
    rescale, numerically identical ops to the fused prologue."""
    from repro.core.ternary import act_quant

    q = act_quant(x, bits=act_bits)
    acc = _xla_path(q.xq, packed, k, codec)
    y = acc.astype(jnp.float32) * (col_scale / q.scale)
    return y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("k", "codec", "act_bits", "impl", "out_dtype",
                     "block_m", "block_n", "block_k"),
)
def ternary_matmul_actq(
    x: jax.Array,
    packed: jax.Array,
    col_scale: jax.Array,
    *,
    k: int,
    codec: str = "pack2",
    act_bits: int = 8,
    impl: str = "pallas",
    out_dtype=jnp.float32,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Act-quant-prologue-fused ternary matmul: RAW float (..., K) -> (..., N).

    The production fast path: per-row absmax int8 quantization happens in
    the kernel prologue (two-phase grid, see ternary_matmul.py), so no
    (M, K) int8 intermediate and no (M, N) int32 accumulator ever touch
    HBM. ``col_scale``: (N,) f32 per-column weight scale. The XLA fallback
    runs the separate quantize-then-matmul pipeline with numerically
    identical ops.
    """
    n = packed.shape[1]
    if impl == "xla":
        return _actq_xla(x, packed, col_scale, k, codec, act_bits, out_dtype)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    m = 1
    for d in x.shape[:-1]:
        m *= d
    bm, bn, bk = _actq_blocks(
        m, n, packed.shape[0] * group, codec, block_m, block_n, block_k,
        kind="actq",
    )
    x2, wp, lead, m, n = _pad_operands(x, packed, codec, bm, bn, bk)
    ws = jnp.pad(
        col_scale.reshape(1, n).astype(jnp.float32),
        ((0, 0), (0, wp.shape[1] - n)),
    )

    interpret = jax.default_backend() == "cpu"
    out = ternary_matmul_actq_pallas(
        x2[None], wp[None], ws[None], codec=codec, act_bits=act_bits,
        block_m=bm, block_n=bn, block_k=bk, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[0, :m, :n].reshape(lead + (n,))


@functools.partial(
    jax.jit,
    static_argnames=("k", "codec", "act_bits", "impl", "out_dtype",
                     "block_m", "block_n", "block_k"),
)
def ternary_matmul_expert(
    x: jax.Array,
    packed: jax.Array,
    col_scale: jax.Array,
    *,
    k: int,
    codec: str = "pack2",
    act_bits: int = 8,
    impl: str = "pallas",
    out_dtype=jnp.float32,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """E-loop expert matmul: raw (E, C, K) float x packed (E, K/g, N) ->
    (E, C, N) float, act-quant prologue + epilogue fused.

    ONE kernel launch covers every expert (leading expert grid dimension)
    instead of E vmapped per-expert launches — the ``pallas_call`` batching
    rule the vmapped path lacked. ``col_scale``: (E, N) f32 per-column
    weight scale (an expert's scalar absmean repeated, or per-segment
    scales for pack-time-fused gate‖up). The XLA fallback vmaps the
    separate quantize-then-matmul pipeline per expert.
    """
    e, c, _ = x.shape
    ep, kp, n = packed.shape
    assert ep == e, (ep, e)
    assert col_scale.shape == (e, n), (col_scale.shape, e, n)
    if impl == "xla":
        return jax.vmap(
            lambda xx, pp, ss: _actq_xla(xx, pp, ss, k, codec, act_bits,
                                         out_dtype)
        )(x, packed, col_scale)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    bm, bn, bk = _actq_blocks(
        c, n, kp * group, codec, block_m, block_n, block_k, kind="expert"
    )
    mp = _round_up(max(c, 1), bm)
    np_ = _round_up(n, bn)
    kpp = _round_up(kp * group, bk)
    x2 = jnp.pad(x, ((0, 0), (0, mp - c), (0, kpp - x.shape[-1])))
    wp = _pad_packed(packed, kpp // group, np_, codec)
    ws = jnp.pad(
        col_scale.astype(jnp.float32), ((0, 0), (0, np_ - n))
    )[:, None, :]

    interpret = jax.default_backend() == "cpu"
    out = ternary_matmul_actq_pallas(
        x2, wp, ws, codec=codec, act_bits=act_bits,
        block_m=bm, block_n=bn, block_k=bk, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:, :c, :n]


@functools.partial(
    jax.jit,
    static_argnames=("k", "codec", "impl", "out_dtype",
                     "block_m", "block_n", "block_k"),
)
def ternary_matmul_expert_fused(
    xq: jax.Array,
    packed: jax.Array,
    x_scale: jax.Array,
    col_scale: jax.Array,
    *,
    k: int,
    codec: str = "pack2",
    impl: str = "pallas",
    out_dtype=jnp.float32,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """Carried-scale E-loop expert matmul: int8 (E, C, K) x packed
    (E, K/g, N) -> (E, C, N) float, epilogue fused.

    The ``fuse_act_quant=False`` / ``QuantizedActivation`` twin of
    ``ternary_matmul_expert``: the caller already quantized the
    activations (``x_scale``: (E, C, 1) f32 per-row scale), so the kernel
    skips the absmax phase and still covers every expert in ONE launch.
    ``col_scale``: (E, N) f32 per-column weight scale. The XLA fallback
    vmaps the unpack-dot + rescale per expert (numerically identical
    ops — bit-exact against the kernel).
    """
    e, c, _ = xq.shape
    ep, kp, n = packed.shape
    assert ep == e, (ep, e)
    assert x_scale.shape == (e, c, 1), (x_scale.shape, e, c)
    assert col_scale.shape == (e, n), (col_scale.shape, e, n)
    if impl == "xla":
        acc = jax.vmap(lambda xx, pp: _xla_path(xx, pp, k, codec))(xq, packed)
        y = acc.astype(jnp.float32) * (
            col_scale[:, None, :] / x_scale.astype(jnp.float32)
        )
        return y.astype(out_dtype)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    bm, bn, bk = _resolve_blocks(
        c, n, kp * group, codec, block_m, block_n, block_k, kind="expert"
    )
    mp = _round_up(max(c, 1), bm)
    np_ = _round_up(n, bn)
    kpp = _round_up(kp * group, bk)
    x2 = jnp.pad(xq, ((0, 0), (0, mp - c), (0, kpp - xq.shape[-1])))
    wp = _pad_packed(packed, kpp // group, np_, codec)
    # padded rows divide by 1 (not 0); padded columns scale to exactly 0
    xs = jnp.pad(
        x_scale.astype(jnp.float32), ((0, 0), (0, mp - c), (0, 0)),
        constant_values=1.0,
    )
    ws = jnp.pad(
        col_scale.astype(jnp.float32), ((0, 0), (0, np_ - n))
    )[:, None, :]

    interpret = jax.default_backend() == "cpu"
    out = ternary_matmul_fused_batched_pallas(
        x2, wp, xs, ws, codec=codec,
        block_m=bm, block_n=bn, block_k=bk, out_dtype=out_dtype,
        interpret=interpret,
    )
    return out[:, :c, :n]
