"""Pallas TPU kernel: packed-ternary matmul (the TriMLA/BiROMA analogue).

Structure mirrors the paper's local-then-global accumulation (§III-B):

  * the grid's K dimension streams packed trit tiles HBM -> VMEM;
  * each (bm, bn) output block keeps an int32 *local accumulator* in VMEM
    that is updated once per K tile (the TriMLA), never per input bit;
  * the final K step leaves the completed sum — one "global" result per
    block, the one-shot adder-tree pass.

Trits arrive packed (2 bits or base-243, see core/packing.py) and are
decoded *inside* VMEM, so HBM traffic is 0.25 (pack2) or 0.2 (pack243)
bytes per weight — the kernel-level expression of "weights never move".
The ternary MAC itself ({-1,0,+1} weights) rides the MXU int8 datapath:
values -1/0/+1 in int8 make the dot product exactly the add/sub/skip of
the TriMLA truth table (verified bit-exactly against ref.py).

Packed row r holds K rows g*r .. g*r+g-1, so the shift/mask (pack2) or
divmod-3 (pack243) decode of a (bk/g, bn) tile yields g *K-planes*, plane
i holding K row g*r+i. The known-scale kernels interleave the planes back
into a (bk, bn) tile in K order — a sublane relayout of every weight tile
on every grid step. The act-quant kernel skips it (*plane decode*): its
entry point reorders the activations once per call into plane order
within each K tile, and the kernel contracts plane i against the
lane-aligned slice i of its x tile, adding g int8 dots into the
accumulator. The sums are the same integers in another order, so the
output is bit-identical to a K-order decode; bk/g has to be a multiple
of 128 lanes (ops.py rounds block_k up to it).

Block shapes default to MXU-aligned (multiples of 128 on M/N, K tiles
sized so the packed rows stay lane-aligned). VMEM footprint per step:
  x tile (bm, bk) int8 + packed tile (bk/g, bn) uint8
  + decoded (bk, bn) int8 + acc (bm, bn) int32
e.g. bm=bn=256, bk=512 (pack2): 128K + 32K + 128K + 256K = 544 KiB << 16 MiB VMEM.

Three entry points:

  * ``ternary_matmul_pallas`` — raw int32 accumulator out (kept for the
    bit-exactness oracle tests and as the building block);
  * ``ternary_matmul_fused_pallas`` — the *known-scale* fast path: the same
    integer pipeline plus a *fused epilogue*. The int32 local accumulator
    lives in VMEM scratch; on the final K step it is rescaled in VMEM by
    the per-column weight scale and per-row activation scale and written
    out directly as f32/bf16. The (M, N) int32 accumulator never exists
    in HBM and the separate XLA rescale pass disappears — one kernel
    launch goes activations-int8 -> scaled float output. Per-column
    (rather than per-tensor) weight scales are what lets fused QKV /
    gate-up projections (models/pack.py::fuse_packed) ride the same
    kernel: each output segment keeps its own absmean scale.
  * ``ternary_matmul_actq_pallas`` — the production fast path: epilogue
    fusion PLUS a *fused act-quant prologue*. The kernel consumes RAW
    bf16/f32 activations; a two-phase grid first sweeps K accumulating the
    per-row absmax into VMEM scratch (phase 0), converts it to the int8
    scale on the last phase-0 step, then re-streams the K tiles and runs
    the quantized int8 x ternary accumulate (phase 1) with the epilogue
    rescale on its final step. The separate XLA act-quant pass — one HBM
    read of the bf16 activations plus a write AND re-read of the (M, K)
    int8 intermediate per projection — disappears entirely; the int8
    activations only ever exist in VMEM, mirroring BitROM's fully-fused
    CiROM datapath where the quantizer sits in front of the ROM read
    pipeline. A leading batch grid dimension makes the same kernel the
    E-loop *expert* kernel: one launch covers all E experts of an MoE
    layer (grid (E, gm, gn, 2, gk)) instead of E vmapped launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.ternary import EPS


def _planes2(wp: jax.Array) -> list:
    """(bk/4, bn) uint8 -> four (bk/4, bn) int8 trit planes (2-bit codes,
    LSB=+, MSB=-); plane i holds trit i of every byte, i.e. K row 4r+i of
    packed row r.

    The shifts, masks and the subtraction run in int32 and only the trits
    narrow to int8: Mosaic has no 8-bit vector shift or subtract."""
    v = wp.astype(jnp.int32)
    planes = []
    for i in range(packing.PACK2_GROUP):
        c = v >> (2 * i)
        planes.append(((c & 1) - ((c >> 1) & 1)).astype(jnp.int8))
    return planes


def _planes243(wp: jax.Array) -> list:
    """(bk/5, bn) uint8 -> five (bk/5, bn) int8 trit planes via repeated
    divmod-3, in int32 (Mosaic has no 16-bit vector remainder or
    division); plane i holds K row 5r+i of packed row r."""
    v = wp.astype(jnp.int32)
    planes = []
    for _ in range(packing.PACK243_GROUP):
        planes.append((v % 3 - 1).astype(jnp.int8))
        v = v // 3
    return planes


def _interleave(planes: list) -> jax.Array:
    """g (bk/g, bn) planes -> (bk, bn) trits in K order (row g*r+i from
    plane i): a sublane relayout of the whole tile."""
    stacked = jnp.stack(planes, axis=1)  # (bk/g, g, bn)
    return stacked.reshape(stacked.shape[0] * len(planes), stacked.shape[2])


def _decode2_block(wp: jax.Array) -> jax.Array:
    """(bk/4, bn) uint8 -> (bk, bn) int8 trits in K order."""
    return _interleave(_planes2(wp))


def _decode243_block(wp: jax.Array) -> jax.Array:
    """(bk/5, bn) uint8 -> (bk, bn) int8 trits in K order."""
    return _interleave(_planes243(wp))


def _kernel(x_ref, w_ref, o_ref, *, codec: str, k_steps: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    decode = _decode2_block if codec == "pack2" else _decode243_block
    trits = decode(w_ref[...])  # (bk, bn) int8 in {-1,0,+1}
    x = x_ref[...]  # (bm, bk) int8
    # TriMLA: {-1,0,+1} weights => signed add / skip; on MXU this is an
    # int8 x int8 -> int32 dot with trit operands.
    acc = jax.lax.dot_general(
        x,
        trits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("codec", "block_m", "block_n", "block_k", "interpret"),
)
def ternary_matmul_pallas(
    xq: jax.Array,
    packed: jax.Array,
    *,
    codec: str = "pack2",
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(M, K) int8 x packed (K/g, N) uint8 -> (M, N) int32.

    M, N, K must already be padded to block multiples (ops.py handles
    padding); block_k must be a multiple of the codec group (4 or 5).
    """
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    assert block_k % group == 0, (block_k, group)
    m, k = xq.shape
    kp, n = packed.shape
    assert kp * group == k, (kp, group, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (m, n, k)

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, codec=codec, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // group, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="ternary_matmul_pallas",
    )(xq, packed)


def _fused_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, codec: str,
                  k_steps: int):
    """Integer accumulate in VMEM scratch; rescale + emit on the last K step.

    xs_ref: (bm, 1) f32 per-row activation scale (act_quant convention:
            dequant = xq / scale, so the epilogue *divides* by it);
    ws_ref: (1, bn) f32 per-column weight scale (dequant = acc * scale).
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    decode = _decode2_block if codec == "pack2" else _decode243_block
    trits = decode(w_ref[...])  # (bk, bn) int8 in {-1,0,+1}
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        trits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        # y = acc * (w_scale / x_scale), computed entirely in VMEM: the
        # (M, N) int32 accumulator never round-trips through HBM.
        y = acc_ref[...].astype(jnp.float32) * (ws_ref[...] / xs_ref[...])
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("codec", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret"),
)
def ternary_matmul_fused_pallas(
    xq: jax.Array,
    packed: jax.Array,
    x_scale: jax.Array,
    col_scale: jax.Array,
    *,
    codec: str = "pack2",
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """(M, K) int8 x packed (K/g, N) uint8 -> (M, N) float, epilogue-fused.

    ``x_scale``: (M, 1) f32 per-row activation scale; ``col_scale``: (1, N)
    f32 per-column weight scale. Shapes must already be padded to block
    multiples (ops.py handles padding; padded x_scale rows must be nonzero).
    """
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    assert block_k % group == 0, (block_k, group)
    m, k = xq.shape
    kp, n = packed.shape
    assert kp * group == k, (kp, group, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (m, n, k)
    assert x_scale.shape == (m, 1), x_scale.shape
    assert col_scale.shape == (1, n), col_scale.shape

    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_fused_kernel, codec=codec, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k // group, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
        name="ternary_matmul_fused_pallas",
    )(xq, packed, x_scale.astype(jnp.float32), col_scale.astype(jnp.float32))


def _fused_batched_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *,
                          codec: str, k_steps: int):
    """Known-scale fused body on the E-loop grid (B, gm, gn, gk): the
    carried-scale twin of the two-phase expert kernel — same integer
    pipeline and epilogue as ``_fused_kernel``, leading batch dimension
    like ``_actq_kernel``, no absmax phase (the caller already owns the
    per-row scale)."""
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    decode = _decode2_block if codec == "pack2" else _decode243_block
    trits = decode(w_ref[0])  # (bk, bn) int8 in {-1,0,+1}
    acc_ref[...] += jax.lax.dot_general(
        x_ref[0],
        trits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * (ws_ref[0] / xs_ref[0])
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("codec", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret"),
)
def ternary_matmul_fused_batched_pallas(
    xq: jax.Array,
    packed: jax.Array,
    x_scale: jax.Array,
    col_scale: jax.Array,
    *,
    codec: str = "pack2",
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """(B, M, K) int8 x packed (B, K/g, N) uint8 -> (B, M, N) float.

    The *carried-scale* E-loop kernel: one launch covers every batch row
    (B = E experts) with epilogue fusion, taking already-quantized int8
    activations plus their per-row scale — the ``fuse_act_quant=False`` /
    ``QuantizedActivation`` form of ``ternary_matmul_actq_pallas``.
    ``x_scale``: (B, M, 1) f32; ``col_scale``: (B, 1, N) f32. Shapes must
    already be padded to block multiples (ops.py handles padding; padded
    x_scale rows must be nonzero).
    """
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    assert block_k % group == 0, (block_k, group)
    b, m, k = xq.shape
    bb, kp, n = packed.shape
    assert bb == b and kp * group == k, (bb, b, kp, group, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (m, n, k)
    assert x_scale.shape == (b, m, 1), x_scale.shape
    assert col_scale.shape == (b, 1, n), col_scale.shape

    grid = (b, m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_fused_batched_kernel, codec=codec, k_steps=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_m, block_k), lambda b, i, j, kk: (b, i, kk)),
            pl.BlockSpec((1, block_k // group, block_n),
                         lambda b, i, j, kk: (b, kk, j)),
            pl.BlockSpec((1, block_m, 1), lambda b, i, j, kk: (b, i, 0)),
            pl.BlockSpec((1, 1, block_n), lambda b, i, j, kk: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=interpret,
        name="ternary_matmul_fused_batched_pallas",
    )(xq, packed, x_scale.astype(jnp.float32), col_scale.astype(jnp.float32))


def _actq_kernel(x_ref, w_ref, ws_ref, o_ref, scale_ref, acc_ref, *,
                 codec: str, k_steps: int, qmax: float, qmin: float):
    """Two-phase body: absmax K-sweep (phase 0), quantized accumulate +
    epilogue (phase 1).

    Grid is (B, gm, gn, 2, gk); ``scale_ref`` is (bm, 1) f32 VMEM scratch
    that holds the running per-row absmax during phase 0 and the finished
    int8 scale (``qmax / max(absmax, EPS)`` — the exact ``act_quant``
    rule) from the last phase-0 step onward. Scratch persists across grid
    steps, so the absmax sweep runs ONCE per row tile — at j == 0 — and
    every later output-column tile j > 0 reuses the finished scale (its
    phase-0 steps are no-ops with the x BlockSpec parked, see the entry
    point). Quantization happens on the re-streamed raw tile in phase 1,
    so the int8 activations never exist outside VMEM. Zero-padded rows
    quantize to all-zero int8 rows (absmax 0 -> huge scale ->
    round(0 * scale) = 0), so no separate pad-scale repair is needed.

    The x tile's K axis arrives in plane order (see the entry point): the
    packed tile decodes into its g K-planes and each contracts against
    its own lane-aligned (bm, bk/g) slice of x, so the weight tile is
    never interleaved back into K order.
    """
    j = pl.program_id(2)
    p = pl.program_id(3)
    kk = pl.program_id(4)
    sweep = (p == 0) & (j == 0)

    @pl.when(sweep & (kk == 0))
    def _init_absmax():
        scale_ref[...] = jnp.zeros_like(scale_ref)

    @pl.when(sweep)
    def _absmax_sweep():
        x = x_ref[0].astype(jnp.float32)
        scale_ref[...] = jnp.maximum(
            scale_ref[...], jnp.max(jnp.abs(x), axis=1, keepdims=True)
        )

    @pl.when(sweep & (kk == k_steps - 1))
    def _finalize_scale():
        # act_quant convention: scale = qmax / max(absmax, EPS); dequant
        # divides by it, so the epilogue below divides too.
        scale_ref[...] = qmax / jnp.maximum(scale_ref[...], EPS)

    @pl.when((p == 1) & (kk == 0))
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p == 1)
    def _quantized_accumulate():
        split = _planes2 if codec == "pack2" else _planes243
        acc = acc_ref[...]
        for i, plane in enumerate(split(w_ref[0])):  # (bk/g, bn) int8
            kq = plane.shape[0]
            x = x_ref[0, :, i * kq:(i + 1) * kq].astype(jnp.float32)
            xq = jnp.clip(jnp.round(x * scale_ref[...]), qmin, qmax)
            acc += jax.lax.dot_general(
                xq.astype(jnp.int8),
                plane,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        acc_ref[...] = acc

    @pl.when((p == 1) & (kk == k_steps - 1))
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * (ws_ref[0] / scale_ref[...])
        o_ref[0] = y.astype(o_ref.dtype)


def _plane_order(x: jax.Array, block_k: int, group: int) -> jax.Array:
    """(B, M, K) -> the same columns in plane order within each block_k
    tile: ``out[..., t*bk + i*(bk/g) + r] = x[..., t*bk + g*r + i]``, so
    slice i of a tile meets K-plane i of its packed tile. The absmax, the
    zero padding and the int32 sums are the same in any K order."""
    # Without the barrier XLA fuses the reorder into the ops that produce
    # x (the residual add and the RMSNorm) and the served logits drift
    # from the K-order decode's (a v5e at M = 1536). One cause is excess
    # precision in that fusion: it recomputes the residual sum in float32
    # and skips its bf16 rounding. With --xla_allow_excess_precision=false
    # that rounding returns but the drift does not go away. The barrier
    # keeps the producers compiled as they are without the reorder.
    x = jax.lax.optimization_barrier(x)
    b, m, k = x.shape
    tiles = x.reshape(b, m, k // block_k, block_k // group, group)
    return jnp.swapaxes(tiles, -1, -2).reshape(b, m, k)


@functools.partial(
    jax.jit,
    static_argnames=("codec", "act_bits", "block_m", "block_n", "block_k",
                     "out_dtype", "interpret"),
)
def ternary_matmul_actq_pallas(
    x: jax.Array,
    packed: jax.Array,
    col_scale: jax.Array,
    *,
    codec: str = "pack2",
    act_bits: int = 8,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    """(B, M, K) raw float x packed (B, K/g, N) uint8 -> (B, M, N) float.

    Act-quant-prologue + epilogue fused (see module docstring). ``x`` is the
    RAW bf16/f32 activation (already zero-padded to block multiples —
    ops.py handles padding); ``col_scale`` is (B, 1, N) f32 per-column
    weight scale. B = 1 for ordinary projections; B = E runs the E-loop
    expert grid (one launch over all experts, each with its own packed
    weights and column scales).

    block_k / g must be a multiple of 128, so that each K-plane's slice of
    the x tile is whole lane tiles (``_plane_order``).
    """
    group = packing.PACK2_GROUP if codec == "pack2" else packing.PACK243_GROUP
    assert block_k % (group * 128) == 0, (block_k, group)
    b, m, k = x.shape
    bb, kp, n = packed.shape
    assert bb == b and kp * group == k, (bb, b, kp, group, k)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (m, n, k)
    assert col_scale.shape == (b, 1, n), col_scale.shape
    if act_bits == 8:
        qmax, qmin = 127.0, -128.0
    elif act_bits == 4:
        qmax, qmin = 7.0, -8.0
    else:  # mirror act_quant so pallas and xla reject identically
        raise ValueError(f"unsupported activation bits: {act_bits}")

    x = _plane_order(x, block_k, group)
    grid = (b, m // block_m, n // block_n, 2, k // block_k)
    return pl.pallas_call(
        functools.partial(_actq_kernel, codec=codec, k_steps=grid[4],
                          qmax=qmax, qmin=qmin),
        grid=grid,
        in_specs=[
            # x streams its K blocks only when the step does real work:
            # phase 1 (quantized accumulate) and the single absmax sweep
            # (phase 0 at j == 0). All other phase-0 steps park on block
            # (b, i, 0) — the pipeline elides copies when consecutive
            # steps map to the same block — so the raw activations cross
            # HBM gn+1 times, not 2*gn.
            pl.BlockSpec(
                (1, block_m, block_k),
                lambda b, i, j, p, kk: (
                    b, i, jnp.where((p == 1) | (j == 0), kk, 0)
                ),
            ),
            # same trick for the packed weights, parked during ALL of
            # phase 0: the trits stream through HBM once (phase 1), not
            # twice — the absmax sweep only ever reads x.
            pl.BlockSpec((1, block_k // group, block_n),
                         lambda b, i, j, p, kk: (b, kk * p, j)),
            pl.BlockSpec((1, 1, block_n), lambda b, i, j, p, kk: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda b, i, j, p, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m, n), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, 1), jnp.float32),
            pltpu.VMEM((block_m, block_n), jnp.int32),
        ],
        interpret=interpret,
        name="ternary_matmul_actq_pallas",
    )(x, packed, col_scale.astype(jnp.float32))


# ---------------------------------------------------------------------------
# ABFT weight checksums (serving SDC detection — docs/kernels.md)
# ---------------------------------------------------------------------------


def abft_wsum(packed: jax.Array, k: int, codec: str,
              scale: jax.Array) -> jax.Array:
    """Scale-weighted per-row (contraction-axis) ABFT checksum vector.

    For a packed ternary weight ``W`` of logical shape (K, N) with
    per-column scale ``s`` (a scalar broadcasts), returns the (K,)
    float32 vector ``wsum[k] = sum_n trit[k, n] * s[n]``. Leading stack
    dims (layer scan, experts) are vmapped through.

    This is the classic algorithm-based fault-tolerance column checksum
    specialized to the ternary pipeline: because
    ``y = (x_q @ trits) * s / x_scale``, the predicted output row-sum is
    ``sum_n y[r, n] = (x_q[r, :] @ wsum) / x_scale[r]`` — one GEMV per
    check, a factor-N cheaper than the matmul it guards. A flipped trit
    at row ``k`` shifts the prediction by ``±x_q[r, k] * s`` (±2 for a
    −1↔+1 flip), so any activation with a nonzero quant at that row
    exposes the fault; rows where every activation quantizes to zero are
    the checksum's blind spot, covered by the exact crc scrub
    (``core/packing.packed_crc32``).

    Computed once at pack time (models/pack.py) from the SAME packed
    words the kernels decode, so a post-pack flip is a disagreement
    between checksum and weight — exactly what the check detects.
    """
    unpack = packing.unpack2 if codec == "pack2" else packing.unpack243

    def one(p2, s):
        trits = unpack(p2)[:k].astype(jnp.float32)
        sv = jnp.asarray(s, jnp.float32)
        if sv.ndim == 0:
            return jnp.sum(trits, axis=-1) * sv
        return trits @ sv

    fn = one
    for _ in range(packed.ndim - 2):
        fn = jax.vmap(fn)
    return fn(packed, scale)
