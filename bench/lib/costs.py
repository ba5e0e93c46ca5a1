"""Operations and bytes that each kernel's call needs, from the shapes and
dtypes of the packed leaves and the KV cache as the program serves them.

An operation is a multiply or an add, so a multiply-accumulate is two.
Bytes are what the call must move between HBM and the chip at the least:
each operand read once and each result written once. The least time of a
call is the larger of its operations at the chip's peak for their type
and its bytes at HBM bandwidth; a kernel's roofline share is the summed
least time of its calls over their summed device time.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence, Tuple

ACT_BYTES = 2  # bf16 activations into the ternary matmul
OUT_BYTES = 4  # the packed matmul returns float32


@dataclasses.dataclass(frozen=True)
class Projection:
    """One stacked packed projection as served: ``layers`` x (K, N). A
    model module (``bench/models/``) reads them from the served tree."""

    name: str
    layers: int
    k: int
    n: int
    weight_bytes: int  # packed words and scales of one layer


def ternary_call(m: int, p: Projection) -> Tuple[float, float]:
    """One layer's act-quant ternary matmul over ``m`` rows."""
    ops = 2.0 * m * p.k * p.n
    nbytes = p.weight_bytes + m * p.k * ACT_BYTES + m * p.n * OUT_BYTES
    return ops, float(nbytes)


def ternary_ops_per_token(projs: Iterable[Projection]) -> float:
    """Operations of one token through every layer of ``projs``: the
    projections that each token runs, as its model module counts them."""
    return sum(2.0 * p.k * p.n * p.layers for p in projs)


def attention_decode(lengths: Sequence[int], heads: int, kv_heads: int,
                     head_dim: int, kv_itemsize: int) -> Tuple[float, float]:
    """One layer of decode attention: each slot's query over its valid
    prefix of ``length`` tokens (the new token included)."""
    total = float(sum(lengths))
    ops = 4.0 * heads * head_dim * total
    kv = total * 2 * kv_heads * head_dim * kv_itemsize
    qo = len(lengths) * 2 * heads * head_dim * ACT_BYTES
    return ops, kv + qo


def attention_prefill(chunks: Sequence[Tuple[int, int]], heads: int,
                      kv_heads: int, head_dim: int,
                      kv_itemsize: int) -> Tuple[float, float]:
    """One layer of chunked-prefill attention: per slot, ``n`` new rows
    after ``offset`` cached tokens, causal within the chunk."""
    ops = nbytes = 0.0
    row = 2 * kv_heads * head_dim * kv_itemsize  # k and v of one token
    for offset, n in chunks:
        ops += 4.0 * heads * head_dim * (n * offset + n * (n + 1) / 2)
        # read the prefix and the chunk's k/v, write the chunk's k/v
        nbytes += (offset + n) * row + n * row
        nbytes += 2 * n * heads * head_dim * ACT_BYTES  # q in, o out
    return ops, nbytes


def lm_head(m: int, d: int, vocab: int, itemsize: int = 2) -> Tuple[float, float]:
    return 2.0 * m * d * vocab, float(d * vocab * itemsize
                                      + m * d * itemsize + m * vocab * 4)


def least(ops: float, nbytes: float, ops_per_s: float,
          bytes_per_s: float) -> float:
    """The least time of a call: its operations at the peak or its bytes
    at HBM bandwidth, whichever takes longer."""
    return max(ops / ops_per_s, nbytes / bytes_per_s)
