"""The ops and bytes functions against hand counts at falcon3-1b's shapes
(d 2048, GQA 8/4 x 256, FFN 8192), the Llama module's reading of the
served projections, and the peaks table's refusal of an unknown chip.

  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

sys.path[:0] = [str(Path(__file__).resolve().parents[2])]

from bench.lib import costs  # noqa: E402
from bench.lib.peaks import peaks  # noqa: E402
from bench.models import llama_bitnet  # noqa: E402

WQKV = costs.Projection("attn.wqkv", 18, 2048, 4096,
                        2048 // 4 * 4096 + 4 * 4096)


def test_ternary_call_by_hand():
    ops, nbytes = costs.ternary_call(32, WQKV)
    assert ops == 2 * 32 * 2048 * 4096 == 536_870_912
    # packed trits 2_097_152 + scales 16_384 + bf16 x 131_072 + f32 out 524_288
    assert nbytes == 2_768_896


def test_projections_read_the_served_leaves():
    leaf = SimpleNamespace(packed=np.zeros((18, 512, 4096), np.uint8),
                           scale=np.zeros((18, 4096), np.float32), k=2048)
    tree = {"blocks": {"attn": {"wqkv": leaf, "ln": np.zeros((18, 2048))}}}
    assert llama_bitnet.projections(tree) == [WQKV]
    assert costs.ternary_ops_per_token([WQKV]) == 2 * 2048 * 4096 * 18


def test_attention_decode_by_hand():
    ops, nbytes = costs.attention_decode([1000, 24], 8, 4, 256, 2)
    assert ops == 4 * 8 * 256 * 1024 == 8_388_608
    # k and v of 1024 tokens (4_194_304) + q and o of two slots (16_384)
    assert nbytes == 4_210_688


def test_attention_prefill_by_hand():
    ops, nbytes = costs.attention_prefill([(256, 128)], 8, 4, 256, 2)
    assert ops == 4 * 8 * 256 * (128 * 256 + 128 * 129 / 2) == 336_068_608
    # read 384 tokens' k/v (1_572_864), write 128 (524_288), q and o
    # of 128 rows (1_048_576)
    assert nbytes == 3_145_728


def test_lm_head_and_least_time():
    ops, nbytes = costs.lm_head(4, 2048, 131072)
    assert ops == 2 * 4 * 2048 * 131072
    assert nbytes == 2048 * 131072 * 2 + 4 * 2048 * 2 + 4 * 131072 * 4
    p = peaks("TPU v5 lite")
    assert costs.least(393e12, 0.0, p["int8_ops_per_s"],
                       p["hbm_bytes_per_s"]) == pytest.approx(1.0)
    assert costs.least(0.0, 819e9, p["int8_ops_per_s"],
                       p["hbm_bytes_per_s"]) == pytest.approx(1.0)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
