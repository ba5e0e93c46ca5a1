"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader gets the :class:`~bench.lib.harness.Run` of one run and returns
a number, or None where the run holds nothing to read (a host metric in a
cell with no such requests, a trace metric in an untraced run, a kernel
that never ran in the traced window).
"""

from __future__ import annotations

from typing import Optional

from bench.lib import costs

# what the trace calls each jitted program of the engine and each kernel
PROGRAMS = {"decode": "step", "chunk": "chunk_step"}
KERNELS = {
    "ternary_matmul": ("ternary_matmul_actq_pallas",),
    "flash_decode": ("flash_decode_attention",),
    "flash_prefill": ("flash_prefill_attention",),
}


def rate(run, what: str) -> Optional[float]:
    w = run.window
    if "start_counts" not in w:
        return None
    span = w["close"] - w["open"]
    got = w["end_counts"][what] - w["start_counts"][what]
    return got / span if span > 0 and got > 0 else None


def program_ms(run, program: str) -> Optional[float]:
    if run.trace is None:
        return None
    n, total = run.trace.program_time(PROGRAMS[program])
    return total / n * 1e3 if n else None


def _share(least: float, device: float) -> Optional[float]:
    return 100.0 * least / device if device > 0 and least > 0 else None


def roofline(run, kernel: str, program: str) -> Optional[float]:
    """Least time of a kernel's calls in ``program`` over their device
    time, in percent."""
    if run.trace is None or run.peaks is None:
        return None
    pk, sz = run.peaks, run.sizes
    _, device = run.trace.kernel_time(KERNELS[kernel], PROGRAMS[program])
    layers = sz["layers"]
    least = 0.0
    if kernel == "ternary_matmul":
        execs, _ = run.trace.program_time(PROGRAMS[program])
        m = run.slots if program == "decode" else run.slots * run.chunk
        per_exec = sum(p.layers * costs.least(*costs.ternary_call(m, p),
                                              pk["int8_ops_per_s"],
                                              pk["hbm_bytes_per_s"])
                       for p in run.projections)
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
    return _share(least, device)


def mfu(run, program: str) -> Optional[float]:
    """The step's model math at the chip's peaks (ternary matmuls at the
    int8 peak; attention over the valid prefixes and the lm_head at the
    bf16 peak) over the step program's device time, in percent."""
    if run.trace is None or run.peaks is None:
        return None
    pk, sz = run.peaks, run.sizes
    _, device = run.trace.program_time(PROGRAMS[program])
    per_token = costs.ternary_ops_per_token(run.projections)
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
    return _share(least, device)


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)

