"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader gets the :class:`~bench.lib.harness.Run` of one run and returns
a number, or None where the run holds nothing to read (a host metric in a
cell with no such requests, a trace metric in an untraced run, a kernel
that never ran in the traced window or that the run's model does not
have). What a kernel's calls and the step's model math need at the
least comes from the run's model module (``bench/models/``).
"""

from __future__ import annotations

from typing import Optional

# what the trace calls each jitted program of the engine
PROGRAMS = {"decode": "step", "chunk": "chunk_step"}


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
    names = run.model.KERNELS.get(kernel)
    if not names:
        return None
    _, device = run.trace.kernel_time(names, PROGRAMS[program])
    return _share(run.model.kernel_least(run, kernel, program), device)


def mfu(run, program: str) -> Optional[float]:
    """The step's model math at the chip's peaks over the step program's
    device time, in percent."""
    if run.trace is None or run.peaks is None:
        return None
    _, device = run.trace.program_time(PROGRAMS[program])
    return _share(run.model.math_least(run, program), device)


def idle_share(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
