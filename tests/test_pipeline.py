"""Pipeline parallelism (GPipe via shard_map): exactness vs plain forward.

Needs >1 local device, so the heavy check runs in a subprocess with
XLA_FLAGS set before jax imports (the main pytest process keeps 1 device).
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.distributed.pipeline import bubble_fraction

ROOT = Path(__file__).resolve().parents[1]


def test_bubble_fraction():
    assert bubble_fraction(6, 6) == 5 / 11  # the paper's 6x6 configuration
    assert bubble_fraction(1, 8) == 0.0
    assert bubble_fraction(4, 28) < 0.1


@pytest.mark.slow
def test_pipeline_matches_plain_forward_subprocess():
    """Runs the falcon3 6-stage pipeline example, which asserts exactness."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "examples" / "pipeline_falcon3.py")],
        capture_output=True,
        text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": str(ROOT / "src")},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pipelined forward == plain forward" in r.stdout
