"""Device time per decode-step execution in operations that no leaf
region of the step claims: the layer scan's own slicing and restacking
of its stacked operands, and copies XLA inserts with empty metadata."""

from bench.lib import regions


def read(run):
    sc = regions.of_run(run)
    split = regions.step_split(sc) if sc else None
    return split.get(regions.UNATTRIBUTED, 0.0) * 1e3 if split else None
