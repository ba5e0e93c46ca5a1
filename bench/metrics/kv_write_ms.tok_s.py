"""Device time per decode-step execution in the KV cache's appends: the
operations whose op_name path holds the ``kv_write`` region."""

from bench.lib import regions


def read(run):
    sc = regions.of_run(run)
    split = regions.step_split(sc) if sc else None
    return split["kv_write"] * 1e3 if split and "kv_write" in split else None
