"""GPipe-style pipeline parallelism via shard_map + ppermute.

The paper's system architecture (§V-B) maps Falcon3-1B as 6 macro
partitions × 3 layers with 6 input batches streamed through a 6-stage
pipeline at full macro utilization. This module is that schedule on a TPU
mesh axis: layer stack split into S stages (params sharded over the
``stage`` axis), microbatches streamed with lax.scan, hidden states handed
to the next stage with collective-permute. The bubble fraction is the
classic (S-1)/(T+S-1); with T = S = 6 the paper's configuration reaches
6/11 ≈ 55% per-round utilization in steady state and full utilization for
continuous streams.

Forward-only here matches the paper's inference deployment; jax.grad can
differentiate straight through ppermute for pipelined training.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.transformer import _attn_block_fwd


def reshape_to_stages(stacked_params, n_stages: int):
    """(L, ...) stacked block params -> (S, L/S, ...)."""

    def r(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])

    return jax.tree.map(r, stacked_params)


def make_pipeline_forward(cfg: ModelConfig, mesh, n_stages: int, n_micro: int,
                          axis: str = "stage", mode: str = "qat"):
    """Returns pipelined(staged_params, x (n_micro, mb, s, d)) -> (n_micro, mb, s, d).

    ``staged_params``: block params reshaped (S, L/S, ...), sharded over
    ``axis`` on dim 0. x holds the embedded microbatch inputs; outputs are
    the last stage's hidden states per microbatch.
    """

    def stage_fn(stage_params, h, positions):
        def body(carry, bp):
            out, _, _ = _attn_block_fwd(bp, carry, cfg, mode, positions)
            return out, None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    def pipelined_local(staged_params, x):
        # shapes inside shard_map: staged_params (1, L/S, ...); x (n_micro, mb, s, d)
        sp = jax.tree.map(lambda a: a[0], staged_params)
        idx = jax.lax.axis_index(axis)
        mb, s, d = x.shape[1], x.shape[2], x.shape[3]
        positions = jnp.arange(s, dtype=jnp.int32)
        pad = jnp.zeros((n_stages - 1, mb, s, d), x.dtype)
        stream = jnp.concatenate([x, pad], axis=0)  # (T, mb, s, d)

        def step(h_prev, x_t):
            inp = jnp.where(idx == 0, x_t, h_prev)
            out = stage_fn(sp, inp, positions)
            h_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return h_next, out

        h0 = jnp.zeros((mb, s, d), x.dtype)
        _, outs = jax.lax.scan(step, h0, stream)  # (T, mb, s, d) per stage
        # microbatch t leaves the last stage at step t + S - 1
        final = jax.lax.dynamic_slice_in_dim(outs, n_stages - 1, n_micro, axis=0)
        return final[None]  # (1, n_micro, mb, s, d) per stage

    fn = shard_map(
        pipelined_local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )

    def pipelined(staged_params, x):
        outs = fn(staged_params, x)  # (S, n_micro, mb, s, d)
        return outs[-1]  # only the last stage's slice is meaningful

    return pipelined


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1) / (T + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
