"""The paper's 6-partition pipeline (§V-B) as a GPipe schedule on a device mesh.

BitROM maps Falcon3-1B as 6 macro partitions x 3 layers and streams 6
batches through them. Here: a reduced falcon3 config with its layer stack
split into 6 stages over 6 placeholder devices, microbatches handed along
with collective-permute. Verifies the pipelined forward matches the plain
forward exactly and reports the bubble fraction.

NOTE: sets XLA_FLAGS for 8 host devices — run standalone, not under pytest.
Run:  PYTHONPATH=src python examples/pipeline_falcon3.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.distributed import pipeline as pp  # noqa: E402
from repro.launch.mesh import _make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.transformer import _attn_block_fwd  # noqa: E402

N_STAGES = 6
N_MICRO = 6  # the paper's 6 pipelined batches


def main() -> None:
    cfg = get_smoke_config("falcon3-1b")
    cfg = dataclasses.replace(cfg, n_layers=N_STAGES * 3)  # 6 partitions x 3 layers
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    mesh = _make_mesh((N_STAGES,), ("stage",))
    staged = pp.reshape_to_stages(params["blocks"], N_STAGES)
    # mode="none": scheduling exactness check without fake-quant rounding
    fwd = pp.make_pipeline_forward(cfg, mesh, N_STAGES, N_MICRO, axis="stage", mode="none")

    mb, s, d = 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (N_MICRO, mb, s, d)) * 0.3

    with mesh:
        out = fwd(staged, x)  # (n_micro, mb, s, d)

    # reference: run each microbatch through the plain (unpipelined) stack
    positions = jnp.arange(s, dtype=jnp.int32)

    def plain(h):
        def body(carry, bp):
            out, _, _ = _attn_block_fwd(bp, carry, cfg, "none", positions)
            return out, None

        h, _ = jax.lax.scan(body, h, params["blocks"])
        return h

    ref = jax.vmap(plain)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    print(f"pipelined forward == plain forward across {N_MICRO} microbatches")
    print(f"stages={N_STAGES} microbatches={N_MICRO} "
          f"bubble={100*pp.bubble_fraction(N_STAGES, N_MICRO):.1f}% "
          f"(paper's 6x6 edge configuration)")


if __name__ == "__main__":
    main()
