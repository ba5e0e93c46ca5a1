"""What every model module under ``bench/models/`` makes its weights from.

A configuration's weights are random and come from the seed alone:
``seed_key`` turns the seed into a PRNG key, and ``draw_trits`` draws a
projection's ternary trits from it in the share of zeros that absmean
rounding gives a Gaussian weight. ``CONTROLS`` names the program's own
lower-precision paths that the check's control switches on. The module
that maps a configuration's blocks onto the program is named by the
file's ``"model"`` key (``bench/models/__init__.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# share of +1 and of -1 trits: what absmean rounding gives a Gaussian
# weight (P(|z| < 0.399) = 0.31 zeros), in 256ths of a random byte
TRIT_ONE_BELOW = 88
TRIT_MINUS_FROM = 168

# lower-precision paths of the program that the check's control switches on:
# int4 activations where the configuration states int8 (BitNet a4.8), and
# fp8 (e4m3) KV-cache tiers where it states bf16
CONTROLS = ("act_int4", "kv_fp8")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw_trits(key: jax.Array, shape) -> jax.Array:
    """Ternary trits (int8 in {-1, 0, 1}) of ``shape``, pure in ``key``."""
    r = jax.random.bits(key, shape, jnp.uint8)
    return (jnp.where(r < TRIT_ONE_BELOW, 1, 0)
            - jnp.where(r >= TRIT_MINUS_FROM, 1, 0)).astype(jnp.int8)
