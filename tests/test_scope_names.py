"""Names a profiler trace is read by (docs/serving.md, "Observability").

The benchmark finds the decode and chunk steps by their jitted
functions' names (XLA modules ``jit_step``, ``jit_chunk_step``) and
splits a step's device time by the named regions on each operation's
op_name path (``bench/lib/regions.py`` keeps the same list). Lowering
the steps of a tiny paged engine on the CPU shows both, so a refactor
that renames a step or drops a region fails here, not in a chip run.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serving.engine import Engine

LEAF_REGIONS = ("embed", "qkv_proj", "kv_write", "attention", "o_proj",
                "mlp", "lm_head", "sample", "bookkeeping")


@pytest.fixture(scope="module")
def lowered():
    cfg = get_smoke_config("falcon3-1b")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, hot_cap=8, max_len=64, prefill_chunk=8,
                 paged=True, slots=2)
    ctx = eng.start_session([])
    n, c = 2, eng.prefill_chunk
    chunk_args = (jnp.zeros((n, c), jnp.int32), jnp.zeros((n,), jnp.int32),
                  jnp.zeros((n,), bool), jnp.zeros((n,), bool),
                  jnp.zeros((n,), jnp.int32), jax.random.PRNGKey(1))
    return {
        "step": ctx.step_fn.lower(eng.params, ctx.state),
        "chunk_step": eng._get_chunk_step().lower(eng.params, ctx.state,
                                                  *chunk_args),
    }


def _scopes(lowered_fn):
    """Every scope on an op_name location of the lowered module."""
    text = lowered_fn.as_text(debug_info=True)
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


@pytest.mark.parametrize("program", ["step", "chunk_step"])
def test_module_name(lowered, program):
    text = lowered[program].as_text()
    assert re.search(rf"module @jit_{program}\b", text)


@pytest.mark.parametrize("program", ["step", "chunk_step"])
@pytest.mark.parametrize("scope", LEAF_REGIONS + ("layers",))
def test_region_in_op_names(lowered, program, scope):
    assert scope in _scopes(lowered[program])


def test_regions_are_leaves(lowered):
    """No leaf region holds another: each operation has one region."""
    for program in ("step", "chunk_step"):
        text = lowered[program].as_text(debug_info=True)
        for loc in re.findall(r'loc\("([^"]*)"', text):
            parts = loc.split("/")
            assert sum(p in LEAF_REGIONS for p in parts) <= 1, loc
