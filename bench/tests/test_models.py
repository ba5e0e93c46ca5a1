"""Model modules (``bench/models/``): the readings they give, and that a
new model kind is taken up by new files alone.

Every per-layer reader, and the roofline and model-math arithmetic behind
them for every kernel and program, reads on the recorded trace
``data/tiny_scoped.xplane.pb`` exactly what it read at commit
ca6b4568c3ab290cd0feb108a1a6582109907ed3, before the Llama arithmetic
moved into ``bench/models/llama_bitnet.py``: at the tiny test
configuration's sizes, and at falcon3-1b's and falcon3-7b's with
hand-made calls. A stub module written to a temporary directory is found
from a configuration and used by ``Spec.model``, the readers and the
region reduction; an unknown module name is refused with the missing
file's path; every module here provides the whole interface.

  JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench.lib import harness, readers, regions, trace  # noqa: E402
from bench.lib import spec as spec_lib  # noqa: E402
from bench.lib.model import CONTROLS, seed_key  # noqa: E402
from bench.lib.peaks import peaks  # noqa: E402

SEED = 2**31 + 7
# configuration, slots and prefill chunk of each pinned case
CASES = {
    "tiny": (DATA / "configs" / "tiny.json", 4, 32),
    "falcon3-1b": (ROOT / "bench" / "configs" / "falcon3-1b.json", 12, 128),
    "falcon3-7b": (ROOT / "bench" / "configs" / "falcon3-7b.json", 32, 128),
}
TRACED_METRICS = ("decode_step_ms.tok_s", "ternary_matmul_roofline.tok_s",
                  "flash_decode_roofline.tok_s", "decode_mfu.tok_s",
                  "device_idle_share.tok_s", "kv_write_ms.tok_s",
                  "unattributed_ms.tok_s", "iteration_host_ms.tok_s")
KERNEL_KEYS = ("ternary_matmul", "flash_decode", "flash_prefill")

# the readings at commit ca6b4568c3ab290cd0feb108a1a6582109907ed3
PINNED = {
    "tiny": {
        "decode_step_ms.tok_s": 0.14067351515150966,
        "ternary_matmul_roofline.tok_s": 1.6513576902285299,
        "flash_decode_roofline.tok_s": 1.9083843206474835,
        "decode_mfu.tok_s": 0.00678528756714689,
        "device_idle_share.tok_s": 99.5182296422221,
        "kv_write_ms.tok_s": 0.017768272727261517,
        "unattributed_ms.tok_s": 0.05519281818183667,
        "iteration_host_ms.tok_s": 4.781317999999979,
        "roofline.ternary_matmul.decode": 1.6513576902285299,
        "roofline.ternary_matmul.chunk": 10.483546546866615,
        "roofline.flash_decode.decode": 1.9083843206474835,
        "roofline.flash_decode.chunk": None,
        "roofline.flash_prefill.decode": None,
        "roofline.flash_prefill.chunk": 0.8650559563050882,
        "mfu.decode": 0.00678528756714689,
        "mfu.chunk": 0.06055255073501145,
    },
    "falcon3-1b": {
        "decode_step_ms.tok_s": 0.14067351515150966,
        "ternary_matmul_roofline.tok_s": 1310.2996157137331,
        "flash_decode_roofline.tok_s": 515.8526394509059,
        "decode_mfu.tok_s": 13.417257167029774,
        "device_idle_share.tok_s": 99.5182296422221,
        "kv_write_ms.tok_s": 0.017768272727261517,
        "unattributed_ms.tok_s": 0.05519281818183667,
        "iteration_host_ms.tok_s": 4.781317999999979,
        "roofline.ternary_matmul.decode": 1310.2996157137331,
        "roofline.ternary_matmul.chunk": 24840.574656586767,
        "roofline.flash_decode.decode": 515.8526394509059,
        "roofline.flash_decode.chunk": None,
        "roofline.flash_prefill.decode": None,
        "roofline.flash_prefill.chunk": 229.5978265412044,
        "mfu.decode": 13.417257167029774,
        "mfu.chunk": 182.8829394485886,
    },
    "falcon3-7b": {
        "decode_step_ms.tok_s": 0.14067351515150966,
        "ternary_matmul_roofline.tok_s": 8111.552694615826,
        "flash_decode_roofline.tok_s": 3224.406148165988,
        "decode_mfu.tok_s": 159.24036932342244,
        "device_idle_share.tok_s": 99.5182296422221,
        "kv_write_ms.tok_s": 0.017768272727261517,
        "unattributed_ms.tok_s": 0.05519281818183667,
        "iteration_host_ms.tok_s": 4.781317999999979,
        "roofline.ternary_matmul.decode": 8111.552694615826,
        "roofline.ternary_matmul.chunk": 388984.9986964771,
        "roofline.flash_decode.decode": 3224.406148165988,
        "roofline.flash_decode.chunk": None,
        "roofline.flash_prefill.decode": None,
        "roofline.flash_prefill.chunk": 405.2385840670434,
        "mfu.decode": 159.24036932342244,
        "mfu.chunk": 1054.6457504135024,
    },
}


def calls(slots: int, chunk: int) -> dict:
    """Hand-made decode and chunk calls of a traced window."""
    return {"decode": [[64 + 37 * i + 11 * j for j in range(slots)]
                       for i in range(6)],
            "chunk": [([(0, chunk), (3 * chunk, chunk // 2)], 1),
                      ([(chunk, chunk)], 0), ([(5 * chunk + 3, 17)], 1)]}


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """The recorded trace where ``run_cell`` leaves a run's, reduced."""
    dst = tmp_path / "trace" / "x.xplane.pb"
    dst.parent.mkdir()
    shutil.copy(DATA / "tiny_scoped.xplane.pb", dst)
    monkeypatch.setattr(regions, "RUN_TRACE_DIR", dst.parent)
    return trace.reduce(dst, harness.SPAN_NAMES)


def make_run(model, conf, slots, chunk, tr, served=None):
    if served is None:
        from repro.models.pack import pack_params

        # the tree the engine serves: the program's, fused as it packs it
        served = jax.eval_shape(lambda: pack_params(
            model.program_params(SEED, conf), model.model_config(conf)))
    return harness.Run(
        cell="pinned", records=[], window={}, setup_s=0.0, model=model,
        sizes=model.sizes(conf), served=served, slots=slots, chunk=chunk,
        kv_itemsize=2, trace=tr, calls=calls(slots, chunk),
        peaks=peaks("TPU v5 lite"))


def readings(run) -> dict:
    spec = spec_lib.Spec()
    out = {m: spec.reader(m)(run) for m in TRACED_METRICS}
    for kernel in KERNEL_KEYS:
        for program in readers.PROGRAMS:
            out[f"roofline.{kernel}.{program}"] = readers.roofline(
                run, kernel, program)
    for program in readers.PROGRAMS:
        out[f"mfu.{program}"] = readers.mfu(run, program)
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_readings_are_pinned(case, traced):
    path, slots, chunk = CASES[case]
    conf = json.loads(path.read_text())
    model = spec_lib.Spec().model(conf)
    assert readings(make_run(model, conf, slots, chunk, traced)) == (
        PINNED[case])


STUB = '''
from __future__ import annotations

import dataclasses

LEAF_REGIONS = ("router",)
KERNELS = {"expert_matmul": ("ternary_matmul_actq_pallas",)}


@dataclasses.dataclass(frozen=True)
class Routed:
    top_k: int


def sizes(conf):
    return {"layers": 1, "vocab": conf["vocab_size"]}


def model_config(conf, control=None):
    raise NotImplementedError


def program_params(seed, conf):
    raise NotImplementedError


def layer_weights(key, layer, s):
    raise NotImplementedError


def kernel_least(run, kernel, program):
    return 2.5e-5


def math_least(run, program):
    return 7.5e-5
'''


def test_new_model_kind_needs_files_only(tmp_path, monkeypatch, traced):
    """A module written beside no other file, named by a configuration,
    is what ``Spec.model``, the readers and the regions use."""
    models = tmp_path / "models"
    models.mkdir()
    (models / "stub_moe.py").write_text(STUB)
    monkeypatch.setattr(spec_lib, "MODELS", models)
    conf = {"name": "stub", "model": "stub_moe", "vocab_size": 64}
    stub = spec_lib.Spec().model(conf)
    assert stub.__file__ == str(models / "stub_moe.py")

    run = make_run(stub, conf, 4, 32, traced, served={})
    step = traced.program_time(readers.PROGRAMS["decode"])[1]
    _, kernel_s = traced.kernel_time(("ternary_matmul_actq_pallas",),
                                     readers.PROGRAMS["decode"])
    assert readers.roofline(run, "expert_matmul", "decode") == (
        100.0 * 2.5e-5 / kernel_s)
    assert readers.mfu(run, "decode") == 100.0 * 7.5e-5 / step
    # a kernel this model does not have reads nothing
    assert readers.roofline(run, "ternary_matmul", "decode") is None

    op = "jit(step)/layers/while/body/mlp/router/dot_general"
    assert regions.region(op, stub.LEAF_REGIONS) == "router"
    assert regions.region(op) == regions.UNATTRIBUTED
    # the run's split has the common regions and the stub's only: the
    # Llama block's projections and MLP fall to unattributed
    split = regions.step_split(regions.of_run(run))
    assert set(split) - {regions.UNATTRIBUTED, "idle", "step",
                         "executions"} <= set(regions.COMMON_REGIONS)
    from bench.models import llama_bitnet

    tiny = json.loads(CASES["tiny"][0].read_text())
    llama = make_run(llama_bitnet, tiny, 4, 32, traced, served={})
    assert "mlp" in regions.step_split(regions.of_run(llama))
    read = spec_lib.Spec().reader("unattributed_ms.tok_s")
    assert read(run) > read(llama)


@pytest.mark.parametrize("conf", [{"name": "x", "model": "no_such_model"},
                                  {"name": "x"}], ids=["unknown", "missing"])
def test_unknown_model_names_the_missing_file(conf):
    with pytest.raises(FileNotFoundError) as err:
        spec_lib.Spec().model(conf)
    assert str(spec_lib.MODELS / f"{conf.get('model')}.py") in str(err.value)


def _configs_naming(model: str):
    paths = sorted((ROOT / "bench" / "configs").glob("*.json")) + sorted(
        (DATA / "configs").glob("*.json"))
    confs = [json.loads(p.read_text()) for p in paths]
    return [c for c in confs if c.get("model") == model]


MODULES = sorted(p.stem for p in spec_lib.MODELS.glob("*.py")
                 if p.stem != "__init__")


@pytest.mark.parametrize("name", MODULES)
def test_every_module_has_the_interface(name):
    confs = _configs_naming(name)
    assert confs, f"no configuration names model {name!r}"
    mod = spec_lib.Spec().model(confs[0])
    for fn in ("sizes", "model_config", "program_params", "layer_weights",
               "kernel_least", "math_least"):
        assert callable(getattr(mod, fn)), fn
    assert isinstance(mod.LEAF_REGIONS, tuple)
    assert all(isinstance(r, str) for r in mod.LEAF_REGIONS)
    assert not set(mod.LEAF_REGIONS) & set(regions.COMMON_REGIONS)
    assert mod.KERNELS and all(
        names and all(isinstance(n, str) for n in names)
        for names in mod.KERNELS.values())
    for conf in confs:
        s = mod.sizes(conf)
        assert s["vocab"] == conf["vocab_size"]
        for control in (None,) + CONTROLS:
            assert mod.model_config(conf, control).vocab_size == s["vocab"]
        with pytest.raises(ValueError, match="unknown control"):
            mod.model_config(conf, "no_such_control")
        tree = jax.eval_shape(lambda: mod.program_params(SEED, conf))
        assert jax.tree.leaves(tree)
        layer = jax.eval_shape(lambda: mod.layer_weights(seed_key(SEED), 0, s))
        assert jax.tree.leaves(layer)
