"""One module per model kind: all that the benchmark knows of a block.

A configuration file names its module with ``"model"``, as it names its
plain reference with ``"reference"``; ``Spec.model(conf)`` loads
``bench/models/<model>.py`` by path. Adding an architecture adds a module
here, and edits no file of ``bench/lib/``. A module provides:

``sizes(conf) -> dict``
    The widths its weights and costs need, from the configuration file.
    The harness reads ``vocab`` from it; the rest is the module's own.
``model_config(conf, control=None)``
    The program's ``ModelConfig``. ``control`` names one of
    ``bench.lib.model.CONTROLS`` to switch on (the check's control); any
    other name is refused.
``program_params(seed, conf)``
    The program's parameter tree, made on the device from the seed.
``layer_weights(key, layer, sizes)``
    One layer's weights, pure in the key (``bench.lib.model.seed_key``)
    and the layer, so that the program's build and the reference's
    layer-by-layer build agree. The reference rebuilds its weights from
    the module: these and any other seeded weights it makes.
``LEAF_REGIONS``
    The leaf scopes (``jax.named_scope``) that its program adds beyond
    the common ones of ``bench.lib.regions.COMMON_REGIONS``.
``KERNELS``
    For each kernel key that a metric reader names (``ternary_matmul``,
    ``flash_decode``, ``flash_prefill``), the trace names of the
    ``pallas_call`` operations that do that work for this model. A key it
    leaves out is a kernel it does not run: that roofline reads nothing.
``kernel_least(run, kernel, program) -> float``
    The least time, in seconds at the chip's peaks (``run.peaks``), of
    the calls of ``KERNELS[kernel]`` that the traced ``program`` made.
``math_least(run, program) -> float``
    The least time, in seconds at the chip's peaks, of the model math of
    the traced executions of ``program``. Model math is what each token
    needs: for a model with routed experts, the experts each token is
    routed to and the shared ones, never every expert.

The cells of a module whose program cannot page its cache (any attention
but full: latent, windowed, linear, state-space) name a traffic file with
``"paged": false``, and with ``"prefill_chunk": 0`` where the program
cannot chunk prefill into that cache either; ``bench/lib/traffic.py``
gives each layout's keys.

``run`` is the :class:`bench.lib.harness.Run` of one traced run: the
module's ``sizes`` (``run.sizes``), the shapes of the served tree
(``run.served``), the slots and chunk, the KV item size, the decode and
chunk calls made in the traced window (``run.calls``) and the trace.
"""
