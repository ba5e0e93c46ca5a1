"""Host time per traced serving-loop iteration in the engine's phases
that neither feed nor wait for the device (``engine.*`` spans other than
``dispatch`` and ``sync``): the host work that leaves the chip idle."""

from bench.lib import regions


def read(run):
    sc = regions.of_run(run)
    phases = regions.phase_seconds(sc) if sc else None
    if not phases:
        return None
    return 1e3 * sum(s for name, s in phases.items()
                     if name not in regions.DEVICE_PHASES)
