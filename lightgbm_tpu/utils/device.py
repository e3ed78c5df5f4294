"""The one platform probe.

Every dispatcher that picks between a Pallas TPU kernel and its XLA
reference (ops/histogram.py, ops/leafhist.py, ops/ordered_grow.py,
ops/pallas_walk.py via serve/forest.py) asks here — through the module
attribute (``device.on_tpu()``), so a compile rehearsal for a described
chip steers all of them by replacing this one function.
"""

from __future__ import annotations


def on_tpu() -> bool:
    """True when jax dispatches to a TPU backend.  A backend that cannot
    initialise raises out of here: training on the scatter reference
    because the chip failed to come up must never happen silently."""
    import jax
    return jax.default_backend() == "tpu"
