"""Production mesh builders (a FUNCTION, never module-level state).

Meshes use ``AxisType.Auto`` axes: the model code places activations with
``with_sharding_constraint`` (repro.models.dist), which only Auto axes
accept, and ``jax.make_mesh`` would otherwise make Explicit ones.
"""
import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW = 50e9                     # B/s per link
