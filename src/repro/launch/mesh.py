"""Production mesh builders.

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init; tests use
small host meshes).

Axes:
  - ``pod``   (multi-pod only): outermost; composes with ``data`` for
    gradient reduction. Scaling to more pods = growing this axis.
  - ``data``  : data parallel / FSDP axis.
  - ``model`` : tensor/expert parallel axis (Megatron TP, MoE EP, and the
    sequence-parallel KV fallback for the 500k cells).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """(data, model) mesh over the first ``data * model`` local devices.

    Raises when fewer devices exist than the mesh asks for: a mesh that
    shrank to what is present would run a different program than the one
    requested and still pass.
    """
    n = len(jax.devices())
    if data < 1 or model < 1 or data * model > n:
        raise ValueError(
            f"a data={data} x model={model} mesh needs {data * model} "
            f"devices; {n} present")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
