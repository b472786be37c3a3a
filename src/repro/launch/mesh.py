"""Mesh construction (production TPU v5e shapes + host meshes for tests).

single pod : (data=16, model=16)           = 256 chips
multi-pod  : (pod=2, data=16, model=16)    = 512 chips

``make_production_mesh`` is a function (never a module-level constant) so
importing this module does not touch jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import numpy as np


def _make_mesh(shape, axes):
    """jax.make_mesh with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """An AbstractMesh (no devices needed).

    Abstract meshes carry only axis structure — enough for
    ``resolve_spec``/``specs_for`` — so sharding layouts can be planned on
    machines without the target device count.
    """
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(names))


def make_production_mesh(*, multi_pod: bool = False):
    """The paper-scale mesh: one or two TPU v5e pods (see module doc)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """(data, model) mesh over whatever devices exist (tests / CPU examples).

    All local devices participate; ``model_parallel`` of them form the
    ``model`` axis and the rest fan out over ``data``.
    """
    n = len(jax.devices())
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} must be a positive divisor of "
            f"the device count ({n} available)"
        )
    return _make_mesh((n // model_parallel, model_parallel), ("data", "model"))


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """Parse a ``--mesh`` string like ``"data=4,model=2"`` into axis sizes.

    Axis order in the string is preserved (it becomes the mesh axis order);
    sizes must be positive integers.
    """
    out: Dict[str, int] = {}
    for item in spec.split(","):
        name, eq, val = item.strip().partition("=")
        if not eq or not name:
            raise ValueError(
                f"bad mesh axis {item!r} in {spec!r}; expected name=size"
            )
        try:
            size = int(val)
        except ValueError:
            raise ValueError(f"mesh axis {name!r} size {val!r} is not an int")
        if size < 1:
            raise ValueError(f"mesh axis {name!r} size must be >= 1, got {size}")
        if name in out:
            raise ValueError(f"duplicate mesh axis {name!r} in {spec!r}")
        out[name] = size
    return out


def make_mesh_from_spec(spec: str):
    """Build a host mesh from a ``--mesh`` string (e.g. ``"data=8,model=1"``).

    Uses the first ``prod(sizes)`` local devices, so a subset mesh (fewer
    devices than available) is allowed; asking for more than exist raises a
    ``ValueError`` naming the device count.
    """
    axes = parse_mesh_spec(spec)
    names = tuple(axes)
    shape = tuple(axes.values())
    n_need = int(np.prod(shape))
    devices = jax.devices()
    if n_need > len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {n_need} devices but only "
            f"{len(devices)} are available"
        )
    if n_need == len(devices):
        return _make_mesh(shape, names)
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices[:n_need]).reshape(shape), names)
