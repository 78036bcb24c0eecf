"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Production targets:
  single-pod: (16, 16)   = 256 chips, axes (data, model)
  multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model)
The ``pod`` axis defaults to extra data parallelism; ``pod_role=
"pipeline"`` uses it as a 2-stage pipeline axis (dist/pipeline.py).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the serving/training code places values with
    # with_sharding_constraint, which explicit axes would refuse
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires forced host device count)."""
    return _make_mesh(shape, axes)


def make_tp_mesh(tp: int) -> jax.sharding.Mesh:
    """1-D tensor-parallel serving mesh over the ``model`` axis.

    The serving engines (``--tp N``) tile each MVM across ``tp`` devices
    and close row-sharded contractions with an exact integer psum
    (``dist.sharding.serve_param_specs``).  Needs ``tp`` visible
    devices; on CPU force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = len(jax.devices())
    if tp > n:
        raise ValueError(
            f"--tp {tp} needs {tp} devices but only {n} are visible; on "
            f"CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{tp} (or more) before the process starts")
    return _make_mesh((tp,), ("model",))
