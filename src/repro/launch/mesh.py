"""Production mesh construction (TPU v5e pods).

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run must set XLA flags first).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_disaggregated_meshes(
        prefill: tuple[int, int, int] = (1, 2, 2),
        decode: tuple[int, int, int] = (1, 2, 2),
) -> tuple[jax.sharding.Mesh, jax.sharding.Mesh]:
    """Split the device fleet into a prefill slice and a decode slice.

    Both meshes use the ``("pod", "data", "model")`` axis names so the
    ``MULTIPOD_SERVE`` rule set (``repro.dist``) applies verbatim on
    either slice — the KV ``cache_batch`` axis shards over
    ``("pod", "data")`` and weights over ``"model"`` exactly as on a
    single multi-pod mesh. The prefill slice takes the first
    ``prod(prefill)`` devices, the decode slice the next
    ``prod(decode)``; ``repro.serve.PagedServeEngine`` replicates params
    and compiled PIM plans to both and hands finished prefill blocks to
    the decode slice.
    """
    import numpy as np

    need_p = int(np.prod(prefill))
    need_d = int(np.prod(decode))
    devs = jax.devices()
    if len(devs) < need_p + need_d:
        raise ValueError(
            f"disaggregated serving needs {need_p}+{need_d} devices, "
            f"have {len(devs)}")
    axes = ("pod", "data", "model")
    mk = jax.sharding.Mesh
    return (mk(np.asarray(devs[:need_p]).reshape(prefill), axes),
            mk(np.asarray(devs[need_p:need_p + need_d]).reshape(decode),
               axes))


def make_test_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh over however many (fake) devices tests have."""
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
