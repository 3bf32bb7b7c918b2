"""Production mesh construction.

Defined as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; smoke tests and
benches see the default single device).
"""

from __future__ import annotations

import jax


def _make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes everywhere: the partitioner places what shard_map leaves
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods (512 chips).

    Axes: `data` (batch / FSDP), `model` (TP / EP / CAM rows); `pod`
    (multi-pod) acts as outer data parallelism + FSDP extension — gradient
    reduction over `pod` crosses the (slow) inter-pod links, which is
    where gradient compression applies (optim/compress.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(n_data: int | None = None, n_model: int | None = None):
    """Small mesh over whatever local devices exist (tests)."""
    n = len(jax.devices())
    if n_data is None or n_model is None:
        n_model = 1
        n_data = n
        for m in (4, 2):
            if n % m == 0:
                n_model = m
                n_data = n // m
                break
    return _make_mesh((n_data, n_model), ("data", "model"))
