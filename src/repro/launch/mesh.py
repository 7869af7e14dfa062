"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips (one v5e pod).
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis is pure data parallelism whose gradient sync crosses the inter-pod DCN
(the axis dist/compression.py targets with int8 error-feedback exchange).

Functions, not module constants: importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import os

import jax


def force_host_device_count(n: int) -> None:
    """Best-effort: expose >= ``n`` host CPU devices for serving meshes.

    Appends ``--xla_force_host_platform_device_count`` to XLA_FLAGS —
    effective only BEFORE the first jax backend initialization (call it
    at the top of a launcher main(), as tests/conftest.py does for
    pytest). A no-op when the flag is already set."""
    if n <= 1:
        return
    flag = "--xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    if flag not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}={n}".strip()


def parse_mesh_shape(spec: str):
    """Parse a ``--mesh-shape`` string into (data, model) sizes.

    Accepts a bare model-axis size ("8" -> data=1, model=8) or an
    explicit "DATAxMODEL" / "DATA,MODEL" pair ("2x4" -> data=2, model=4).

    >>> parse_mesh_shape("8")
    (1, 8)
    >>> parse_mesh_shape("2x4")
    (2, 4)
    """
    parts = [int(p) for p in spec.lower().replace("x", ",").split(",") if p]
    if not parts or any(p < 1 for p in parts) or len(parts) > 2:
        raise ValueError(f"mesh shape {spec!r}: expected 'MODEL' or "
                         "'DATAxMODEL' with positive sizes")
    if len(parts) == 1:
        return 1, parts[0]
    return parts[0], parts[1]


def make_serve_mesh(spec: str):
    """Build the ("data", "model") serving mesh for a --mesh-shape value.

    Forces enough host CPU devices first (no-op once jax initialized or
    on real accelerator backends with sufficient devices)."""
    data, model = parse_mesh_shape(spec)
    force_host_device_count(data * model)
    return make_host_mesh(data=data, model=model)


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto-typed: the sharding layer
    places arrays with NamedShardings and ``with_sharding_constraint`` and
    lets GSPMD propagate the rest, which Explicit axes (the installed JAX's
    default) would turn into per-op sharding-type errors."""
    from jax.sharding import AxisType
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2, pod: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def single_device_mesh():
    return make_mesh((1, 1), ("data", "model"))
