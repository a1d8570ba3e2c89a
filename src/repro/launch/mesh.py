"""Production mesh definitions.

A function, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 device; only dryrun.py forces 512).

Two families live here:

* the training/dryrun meshes (``make_production_mesh`` / ``make_host_mesh``),
  kept from the transformer substrate;
* the solver's 1-D **system-batch mesh** (``make_solver_mesh``) that the
  batched repeated-solve engine shards over — the K independent systems of
  ``factor_batched`` / ``solve_batched`` / ``solve_sequence`` are
  embarrassingly parallel, so a single data axis is the whole story — plus
  ``ensure_devices``, which gives a script N devices: the chips of a TPU
  host, or N virtual CPU devices (``ensure_virtual_cpu_devices``) so that
  tests and CI exercise multi-device sharding on one CPU host.
"""
from __future__ import annotations

import os

import jax

#: mesh axis name the batched solver shards the system-batch dimension over
BATCH_AXIS = "systems"


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over however many (fake or real) devices exist — used by
    distributed smoke tests."""
    n = len(jax.devices())
    data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def make_solver_mesh(n_devices: int | None = None, axis: str = BATCH_AXIS):
    """1-D mesh over the system-batch axis of the batched solver.

    ``n_devices=None`` takes every visible device; an int takes the first
    ``n_devices`` (so a sweep over device counts on one host is just
    ``make_solver_mesh(1), make_solver_mesh(2), ...``).  The returned mesh
    is what ``HyluOptions.mesh`` accepts directly — passing an int there
    routes through this helper."""
    import numpy as np

    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if not 1 <= n <= len(devs):
        platform = devs[0].platform
        hint = (f"force virtual devices with launch.mesh.ensure_devices({n})"
                " (or XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n}) before jax initializes its backend"
                if platform == "cpu" else
                f"this {platform} host has no more chips to shard over")
        raise ValueError(
            f"make_solver_mesh: asked for {n} devices but {len(devs)} "
            f"{platform} devices are visible — {hint}")
    return jax.sharding.Mesh(np.asarray(devs[:n]), (axis,))


def _request_virtual_cpu_devices(n: int) -> None:
    """Ask for ``n`` virtual CPU devices if no jax backend has started
    yet (XLA reads the flag once, when the CPU backend initializes)."""
    try:
        from jax._src import xla_bridge as _xb
        initialized = bool(getattr(_xb, "_backends", None))
    except Exception:                        # private API moved: probe hard
        initialized = True
    if not initialized:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()


def ensure_devices(n: int) -> int:
    """At least ``n`` devices for :func:`make_solver_mesh`: the chips of an
    accelerator host, else ``n`` virtual CPU devices.

    Run it before anything touches jax devices.  The virtual-device flag
    it sets shapes only the CPU platform, so on an accelerator host it
    changes nothing and the real chips are used; only when the default
    platform is ``cpu`` does :func:`ensure_virtual_cpu_devices` apply.
    Returns the default platform's device count; raises if it has fewer
    than ``n``."""
    n = int(n)
    _request_virtual_cpu_devices(n)
    devs = jax.devices()
    if devs[0].platform == "cpu":
        return ensure_virtual_cpu_devices(n)
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices but this {devs[0].platform} "
                           f"host has {len(devs)}")
    return len(devs)


def ensure_virtual_cpu_devices(n: int) -> int:
    """Force ≥ ``n`` virtual CPU devices (the multi-device test/CI harness).

    XLA reads ``--xla_force_host_platform_device_count`` exactly once, when
    the CPU backend initializes — so this must run before anything touches
    ``jax.devices()`` / puts an array on device.  Returns the resulting
    device count; raises if the backend already initialized with fewer
    devices than requested (the caller should set ``XLA_FLAGS`` in the
    environment, or run in a subprocess — see tests/test_sharding.py)."""
    n = int(n)
    _request_virtual_cpu_devices(n)
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"need {n} devices but jax initialized with {have}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "importing/using jax (e.g. in a fresh subprocess)")
    return have
