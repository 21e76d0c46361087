"""Mesh factories (functions, never module-level constants — importing
this module must not touch jax device state).

Production target: TPU v5e pods of 256 chips in a 16×16 ICI torus.
Single-pod mesh (16, 16) = ("data", "model"); multi-pod adds a leading
"pod" axis over the data-center interconnect: (2, 16, 16).

``make_mesh_for`` is the elastic entry point: any chip count factors into
(pods, data, model) with the model axis held at the per-pod TP degree, so
scaling 256 → 4096 chips is a config change, not a code change (restore
from checkpoint and relaunch — sharding rules are mesh-shape agnostic).
"""
from __future__ import annotations

import os
import sys

import jax

__all__ = ["make_production_mesh", "make_mesh_for", "make_host_mesh",
           "ensure_host_device_count", "require_host_devices",
           "serving_devices"]


def ensure_host_device_count(n: int, module: str, argv) -> None:
    """Re-exec ``python -m module argv`` with the host CPU split into
    ``n`` XLA devices (the ``--host-devices`` knob of the serving
    launcher and benchmarks — a CPU-only multi-device demo).

    XLA fixes the device count at backend *initialization*, so the flag
    must be in the environment before the first jax computation; callers
    invoke this from their entry point before any timing/serving work,
    and ``require_host_devices`` checks the result once jax is up.
    No-op when ``n <= 0`` or the flag is already set (the re-exec'd
    child takes this branch).
    """
    if n <= 0 or "--xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()
    os.execv(sys.executable, [sys.executable, "-m", module] + list(argv))


def require_host_devices(n: int) -> None:
    """Refuse a ``--host-devices`` request jax cannot honour: the knob
    splits the host CPU, so on an accelerator backend it is an error,
    and on the CPU the split must have happened before jax started."""
    if n <= 0:
        return
    backend = jax.default_backend()
    if backend != "cpu":
        raise ValueError(f"--host-devices splits the host CPU into XLA "
                         f"devices; this host serves on {backend!r} — "
                         "drop the flag and mesh over its own devices")
    if len(jax.devices()) < n:
        raise ValueError(f"--host-devices {n} requested but jax sees "
                         f"{len(jax.devices())} device(s): the split only "
                         "applies when the launcher runs as `python -m` "
                         "before jax initializes")


def serving_devices(n: int) -> list:
    """The first ``n`` visible devices for a serving mesh; a request for
    more devices than this process sees raises (never shrinks)."""
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"a serving mesh of {n} devices needs {n}, but "
                         f"jax sees {len(devs)} {devs[0].platform} "
                         "device(s)")
    return devs[:n]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh_for(n_devices: int, model_parallel: int = 16,
                  chips_per_pod: int = 256):
    """Elastic mesh for any device count (1000+-node deployments)."""
    if n_devices <= chips_per_pod:
        data = n_devices // model_parallel
        if data == 0:
            return jax.make_mesh((1, n_devices), ("data", "model"))
        return jax.make_mesh((data, model_parallel), ("data", "model"))
    pods = n_devices // chips_per_pod
    data = chips_per_pod // model_parallel
    return jax.make_mesh((pods, data, model_parallel),
                         ("pod", "data", "model"))


def make_host_mesh():
    """Whatever this host has (tests / examples): (n, 1) data×model."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))
